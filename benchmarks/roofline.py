"""The yardstick of the roofline shares: the chip's peaks and the least
work one boosting iteration needs, from the configuration's shapes only.

Peaks: one TPU v5e chip, Google Cloud documentation ("TPU v5e" system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s. Copied from ``lightgbm_tpu/hostenv.py::_DEVICE_PEAKS`` without
its environment overrides. A device kind that is not in the table is an
error, never a default.

Work: what leaf-wise histogram GBDT needs for one tree of ``L`` leaves on
``N`` rows of ``F`` features in ``B`` bins, whatever implements it. It
never reads what the program did (passes, packing, padding), so it reads
the same before and after any optimisation, and a share above 100% is a
fault of this function.

- depth D = ceil(log2 L): a balanced tree of L leaves.
- rows touched by histogram builds per tree R = N (1 + (D - 1) / 2): the
  root reads every row; at each further level the smaller child of every
  split is built and the larger comes by subtraction, and the smaller
  children of one level hold at most half of the rows. The last level's
  children are never split, but their parents' histograms were needed.
- histogram bytes = R (F ceil(log2(B + 1)) / 8 + 8): a row's bins packed
  to the bits B + 1 values need, and its gradient and hessian as float32.
- histogram operations = R F B 2 2: the one-hot contraction that puts a
  row's (g, h) into one of B bins of each feature, a multiply and an add
  each. (A scatter formulation needs only R F 2 adds; the contraction is
  what an MXU does, and the byte bound is below both here.)
- the rest of an iteration reads the score and the label and writes the
  score: N 12 bytes; its operations are not counted.

Least time = max(bytes / peak bytes/s, operations / peak FLOP/s), each
bound reported beside the other. The operations' peak is the bfloat16
one, or the int8 one for a configuration that sums quantized gradients
(``use_quantized_grad``): the peak of the precision the configuration
states, not of what the program happens to run.
"""

from __future__ import annotations

import math

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 1.97e14, "int8_ops_per_s": 3.93e14,
                    "bytes_per_s": 8.19e11, "hbm_bytes": 16e9},
}


def device_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}): add its published peaks, "
                       "with their source, to benchmarks/roofline.py")
    return PEAKS[device_kind]


def rows_touched(n: int, leaves: int) -> float:
    depth = max(1, math.ceil(math.log2(leaves)))
    return n * (1.0 + (depth - 1) / 2.0)


def histogram_work(n: int, f: int, b: int, leaves: int) -> dict:
    """Bytes and operations of one tree's histogram builds."""
    r = rows_touched(n, leaves)
    bits = math.ceil(math.log2(b + 1))
    return {"bytes": r * (f * bits / 8.0 + 8.0),
            "ops": r * f * b * 2.0 * 2.0}


def iteration_work(n: int, f: int, b: int, leaves: int) -> dict:
    """Bytes and operations of one whole boosting iteration (one tree)."""
    work = histogram_work(n, f, b, leaves)
    return {"bytes": work["bytes"] + n * 12.0, "ops": work["ops"]}


def least_seconds(work: dict, peaks: dict, int8: bool = False) -> dict:
    """``int8``: the configuration sums int8 gradients, so its operations
    are held against the chip's int8 peak, twice the bfloat16 one."""
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    by_ops = work["ops"] / peaks["int8_ops_per_s" if int8 else "flops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "ops",
            "by_bytes_s": by_bytes, "by_ops_s": by_ops}
