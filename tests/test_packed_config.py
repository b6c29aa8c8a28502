"""The published Higgs GPU configuration at ``max_bin=15`` (benchmark
cell ``higgs-gpu15.train``, PR 35) at sizes a test can hold.

At 15 bins the program stores two rows a byte by default
(``tpu_bin_pack=auto``, ``ops/bin_pack.PackedBins``), and every fast path
reads bins another way than at 63: the histogram step once a bit-section,
the wave partition through ``bin_pack.unpack_rows``. The data is the
benchmark's own (``benchmarks/data.py``, seeded), 28 features, through
``lgb.train`` on the waved grower inside the fused iteration program.

- Packing is a re-encoding: the trees with it on and off are bit for bit
  the same, at row counts that are no multiple of the section, of
  ``PACK_ALIGN`` or of a row chunk.
- The system against the plain reference (``benchmarks/reference.py``,
  NumPy float64, imports nothing of the program): trees and scores of a
  15-bin run pass ``compare``; the same run with its two packed sections
  swapped (a planted fault in the storage: section 1's rows read as
  section 0's) does not.

None of these numbers is a device number: the CPU runs the XLA twin of
the kernels, whose sums are exact float32.
"""

import importlib.util
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import bin_pack
from tests.test_bin_pack import strip_params

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _bench_module(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(_BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_data = _bench_module("data")
reference = _bench_module("reference")

SEED = 818_105_170      # the driver's
FEATURES = 28
# the configuration's published parameters (benchmarks/configs/
# higgs-gpu15.json) at a test's number of leaves
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 15,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 100}


def _data(rows):
    return bench_data.make_data(rows, FEATURES, SEED,
                                {"generator": "gaussian-logit"})


def _train(x, y, rounds, **extra):
    bst = lgb.train({**PARAMS, "verbosity": -1, **extra},
                    lgb.Dataset(x, label=y), num_boost_round=rounds)
    gbdt = bst._gbdt
    # the waved grower inside the fused iteration program
    assert gbdt._use_waved() and gbdt._fast_path_ok(None)
    return bst


@pytest.mark.parametrize("rows", [20_000, 20_001, 32_768 + 5])
def test_trees_with_packing_on_and_off_are_bit_identical(rows):
    vpb = bin_pack.pack_vpb(PARAMS["max_bin"])
    section = -(-rows // vpb)
    assert vpb == 2 and section % bin_pack.PACK_ALIGN and rows % 2048
    x, y = _data(rows)
    packed = _train(x, y, 5)
    raw = _train(x, y, 5, tpu_bin_pack="off")
    bins = packed._gbdt.bins_fm
    assert isinstance(bins, bin_pack.PackedBins) and bins.vpb == 2
    assert bins.shape == (FEATURES, rows)
    assert bins.section == bin_pack.section_len(rows, vpb) > section
    assert not isinstance(raw._gbdt.bins_fm, bin_pack.PackedBins)
    assert packed.num_trees() == raw.num_trees() == 5
    assert strip_params(packed.model_to_string()) == \
        strip_params(raw.model_to_string())
    np.testing.assert_array_equal(np.asarray(packed._gbdt.scores),
                                  np.asarray(raw._gbdt.scores))


# What `compare` follows here, and the limits it is held to. The limits
# are loose CPU ones, not the cell's (those come from the chip's readings
# at 84M rows, PERF.md section 2): on the CPU the histograms are exact
# float32, so a sound run reads rounding only (1e-5 and under) on the
# value gaps, the counts and the scores; `split_gain_shortfall` reads the
# distance between the reference's 15-bin quantile grid and the
# program's (0.001 on this seed's four splits of 31-leaf trees, so 0.3 is
# loose; at 255 leaves one split in twelve reads over 0.3 for the grids
# alone, which is why the cell itself searches the root only: PERF.md
# section 2).
# The swapped sections read every number but the first two orders over.
ROWS = 20_000
CHECK = {"trees": 2, "score_rows": 5_000, "split_nodes": 4,
         "split_rows": ROWS}
LIMITS = {"trees_missing": 0, "leaf_count_gap": 1e-3,
          "leaf_value_gap": 1e-3, "median_leaf_value_gap": 1e-3,
          "split_gain_shortfall": 0.3, "score_gap": 1e-5,
          "min_hessian_shortfall": 0.01}


def _numbers(bst, x, y, rounds):
    scores = np.asarray(bst._gbdt.scores)[0][:len(y)]
    trees = reference.parse_model(bst.model_to_string())
    return reference.compare(reference.Whole(x, y), PARAMS, trees, scores,
                             rounds, CHECK, SEED, 2)


def _over(numbers):
    return {name for name in reference.NUMBERS
            if not numbers[name] <= LIMITS[name]}


def test_a_15_bin_run_passes_the_plain_reference():
    x, y = _data(ROWS)
    bst = _train(x, y, 3)
    assert isinstance(bst._gbdt.bins_fm, bin_pack.PackedBins)
    numbers = _numbers(bst, x, y, 3)
    assert not _over(numbers), numbers


def test_swapped_sections_fail_the_plain_reference(monkeypatch):
    """The planted fault: the packer puts section 1's rows into the low
    nibble and section 0's into the high one, so every reader takes a
    row's bins from the row one section away."""
    pack = bin_pack.pack_bins_host

    def swapped(bins_fm, max_bins):
        pb = pack(bins_fm, max_bins)
        assert pb.vpb == 2
        pb.data = (pb.data >> 4) | ((pb.data & 0xF) << 4)
        return pb

    monkeypatch.setattr(bin_pack, "pack_bins_host", swapped)
    x, y = _data(ROWS)
    bst = _train(x, y, 3)
    numbers = _numbers(bst, x, y, 3)
    over = _over(numbers)
    assert {"leaf_count_gap", "leaf_value_gap", "score_gap"} <= over, numbers
