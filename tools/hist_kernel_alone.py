"""Kernel-alone timing of the multi-leaf histogram kernels on the chip.

    chiprun -- python3 tools/hist_kernel_alone.py [--tree DIR] [--rows N]
        [--features F] [--kinds int8,fused] [--shares 1.0,0.5,0.25,0.1]
        [--max-bin B] [--pack auto|off] [--squeeze-stage rule|0..7]

One pass of the int8 kernel and of the gradient-fused float kernel over
synthetic bins (63 bins unless `--max-bin`, 42 slots) at the benchmark's
narrow shape (63,004,672 x 28) or another, for each live share of the
rows (the share whose leaf is one of the pass's slots), and the root's
pass (every row live, told at the call site). At 15 bins and fewer the
bins are bit-packed as the booster packs them (`ops/bin_pack`, on the
host, its seconds printed) unless `--pack off`. A reading is the
kernel's own device seconds from a profiler trace (the median of three
calls) beside the call's wall seconds (pads included), with the squeeze
the geometry chose (`squeeze_stage`, the network's first stage: 0 the
live rows to the chunk's front, 7 each of a vreg's 128 lane columns to
its own top; `--squeeze-stage` puts another in the rule's place, which is
how the rule's model was held against the chip: PERF.md section 6, PR
36). `--tree` points at another checkout
(a `git archive` of the parent, say) so that both sides of a change are
read on one machine, one process after the other; a checkout from before
PR 33 has one reading a kind (no live rows to tell). Prints one JSON line
a reading and appends it to chiprun_out/hist_kernel_alone.jsonl; at
2^20 rows the kernels are also held to their XLA twins. PERF.md section
6 (PR 33) has the readings this was written for."""
import argparse
import functools
import glob
import json
import os
import sys
import time

ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--tree", default=os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))
ap.add_argument("--rows", type=int, default=63_004_672)
ap.add_argument("--features", type=int, default=28)
ap.add_argument("--kinds", default="int8,fused")
ap.add_argument("--shares", default="1.0,0.5,0.25,0.1")
ap.add_argument("--max-bin", type=int, default=63)
ap.add_argument("--pack", choices=("auto", "off"), default="auto")
ap.add_argument("--squeeze-stage", default="rule",
                choices=("rule", *"01234567"))
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.tree))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from lightgbm_tpu.ops import bin_pack                         # noqa: E402
from lightgbm_tpu.ops import pallas_histogram as ph           # noqa: E402

N, F, B, SLOTS = args.rows, args.features, args.max_bin, 42
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "chiprun_out")
SQUEEZES = hasattr(ph, "_squeeze_lanes")
if args.squeeze_stage != "rule":
    ph._squeeze_stage = lambda *a, **k: int(args.squeeze_stage)


def binary_grad(score, label, weight):
    p = jax.nn.sigmoid(score)
    return p - label, p * (1.0 - p)


@functools.partial(jax.jit, static_argnames=("n", "f"))
def make(key, n, f):
    ks = jax.random.split(key, 5)
    bins = jax.random.randint(ks[0], (f, n), 0, B,
                              jnp.int32).astype(jnp.uint8)
    g = jax.random.randint(ks[1], (n,), -63, 64, jnp.int32).astype(jnp.int8)
    h = jax.random.randint(ks[2], (n,), 0, 64, jnp.int32).astype(jnp.int8)
    score = jax.random.normal(ks[3], (n,), jnp.float32)
    label = (jax.random.uniform(ks[4], (n,)) < 0.5).astype(jnp.float32)
    return bins, g, h, jnp.ones((n,), jnp.int8), score, label


@functools.partial(jax.jit, static_argnames=("n",))
def make_leaf(key, share, n):
    k1, k2 = jax.random.split(key)
    slot = jax.random.randint(k1, (n,), 0, SLOTS, jnp.int32)
    return jnp.where(jax.random.uniform(k2, (n,)) < share, slot, slot + 100)


def stored(bins):
    """The bins as the booster would hold them on the device: PackedBins
    where the bin count admits it and `--pack` allows (packed on the
    host by `pack_bins_host`), else as they are; and the pack's host
    seconds."""
    if args.pack == "off" or bin_pack.pack_vpb(B) == 1:
        return bins, None
    host = np.asarray(bins)
    t0 = time.perf_counter()
    packed = bin_pack.pack_bins_host(host, B)
    return bin_pack.to_device(packed), time.perf_counter() - t0


def kernel_seconds(fn, operands, reps=3):
    """(the kernel op's device seconds a call, wall seconds a call, the
    histogram)"""
    out = jax.block_until_ready(fn(*operands))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*operands))
    wall = (time.perf_counter() - t0) / reps
    trace_dir = os.path.join(OUT, "hist_kernel_alone_trace")
    os.system(f"rm -rf {trace_dir}")
    jax.profiler.start_trace(trace_dir)
    for _ in range(reps):
        jax.block_until_ready(fn(*operands))
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    durs = sorted(
        ev.duration_ns for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/device:TPU:") for line in plane.lines
        if line.name == "XLA Ops" for ev in line.events
        if "lgbm_hist" in ev.name)
    return (durs[len(durs) // 2] / 1e9 if durs else None), wall, out


def entry(kind, **kw):
    if kind == "int8":
        def fn(bins, g, h, w, score, label, rl, ids):
            return ph.hist_pallas_multi_int8(
                bins, jnp.stack([g, h, w], axis=1), rl, ids, max_bins=B,
                num_slots=SLOTS, **kw)
    else:
        def fn(bins, g, h, w, score, label, rl, ids):
            return ph.hist_pallas_multi_fused(
                bins, score, label, None, w.astype(jnp.float32), rl, ids,
                grad_fn=binary_grad, max_bins=B, num_slots=SLOTS,
                precise="default", **kw)
    return jax.jit(fn)


def emit(**rec):
    rec["tree"] = args.tree
    print(json.dumps(rec), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "hist_kernel_alone.jsonl"), "a") as fh:
        fh.write(json.dumps(rec) + "\n")


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"hist_kernel_alone.py times the chip; found {dev}")
    raw, *rest = make(jax.random.PRNGKey(7), N, F)
    bins, pack_s = stored(raw)
    data = (bins, *rest)
    vpb = getattr(bins, "vpb", 1)
    if pack_s is not None:
        emit(pack_bins_host_s=pack_s, rows=N, features=F, vpb=vpb,
             section=bins.section)
    ids = jnp.arange(SLOTS, dtype=jnp.int32)
    shares = [float(s) for s in args.shares.split(",")]
    leaves = {s: make_leaf(jax.random.PRNGKey(int(s * 1000)), s, N)
              for s in shares}
    for kind in args.kinds.split(","):
        base = dict(kind=kind, rows=N, features=F, max_bin=B, pack_factor=vpb,
                    device=dev.device_kind)
        if not SQUEEZES:
            s, wall, _ = kernel_seconds(entry(kind),
                                        (*data, leaves[shares[-1]], ids))
            emit(**base, live="any", kernel_s=s, wall_s=wall)
            continue
        geom = ph._fb_geometry(F, B, vpb, 1 if kind == "int8" else 2,
                               **({"rows": N} if vpb == 1
                                  else {"section": bins.section}))
        base["squeeze_stage"] = getattr(geom, "squeeze_stage", 0)
        s, wall, _ = kernel_seconds(entry(kind, all_live=True),
                                    (*data, jnp.zeros((N,), jnp.int32), ids))
        emit(**base, live="root", kernel_s=s, wall_s=wall,
             geometry=geom._asdict())
        fn = entry(kind)
        for share in shares:
            s, wall, _ = kernel_seconds(fn, (*data, leaves[share], ids))
            emit(**base, live=share, kernel_s=s, wall_s=wall)
    if SQUEEZES:
        n = min(N, 1 << 20)
        raw, g, h, w, score, label = (x[..., :n] for x in (raw, *rest))
        bins, _ = stored(raw)
        rl = leaves[shares[-1]][:n]
        gh = jnp.stack([g, h, w], axis=1)
        kw = dict(max_bins=B, num_slots=SLOTS)
        got = ph.hist_pallas_multi_int8(bins, gh, rl, ids, **kw)
        want = ph.hist_multi_int8_xla(raw, gh, rl, ids, **kw)
        emit(check="int8 against its XLA twin",
             equal=bool(jnp.array_equal(got, want)))
        gf, hf = binary_grad(score, label, None)
        one = jnp.ones_like(gf)
        got = ph.hist_pallas_multi_fused(
            bins, score, label, None, one, rl, ids, grad_fn=binary_grad,
            precise="highest", **kw)
        want = ph.hist_multi_xla(raw, jnp.stack([gf, hf, one], axis=1), rl,
                                 ids, **kw)
        emit(check="fused float against its XLA twin",
             max_abs_gap=float(jnp.max(jnp.abs(got - want))),
             largest=float(jnp.max(jnp.abs(want))))


main()
