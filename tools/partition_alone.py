"""Kernel-alone timing of the wave partition on the chip, both forms.

    python3 tools/partition_alone.py [--tree DIR] [--out DIR]
        [--shapes narrow,packed,wide,sections] [--waves 1,8,42] [--reps 5]

on a machine with a TPU. One wave of `ops/partition.apply_wave_splits`
over synthetic bins and leaves, in the XLA form (the row-sized fusions)
and as the Pallas kernel (`ops/pallas_partition`), at the benchmark
cells' shapes: `narrow` 63,000,000 x 28 uint8 at 63 bins, `packed`
84,000,000 x 28 at 15 bins (PackedBins, two rows a byte, packed on the
device here), `wide` 1,200,000 x 2000 at 63 bins, `sections` 18,000 x
1000 at 3 bins (four rows a byte: two row blocks a bit-section, the last
section empty, the grid's one shape where a step has no rows; not in
the default list); 255 leaves, the
wave's split leaves drawn from the 213 current ones and its last step
invalid, as in a full wave. A reading is a form's device seconds a wave
(the sum of the profiler's device events of `--reps` calls over the
calls, less the copy of the operand that the kernel's in-place output
costs where it is not donated; the median call's wall seconds beside
it), and whether the two forms' row_leaf agree bit for bit. Prints one
JSON line a reading and, with `--out`, appends it to
DIR/partition_alone.jsonl. `--tree` reads another checkout (a `git
archive` of the parent, say); a checkout without the kernel reads the
XLA form only. PERF.md section 6 has the readings this was written
for."""
import argparse
import functools
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--tree", default=os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))
ap.add_argument("--out", help="a directory for the JSON lines")
ap.add_argument("--shapes", default="narrow,packed,wide")
ap.add_argument("--waves", default="1,8,42")
ap.add_argument("--reps", type=int, default=5)
ap.add_argument("--rows", type=int, default=0,
                help="rows in place of each shape's own (a rehearsal)")
ap.add_argument("--max-block-rows", type=int, default=0,
                help="the kernel's largest row block in place of the "
                "module's (pallas_partition._MAX_BLOCK_ROWS)")
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.tree))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
from lightgbm_tpu.ops import partition as part_ops            # noqa: E402
from lightgbm_tpu.ops.bin_pack import (                       # noqa: E402
    PackedBins, pack_vpb, section_len)

SHAPES = {"narrow": (63_000_000, 28, 63), "packed": (84_000_000, 28, 15),
          "wide": (1_200_000, 2000, 63), "sections": (18_000, 1000, 3)}
L, LIVE = 255, 213
HAS_KERNEL = hasattr(part_ops, "wave_form")
if args.max_block_rows:
    from lightgbm_tpu.ops import pallas_partition
    pallas_partition._MAX_BLOCK_ROWS = args.max_block_rows


@functools.partial(jax.jit, static_argnames=("n", "f", "b"))
def make(key, n, f, b):
    k1, k2 = jax.random.split(key)
    bins = jax.random.randint(k1, (f, n), 0, b, jnp.int32).astype(jnp.uint8)
    leaf = jax.random.randint(k2, (n,), 0, LIVE, jnp.int32)
    return bins, leaf


@functools.partial(jax.jit, static_argnames=("n", "vpb"))
def pack(bins, n, vpb):
    """bin_pack.pack_bins_host's split-section layout, on the device."""
    sec = section_len(n, vpb)
    bits = 8 // vpb
    padded = jnp.pad(bins, ((0, 0), (0, vpb * sec - n)))
    data = jnp.zeros((bins.shape[0], sec), jnp.uint8)
    for v in range(vpb):
        data = data | (padded[:, v * sec:(v + 1) * sec] << (bits * v))
    return data


def wave(key, w, f, b):
    ks = jax.random.split(key, 5)
    valid = (jnp.arange(w) < w - 1) | (w == 1)
    return dict(
        leaf_ids=jax.random.permutation(ks[0], LIVE)[:w].astype(jnp.int32),
        right_ids=(LIVE + jnp.arange(w)).astype(jnp.int32),
        features=jax.random.randint(ks[1], (w,), 0, f, jnp.int32),
        thresholds=jax.random.randint(ks[2], (w,), 0, b - 1, jnp.int32),
        default_lefts=jax.random.bernoulli(ks[3], 0.5, (w,)),
        cat_masks=jnp.zeros((w, b), bool), valid=valid,
        num_bins=jnp.full((f,), b, jnp.int32),
        missing_type=jax.random.randint(ks[4], (f,), 0, 3, jnp.int32),
        is_categorical=jnp.zeros((f,), bool))


def form_fn(form):
    kw = {"form": form} if HAS_KERNEL else {}

    @jax.jit
    def fn(row_leaf, bins, wv):
        return part_ops.apply_wave_splits(
            row_leaf, bins, wv["leaf_ids"], wv["right_ids"], wv["features"],
            wv["thresholds"], wv["default_lefts"], wv["cat_masks"],
            wv["valid"], wv["num_bins"], wv["missing_type"],
            wv["is_categorical"], L, None, False, **kw)
    return fn


def device_seconds(fn, operands, reps):
    """(device seconds a call: every device event of `reps` calls, the
    median call's wall seconds, the output)"""
    out = jax.block_until_ready(fn(*operands))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        walls.append(time.perf_counter() - t0)
    trace_dir = tempfile.mkdtemp(prefix="partition-alone-")
    jax.profiler.start_trace(trace_dir)
    for _ in range(reps):
        jax.block_until_ready(fn(*operands))
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    ops = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops[ev.name] = (ops.get(ev.name, 0)
                                        + ev.duration_ns / 1e9 / reps)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return ops, sorted(walls)[reps // 2], out


def emit(**rec):
    rec["tree"] = args.tree
    print(json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "partition_alone.jsonl"),
                  "a") as fh:
            fh.write(json.dumps(rec) + "\n")


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rows:
        sys.exit(f"partition_alone.py times the chip; found {dev}")
    forms = ("xla", "kernel") if HAS_KERNEL else ("xla",)
    for shape in args.shapes.split(","):
        n, f, b = SHAPES[shape]
        n = args.rows or n
        raw, leaf = make(jax.random.PRNGKey(11), n, f, b)
        bins, vpb = raw, pack_vpb(b)
        if vpb > 1:
            bins = PackedBins(pack(raw, n, vpb), n, vpb)
        del raw
        for w in (int(x) for x in args.waves.split(",")):
            wv = wave(jax.random.PRNGKey(w), w, f, b)
            outs = {}
            for form in forms:
                ops, wall, outs[form] = device_seconds(
                    form_fn(form), (leaf, bins, wv), args.reps)
                # the kernel's in-place output costs a copy of row_leaf
                # here, where the operand is not donated; in the grower
                # it is an intermediate and nothing is copied
                copies = sum(t for k, t in ops.items()
                             if k.split(" = ")[0].startswith("%copy"))
                emit(shape=shape, rows=n, features=f, max_bin=b, waves=w,
                     form=form, max_block_rows=args.max_block_rows or None,
                     device_s=sum(ops.values()) - copies, copy_s=copies,
                     wall_s=wall,
                     ops=sorted(ops.items(), key=lambda kv: -kv[1])[:6],
                     moved=int(jnp.sum(outs[form] != leaf)),
                     device=dev.device_kind)
            if len(outs) == 2:
                emit(shape=shape, waves=w, check="kernel against the XLA form",
                     equal=bool(jnp.array_equal(outs["xla"],
                                                outs["kernel"])))
        del bins, leaf


main()
