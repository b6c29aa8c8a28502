"""`tpu_bin_pack` auto against off at a benchmark cell's own size, on the chip.

    chiprun --timeout 1500 -- bash -c "python3 tools/bin_pack_at_size.py \
        --setting auto && python3 tools/bin_pack_at_size.py --setting off \
        && python3 tools/bin_pack_at_size.py --compare"
    [--workload higgs-gpu15.train] [--seed N] [--iterations 9] [--quantized]

One process a setting (the device's peak memory is the process's): the
cell's data from the seed (`benchmarks/data.py`), binned, then one
`lgb.train` call with the configuration's published parameters and that
setting of `tpu_bin_pack`, `--iterations` trees. Every iteration is
stopped on the training scores; the first compiles and is left out of the
rate; the last runs under the profiler and gives the histogram kernels'
device seconds. Prints one JSON line (iterations a second over the whole
iterations between, `memory_peak_bytes`, the kernels' seconds, each
iteration's seconds) and keeps the model and the training scores under
chiprun_out/. `--compare` (no device) then says whether the two models
(less their echoed parameters) and scores are bit for bit the same, and
if not, how far apart: the first tree that differs, the trees whose
splits (feature, threshold, children) are the same, the widest gap
between two leaf values and between two scores. `--quantized` trains with
int8 gradients, whose histogram sums are exact integers in any order: the
setting under which the two storages must agree bit for bit on the chip
too. Lines are appended to
chiprun_out/bin_pack_at_size.jsonl. PERF.md section 6 (PR 35) has the
readings this was written for."""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--workload", default="higgs-gpu15.train")
ap.add_argument("--seed", type=int, default=818_105_170)
ap.add_argument("--iterations", type=int, default=9)
ap.add_argument("--rows", type=int, default=0, help="the cell's if 0")
ap.add_argument("--setting", choices=("auto", "off"))
ap.add_argument("--compare", action="store_true")
ap.add_argument("--quantized", action="store_true",
                help="int8 gradients (use_quantized_grad, 126 levels): "
                     "histogram sums are exact integers, whatever their order")
ap.add_argument("--any-device", action="store_true",
                help="rehearse where there is no chip")
args = ap.parse_args()
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    sys.path.insert(0, os.path.abspath(p))

import numpy as np                                            # noqa: E402
import data as bench_data                                     # noqa: E402
import manifest                                               # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out")


def strip_params(model: str) -> str:
    head, _, rest = model.partition("parameters:")
    return head + rest.partition("end of parameters")[2]


def kernel_seconds(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return sum(ev.duration_ns for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/device:TPU:")
               for line in plane.lines if line.name == "XLA Ops"
               for ev in line.events if "lgbm_hist" in ev.name) / 1e9


class Stamps:
    """`lgb.train` callback: the clock at the end of every iteration, the
    last one under the profiler."""

    def __init__(self, iterations, trace_dir):
        self.iterations, self.trace_dir = iterations, trace_dir
        self.stamps = []

    def __call__(self, env):
        import jax
        jax.block_until_ready(env.model._gbdt.scores)
        self.stamps.append(time.perf_counter())
        if len(self.stamps) == self.iterations - 1:
            jax.profiler.start_trace(self.trace_dir)
        elif len(self.stamps) == self.iterations:
            jax.profiler.stop_trace()


def emit(**rec):
    print(json.dumps(rec), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "bin_pack_at_size.jsonl"), "a") as fh:
        fh.write(json.dumps(rec) + "\n")


def kept(setting):
    return os.path.join(OUT, f"bin_pack_at_size_{setting}")


def train(setting):
    import jax
    from lightgbm_tpu import compile_cache
    compile_cache.configure("auto")
    import lightgbm_tpu as lgb
    cell = manifest.load_cell(args.workload)
    cfg = cell.config
    n, f = int(args.rows or cell.traffic["rows"]), int(cfg["num_features"])
    params = dict(cfg["params"], verbosity=-1)
    if args.quantized:
        params.update(use_quantized_grad=True, num_grad_quant_bins=126)
    x, y = bench_data.make_data(n, f, args.seed, cfg["data"])
    ds = lgb.Dataset(x, label=y, params=params)
    ds.construct()
    ds.data = ds._binned.raw_data = None
    del x
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.any_device:
        sys.exit(f"bin_pack_at_size.py reads the chip; found {dev}")
    trace_dir = tempfile.mkdtemp(prefix="bin-pack-trace-")
    stamps = Stamps(args.iterations, trace_dir)
    t0 = time.perf_counter()
    bst = lgb.train(dict(params, tpu_bin_pack=setting), ds,
                    num_boost_round=args.iterations, callbacks=[stamps])
    secs = [b - a for a, b in zip([t0] + stamps.stamps, stamps.stamps)]
    timed = secs[1:-1]          # less the first and the traced one
    kernels = kernel_seconds(trace_dir) if dev.platform == "tpu" else None
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(OUT, exist_ok=True)
    with open(kept(setting) + ".txt", "w") as fh:
        fh.write(bst.model_to_string())
    np.save(kept(setting) + ".npy", np.asarray(bst._gbdt.scores)[0][:n])
    stats = dev.memory_stats() or {}
    emit(workload=cell.name, seed=args.seed, rows=n, tpu_bin_pack=setting,
         quantized=args.quantized,
         storage=type(bst._gbdt.bins_fm).__name__,
         train_iters_per_s=len(timed) / sum(timed),
         memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
         hist_kernels_s_traced_iteration=kernels,
         iteration_seconds=secs, device=dev.device_kind)


def compare():
    import reference
    models, scores = {}, {}
    for setting in ("auto", "off"):
        with open(kept(setting) + ".txt") as fh:
            models[setting] = strip_params(fh.read())
        scores[setting] = np.load(kept(setting) + ".npy")
    same = models["auto"] == models["off"]
    rec = dict(models_bit_identical=same, scores_bit_identical=bool(
        np.array_equal(scores["auto"], scores["off"])))
    if not same:
        a, b = (reference.parse_model(models[k]) for k in ("auto", "off"))
        shape = ("split_feature", "threshold", "left_child", "right_child")
        alike = [all(np.array_equal(ta[k], tb[k]) for k in shape)
                 for ta, tb in zip(a, b)]
        rec.update(
            trees=len(a), trees_with_the_same_splits=sum(alike),
            first_trees_splits_the_same_in_order=int(np.sum(
                (a[0]["split_feature"] == b[0]["split_feature"])
                & (a[0]["threshold"] == b[0]["threshold"]))),
            first_tree_that_differs=next(
                i for i, (ta, tb) in enumerate(zip(a, b))
                if not all(np.array_equal(ta[k], tb[k]) for k in ta)),
            widest_leaf_value_gap=max(
                [float(np.max(np.abs(ta["leaf_value"] - tb["leaf_value"])))
                 for ta, tb, ok in zip(a, b, alike) if ok] or [None]),
            widest_score_gap=float(np.max(np.abs(
                scores["auto"] - scores["off"]))),
            scores_equal_share=float(np.mean(
                scores["auto"] == scores["off"])))
    emit(**rec)


if args.compare:
    compare()
else:
    train(args.setting)
