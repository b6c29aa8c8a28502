"""Benchmark: boosting iterations/sec on Higgs-shaped data — plus
`--predict` (bulk serving rows/sec through the tree-parallel inference
engine vs the pre-engine per-tree-scan path) and `--serve` (the async
model server's SLO on an open-loop mixed-size request trace).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

`--predict` emits metric `predict_rows_per_sec` on the serving bench
shape (T=100 trees, 255 leaves, 28 features); `vs_baseline` is the
speedup over the per-tree `lax.scan` traversal the engine replaced
(measured in the same run, same chunking), so the serving trajectory
gets its own BENCH series with a self-contained anchor.

`--serve` emits metric `serve_rows_per_sec` plus `serve_p50_ms` /
`serve_p95_ms` / `serve_p99_ms` request-latency quantiles: a synthetic
open-loop arrival trace of mixed-size requests (mostly B<=64 with
periodic medium batches) replays through serve/ModelServer on the same
bench ensemble; `vs_baseline` is the speedup over dispatching the SAME
request list sequentially straight into the engine — the no-scheduler
alternative, measured in the same run.

`--fleet` emits metric `fleet_availability` plus a `fleet` summary
dict (perf-gate check 12): the --serve open-loop trace replays through
a 3-replica FleetRouter (serve/fleet.py) with one replica killed
mid-run; availability is the fraction of requests served despite the
kill (failover retries absorb the dead replica), alongside the fleet
p99 vs a single-replica reference measured in the same run.

Baseline: the reference CPU result on Higgs-10.5M — 500 iterations in
130.094 s => 3.843 iters/sec (docs/Experiments.rst:113; see BASELINE.md).
Config mirrors the reference GPU benchmark setup (max_bin=63,
num_leaves=255, lr=0.1, min_sum_hessian=100, objective=binary —
docs/GPU-Performance.rst:108-123).

The dataset is synthetic with Higgs shape (28 features, N rows; the real
Higgs is not redistributable and this environment has no egress). Row
count defaults to 10.5M (override with BENCH_ROWS) so iters/sec is
directly comparable to the published 3.843.

One process, one backend: the measurement runs on whatever backend JAX
gives this process, the JSON line records it (`platform`, `device_kind`,
`device_count`), and any failure exits non-zero — there is no probe, no
retry, no smaller shard and no CPU fallback. A number from a CPU run is
a CPU number; read `platform` before reading `value`.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

BASELINE_IPS = 500.0 / 130.094  # reference CPU Higgs-10.5M iters/sec


_BENCH_MODES = ("train", "predict", "serve", "continual", "stream",
                "coldstart", "fleet", "shap", "rank")


def parse_bench_mode(argv=None) -> str:
    """THE bench flag parser: the mode comes from a `--<mode>` flag
    (`--predict`, `--serve`; no flag = train). Adding a mode means
    adding its name to _BENCH_MODES."""
    argv = sys.argv[1:] if argv is None else argv
    mode = "train"
    for tok in argv:
        name = tok[2:] if tok.startswith("--") else None
        if name in _BENCH_MODES:
            mode = name
        elif name is not None:
            raise SystemExit(
                f"bench.py: unknown flag {tok} "
                f"(known: {', '.join('--' + m for m in _BENCH_MODES[1:])})")
    return mode


def _telemetry_enabled() -> bool:
    return (os.environ.get("LGBM_TPU_TIMETAG", "") not in ("", "0")
            or os.environ.get("LGBM_TPU_TELEMETRY", "") not in ("", "0")
            or bool(os.environ.get("LGBM_TPU_TRACE", "")))


_MODE_METRIC = {"train": "boosting_iters_per_sec_higgs_shape",
                "predict": "predict_rows_per_sec",
                "serve": "serve_rows_per_sec",
                "continual": "continual_rows_per_sec",
                "stream": "stream_rows_per_sec",
                "coldstart": "coldstart_compile_reduction",
                "fleet": "fleet_availability",
                "shap": "contrib_rows_per_sec",
                "rank": "rank_train_rows_per_sec"}


def _emit(record: dict) -> None:
    """Print the ONE JSON result line, stamped with the device the
    measurement ran on as JAX reports it."""
    import jax
    dev = jax.devices()[0]
    record.update(platform=dev.platform, device_kind=dev.device_kind,
                  device_count=len(jax.devices()))
    print(json.dumps(record), flush=True)


def main():
    mode = parse_bench_mode()
    try:
        _MODE_MEASURE[mode]()
    except BaseException as exc:
        _emit_partial_obs(mode, exc)
        raise


def _measure():
    n = int(os.environ.get("BENCH_ROWS", 10_500_000))
    f = 28
    iters = int(os.environ.get("BENCH_ITERS", 10))
    warmup = 2

    telemetry = _telemetry_enabled()
    if telemetry:
        # record spans for the phase-time summary folded into the JSON
        # line below (export/exit-print still follow the env knobs),
        # arm the span-boundary HBM watermark sampler (no-op on CPU),
        # and the XLA introspector (compile time + cost analysis per
        # program boundary)
        from lightgbm_tpu.obs import global_tracer
        from lightgbm_tpu.obs.health import global_health
        from lightgbm_tpu.obs.memory import global_watermarks
        from lightgbm_tpu.obs.xla import global_xla
        global_tracer.enable()
        global_watermarks.enable()
        global_xla.enable()
        global_health.enable()

    import jax
    # persistent compilation cache (compile_cache.py shared policy): a
    # retried/repeated bench attempt — or a later driver run in the same
    # image — skips the multi-minute waved 255-leaf compile entirely
    from lightgbm_tpu.compile_cache import configure as _cache_configure
    _cache_configure("auto")
    import lightgbm_tpu as lgb

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    # Higgs-like: mix of informative and noise features, ~53% positive
    x = rng.randn(n, f).astype(np.float32)
    logit = (x[:, 0] + 0.6 * x[:, 1] ** 2 + 0.4 * x[:, 2] * x[:, 3]
             - 0.3 * np.abs(x[:, 4]) + 0.5 * rng.randn(n))
    y = (logit > 0.2).astype(np.float32)
    n_test = min(200_000, n)
    xt = rng.randn(n_test, f).astype(np.float32)
    lt = (xt[:, 0] + 0.6 * xt[:, 1] ** 2 + 0.4 * xt[:, 2] * xt[:, 3]
          - 0.3 * np.abs(xt[:, 4]) + 0.5 * rng.randn(n_test))
    yt = (lt > 0.2).astype(np.float32)

    params = {
        "objective": "binary",
        "num_leaves": 255,
        "learning_rate": 0.1,
        "max_bin": 63,
        "min_sum_hessian_in_leaf": 100,
        "min_data_in_leaf": 0,
        "verbosity": -1,
    }
    t0 = time.time()
    ds = lgb.Dataset(x, label=y, params=params)
    ds.construct()
    bin_time = time.time() - t0

    bst = lgb.Booster(params, ds)
    t0 = time.time()
    for _ in range(warmup):
        bst.update()
    jax.block_until_ready(bst._gbdt.scores)
    warm_time = time.time() - t0

    # BENCH_CHECKPOINT_EVERY=k snapshots the booster every k measured
    # iterations (to BENCH_CHECKPOINT_PATH or a temp file) so the
    # emitted `resilience` record — and perf-gate check 7's overhead
    # ceiling — measures the REAL snapshot cost at bench shape, not a
    # synthetic fixture. Off (default): zero code in the loop.
    ckpt_every = int(os.environ.get("BENCH_CHECKPOINT_EVERY", "0") or 0)
    ckpt_path = os.environ.get("BENCH_CHECKPOINT_PATH") or os.path.join(
        tempfile.gettempdir(), f"bench_ckpt_{os.getpid()}.ckpt")
    if ckpt_every > 0:
        from lightgbm_tpu.resilience import checkpoint as _ckpt
        _ckpt.reset_totals()

    ckpt_is_temp = ckpt_every > 0 and \
        not os.environ.get("BENCH_CHECKPOINT_PATH")
    t0 = time.time()
    try:
        for it in range(iters):
            bst.update()
            if ckpt_every > 0 and (it + 1) % ckpt_every == 0:
                _ckpt.save_checkpoint(bst, ckpt_path, iters)
        jax.block_until_ready(bst._gbdt.scores)
        dt = (time.time() - t0) / iters
    finally:
        if ckpt_is_temp and os.path.exists(ckpt_path):
            os.remove(ckpt_path)  # bench-shape snapshots are large;
            # don't strand them in /tmp across runs

    iters_per_sec = 1.0 / dt

    # device-time attribution (obs/profile.py): profile a couple of
    # EXTRA iterations after the measured loop — the per-call sync the
    # fallback path inserts would depress the headline iters/sec if the
    # window overlapped the measured iterations. global_xla (enabled
    # under telemetry above) feeds cost-analysis bytes/flops into the
    # roofline join; perf-gate check 11 reads the emitted record.
    profile_extra = int(os.environ.get("BENCH_PROFILE_ITERS", "2") or 0)
    prof_summary = None
    if profile_extra > 0:
        from lightgbm_tpu.obs.profile import global_profile
        global_profile.start_window(source="bench")
        for _ in range(profile_extra):
            bst.update()
        jax.block_until_ready(bst._gbdt.scores)
        prof_summary = global_profile.stop_window()

    unit = "iters/sec (N=%d, 255 leaves, 63 bins, bin=%.1fs" % (n, bin_time)
    if platform != "tpu":
        unit += ", platform=%s" % platform
    unit += ")"
    result = {
        "metric": "boosting_iters_per_sec_higgs_shape",
        "value": round(iters_per_sec, 4),
        "unit": unit,
        "vs_baseline": round(iters_per_sec / BASELINE_IPS, 4),
    }
    # histogram HBM traffic counters (always-on obs meta, set by the
    # grower build): the driver-visible side of ROADMAP item 3 — bytes
    # per iteration under the active encodings (bin packing, gh
    # encoding, fused gradient pass, subtraction-aware wave schedule)
    # vs the unpacked/no-subtraction oracle. Checked by
    # tools/check_perf_gate.py.
    from lightgbm_tpu.obs.metrics import global_metrics
    ht = global_metrics.meta.get("hist_traffic")
    if ht:
        result["hist_bytes_per_iter"] = ht["hist_bytes_per_iter"]
        result["hist_rows_scanned_per_iter"] = ht["rows_scanned_per_iter"]
        result["hist_passes_per_iter"] = ht["passes"]
        result["hist_bytes_oracle_per_iter"] = global_metrics.meta[
            "hist_traffic_oracle"]["hist_bytes_per_iter"]
        result["hist_bytes_reduction"] = global_metrics.meta[
            "hist_bytes_reduction"]
    # cross-device collective traffic model (set when a mesh is active):
    # bytes/iter the active tpu_hist_reduce mode puts on ICI/DCN vs the
    # full-histogram psum oracle. Checked by check_perf_gate.py check 14.
    ct = global_metrics.meta.get("collective_traffic")
    if ct:
        result["collective_bytes_per_iter"] = ct[
            "collective_bytes_per_iter"]
        result["collective_reduction_mode"] = ct["reduction"]
        result["collective_reduction"] = global_metrics.meta[
            "collective_reduction"]
    # peak-HBM accounting (obs/memory.py): the analytic model is
    # always-on meta; the measured peak exists only on accelerator
    # backends (memory_stats() is None on CPU). check_perf_gate.py
    # holds model-vs-measured to the recorded band when both appear.
    # device-time + roofline record (obs/profile.py): per-program
    # device-busy seconds from the post-loop profile window, and the
    # measured-vs-peak join (achieved bytes/s, utilization, memory- vs
    # compute-bound verdict per tag). check_perf_gate.py check 11 holds
    # the coverage band and the utilization floor on this record.
    if prof_summary and prof_summary.get("device_seconds_by_tag"):
        result["device_seconds_by_tag"] = {
            tag: round(sec, 6) for tag, sec in
            prof_summary["device_seconds_by_tag"].items()}
        result["roofline"] = global_profile.roofline()
    mm = global_metrics.meta.get("mem_model")
    if mm:
        result["mem_peak_model_bytes"] = mm["peak_bytes"]
        result["mem_peak_phase"] = mm["peak_phase"]
    from lightgbm_tpu.obs.memory import measured_peak_bytes
    measured = measured_peak_bytes()
    if measured:
        result["mem_peak_measured_bytes"] = measured
    # checkpoint-overhead accounting (resilience/checkpoint.py): only
    # present when the run actually snapshotted (tpu_checkpoint_* knobs
    # in the train params); check_perf_gate.py check 7 holds the
    # snapshot time share of train wall-time to the recorded ceiling
    from lightgbm_tpu.resilience.checkpoint import checkpoint_totals
    ck = checkpoint_totals()
    if ck.get("checkpoints"):
        result["resilience"] = {
            "checkpoints": int(ck["checkpoints"]),
            "checkpoint_seconds_total": round(ck["seconds_total"], 4),
            "train_seconds": round(dt * iters, 4),
        }
    if telemetry:
        # fold the phase-time summary into the one JSON line instead of
        # leaving it buried in raw stderr
        from lightgbm_tpu.obs import global_tracer
        phases = {"bin_seconds": round(bin_time, 3),
                  "warmup_compile_seconds": round(warm_time, 3),
                  "per_iter_seconds": round(dt, 4)}
        for name, agg in global_tracer.summary().items():
            phases[name] = round(agg["seconds"], 4)
        result["phases"] = phases
        # XLA compile attribution (obs/xla.py): total compile wall-time
        # and which phase's programs recompiled, per executable
        from lightgbm_tpu.obs.xla import global_xla
        xs = global_xla.summary()
        if xs["n_programs"]:
            result["compile_s_total"] = xs["compile_s_total"]
            result["n_recompiles_by_phase"] = xs["n_recompiles_by_phase"]
        # live per-phase HBM watermarks (accelerator backends only —
        # the sampler self-disables where memory_stats() is None)
        from lightgbm_tpu.obs.memory import global_watermarks
        wm = global_watermarks.summary()
        if wm:
            result["mem_phase_watermarks"] = {
                name: ph["delta_bytes"] for name, ph in wm.items()}
        # training-health summary (obs/health.py): runtime-attributed
        # collective calls/bytes per tag, the timed collective probe,
        # straggler skew, drift/nonfinite counters — the comms-health
        # side of the item-4 gate (tools/check_perf_gate.py health
        # check reads these fields from the candidate JSON)
        from lightgbm_tpu.obs.health import global_health
        hs = global_health.summary()
        if hs:
            result["health"] = hs
    _emit(result)
    # quality sanity: held-out AUC after the benchmarked iterations — a
    # guard on the bf16-input histogram path (tpu_hist_precision default)
    pred = bst.predict(xt, raw_score=True)
    order = np.argsort(pred)
    ranks = np.empty(n_test)
    ranks[order] = np.arange(1, n_test + 1)
    pos = yt > 0.5
    auc = (ranks[pos].sum() - pos.sum() * (pos.sum() + 1) / 2) / (
        pos.sum() * (~pos).sum())
    auc_line = f"test_auc@{warmup + iters}iters={auc:.4f}"
    print(f"# platform={platform} bin={bin_time:.1f}s "
          f"warmup+compile={warm_time:.1f}s per_iter={dt:.3f}s {auc_line}",
          file=sys.stderr)


def _random_trees(rng, num_trees: int, num_leaves: int, num_features: int):
    """Synthetic 255-leaf ensembles for the serving bench: training 100
    such trees on CPU would dwarf the attempt budget, and inference
    throughput only depends on tree SHAPE, not split quality. Topology
    follows the learner's numbering (internal node s splits an existing
    leaf; left child keeps the parent's leaf id, right child becomes
    leaf s+1)."""
    from lightgbm_tpu.tree import Tree
    trees = []
    for _ in range(num_trees):
        tr = Tree(num_leaves)
        slot = {}  # leaf id -> (node, side) where that leaf hangs
        for s in range(num_leaves - 1):
            leaf = int(rng.randint(0, s + 1))
            if leaf in slot:
                node, side = slot.pop(leaf)
                (tr.left_child if side == 0 else tr.right_child)[node] = s
            tr.split_feature[s] = tr.split_feature_inner[s] = \
                rng.randint(0, num_features)
            tr.threshold[s] = rng.randn() * 0.7
            tr.left_child[s] = ~leaf
            tr.right_child[s] = ~(s + 1)
            slot[leaf] = (s, 0)
            slot[s + 1] = (s, 1)
        tr.leaf_value[:] = rng.randn(num_leaves) * 0.1
        # synthetic cover counts so the SHAP bench can form z-fractions
        # (child_count / parent_count); internal counts are the exact
        # subtree sums, built children-first (node s's children are
        # always leaves or internal nodes > s)
        tr.leaf_count[:] = rng.randint(1, 100, num_leaves)
        for s in reversed(range(num_leaves - 1)):
            tr.internal_count[s] = sum(
                tr.leaf_count[~c] if c < 0 else tr.internal_count[c]
                for c in (tr.left_child[s], tr.right_child[s]))
        trees.append(tr)
    return trees


def _measure_predict():
    """Serving bench: rows/sec through the streaming inference engine
    (vmapped tree-parallel traversal) vs the pre-engine per-tree scan,
    same ensemble, same chunking — bit-equality asserted on a probe
    block before timing."""
    n = int(os.environ.get("BENCH_ROWS", 8_000_000))
    t = int(os.environ.get("BENCH_PREDICT_TREES", 100))
    leaves = int(os.environ.get("BENCH_PREDICT_LEAVES", 255))
    f = 28
    chunk = int(os.environ.get("BENCH_PREDICT_CHUNK", 1 << 20))

    import jax
    from lightgbm_tpu.compile_cache import configure as _cache_configure
    _cache_configure("auto")
    import numpy as np
    from lightgbm_tpu.ops import predict as pred_ops

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    trees = _random_trees(rng, t, leaves, f)
    data = rng.randn(n, f).astype(np.float64)

    class _Owner:  # packed-ensemble cache host
        pass

    owner = _Owner()

    def engine_run():
        return pred_ops.predict_raw_cached(owner, trees, 1, data, "bench",
                                           chunk)

    ens = pred_ops.pack_ensemble(trees, 1)

    def scan_run():
        # the pre-change path: per-tree lax.scan, exact chunk shapes
        import jax.numpy as jnp
        outs = []
        for lo in range(0, n, chunk):
            x = jnp.asarray(data[lo:lo + chunk], jnp.float32)
            outs.append(np.asarray(pred_ops.predict_raw_scan(ens, x),
                                   np.float64))
        return np.concatenate(outs, axis=0)

    # correctness probe: the engine must reproduce the scan path bitwise
    probe = min(n, 10_000)
    import jax.numpy as jnp
    probe_scan = np.asarray(pred_ops.predict_raw_scan(
        ens, jnp.asarray(data[:probe], jnp.float32)), np.float64)
    probe_engine = pred_ops.predict_raw_cached(
        _Owner(), trees, 1, data[:probe], "probe", chunk)
    bit_equal = bool(np.array_equal(probe_scan, probe_engine))

    engine_run()  # compile + warm
    reps = int(os.environ.get("BENCH_PREDICT_REPS", 3))
    t0 = time.time()
    for _ in range(reps):
        engine_run()
    engine_rps = n * reps / (time.time() - t0)

    scan_run()  # compile + warm
    t0 = time.time()
    scan_run()
    scan_rps = n / (time.time() - t0)

    unit = "rows/sec (N=%d, T=%d, %d leaves" % (n, t, leaves)
    if platform != "tpu":
        unit += ", platform=%s" % platform
    if not bit_equal:
        unit += ", PARITY-MISMATCH"
    unit += ")"
    result = {
        "metric": "predict_rows_per_sec",
        "value": round(engine_rps, 1),
        "unit": unit,
        # anchor: speedup over the per-tree-scan path this engine replaced
        "vs_baseline": round(engine_rps / max(scan_rps, 1e-9), 4),
        "scan_rows_per_sec": round(scan_rps, 1),
    }
    _emit(result)
    print("# platform=%s engine=%.0f rows/s scan=%.0f rows/s "
          "speedup=%.2fx bit_equal=%s"
          % (platform, engine_rps, scan_rps, engine_rps / max(scan_rps, 1e-9),
             bit_equal), file=sys.stderr)


def _measure_shap():
    """Explanation bench: SHAP-contribution rows/sec through the batched
    device TreeSHAP kernel (ops/shap.py, path-decomposed pack) vs the
    reference recursive host oracle measured in the SAME run on a row
    subset — the per-row recursion cost is row-count-independent, so the
    subset extrapolates. Parity between the two is asserted on that
    subset before timing; the path-table pack bytes ride along so the
    perf gate can band them against the analytic memory model."""
    n = int(os.environ.get("BENCH_ROWS", 200_000))
    t = int(os.environ.get("BENCH_SHAP_TREES", 50))
    leaves = int(os.environ.get("BENCH_SHAP_LEAVES", 31))
    f = 28
    chunk = int(os.environ.get("BENCH_SHAP_CHUNK", 4096))

    import jax
    from lightgbm_tpu.compile_cache import configure as _cache_configure
    _cache_configure("auto")
    from lightgbm_tpu.ops import predict as pred_ops
    from lightgbm_tpu.ops import shap as shap_ops
    from lightgbm_tpu import shap as shap_host
    from lightgbm_tpu.obs.memory import predict_memory_model

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    trees = _random_trees(rng, t, leaves, f)
    data = rng.randn(n, f).astype(np.float64)
    data[::11, 3] = np.nan  # exercise the missing-routing tables

    class _Owner:  # packed path-table cache host
        pass

    owner = _Owner()

    def device_run():
        return shap_ops.shap_contrib_cached(owner, trees, 1, data, f,
                                            "bench", chunk)

    # host recursive oracle on a subset: minutes per thousand rows at
    # this tree count, so the subset carries the baseline
    n_oracle = min(int(os.environ.get("BENCH_SHAP_ORACLE_ROWS", 128)), n)
    t0 = time.time()
    oracle = shap_host._contrib_over_trees(
        lambda it, ki: trees[it], t, 1, data[:n_oracle], f, 0, -1)
    oracle_rps = n_oracle / (time.time() - t0)

    dev = device_run()  # compile + warm (and the parity source)
    scale = max(np.abs(oracle).max(), 1.0)
    rel_err = float(np.abs(dev[:n_oracle] - oracle).max() / scale)
    bit_equal = rel_err <= 2e-3  # f32 recurrence noise vs f64 recursion

    reps = int(os.environ.get("BENCH_SHAP_REPS", 3))
    t0 = time.time()
    for _ in range(reps):
        device_run()
    device_rps = n * reps / (time.time() - t0)

    packer = pred_ops._get_packer(owner, "bench")
    pack = packer.shap_update(trees, 1, f, chunk_rows=chunk)  # cached
    model = predict_memory_model(
        num_rows=n, num_features=f, num_trees=t, num_leaves=leaves,
        chunk_rows=chunk, contrib=True)

    unit = "rows/sec (N=%d, T=%d, %d leaves" % (n, t, leaves)
    if platform != "tpu":
        unit += ", platform=%s" % platform
    if not bit_equal:
        unit += ", PARITY-MISMATCH"
    unit += ")"
    result = {
        "metric": "contrib_rows_per_sec",
        "value": round(device_rps, 1),
        "unit": unit,
        # anchor: speedup over the reference recursion this kernel
        # replaced (perf-gate check 13 floors this)
        "vs_baseline": round(device_rps / max(oracle_rps, 1e-9), 4),
        "shap": {
            "device_rows_per_sec": round(device_rps, 1),
            "oracle_rows_per_sec": round(oracle_rps, 2),
            "oracle_rows": n_oracle,
            "oracle_rel_err": round(rel_err, 8),
            "paths": int(pack.num_paths),
            "depth": int(pack.depth),
            "pack_bytes": int(2 * packer.shap_nbytes),
            "model_pack_bytes": int(model["components"]["shap_pack"]),
            "chunk_rows": chunk,
        },
    }
    _emit(result)
    print("# platform=%s device=%.0f rows/s oracle=%.1f rows/s "
          "speedup=%.1fx paths=%d depth=%d rel_err=%.2g"
          % (platform, device_rps, oracle_rps,
             device_rps / max(oracle_rps, 1e-9), pack.num_paths,
             pack.depth, rel_err), file=sys.stderr)


def _measure_rank():
    """Ranking bench: lambdarank training rows/sec on a synthetic
    query/document fixture plus a served smoke trace of the trained
    ranker — the first recorded datapoint for the ranking objective.
    vs_baseline anchors lambdarank against a pointwise binary train of
    the SAME shape in the same run (the pairwise-gradient overhead)."""
    import asyncio

    n = int(os.environ.get("BENCH_ROWS", 500_000))
    f = 20
    qsize = int(os.environ.get("BENCH_RANK_QUERY_SIZE", 20))
    iters = int(os.environ.get("BENCH_RANK_ITERS", 10))
    warmup = 2

    import jax
    from lightgbm_tpu.compile_cache import configure as _cache_configure
    _cache_configure("auto")
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import ModelRegistry, ModelServer, replay
    from lightgbm_tpu.obs.metrics import global_metrics

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    n_query = max(n // qsize, 1)
    n = n_query * qsize
    x = rng.randn(n, f)
    group = np.full(n_query, qsize, np.int32)
    # graded relevance 0..3: a noisy monotone function of two features
    score = x[:, 0] + 0.5 * x[:, 3] + rng.randn(n) * 0.7
    y = np.clip(np.digitize(score, (-1.0, 0.3, 1.5)), 0, 3).astype(
        np.float64)

    params = {"objective": "lambdarank", "num_leaves": 63,
              "learning_rate": 0.1, "verbosity": -1}
    ds = lgb.Dataset(x, label=y, group=group, params=params)
    t0 = time.time()
    bst = lgb.train(params, ds, num_boost_round=warmup)
    warm_time = time.time() - t0
    t0 = time.time()
    bst = lgb.train(params, ds, num_boost_round=warmup + iters)
    rank_rps = n * (warmup + iters) / (time.time() - t0)

    # pointwise anchor: binary train, identical data shape and leaves
    p2 = dict(params, objective="binary")
    yb = (y >= 2).astype(np.float64)
    ds2 = lgb.Dataset(x, label=yb, params=p2)
    lgb.train(p2, ds2, num_boost_round=warmup)
    t0 = time.time()
    lgb.train(p2, ds2, num_boost_round=warmup + iters)
    binary_rps = n * (warmup + iters) / (time.time() - t0)

    # quality sanity: mean NDCG@5 of the trained ranker over the queries
    pred = bst.predict(x, raw_score=True)
    gains, ndcg = 2.0 ** y - 1.0, []
    disc = 1.0 / np.log2(np.arange(2, qsize + 2))
    for q in range(min(n_query, 2000)):
        sl = slice(q * qsize, (q + 1) * qsize)
        g, p = gains[sl], pred[sl]
        ideal = (np.sort(g)[::-1][:5] * disc[:5]).sum()
        if ideal <= 0:
            continue
        got = (g[np.argsort(-p)][:5] * disc[:5]).sum()
        ndcg.append(got / ideal)
    ndcg5 = float(np.mean(ndcg)) if ndcg else 0.0

    # serve smoke: the trained ranker behind ModelServer, mixed-size
    # trace (lowlat + coalesced), request latency reservoir
    registry = ModelRegistry()
    registry.load("rank", booster=bst)
    server = ModelServer(registry, max_batch_rows=8192, max_wait_ms=2.0)
    server.warm("rank", f)
    smoke_rows = min(n, int(os.environ.get("BENCH_RANK_SERVE_ROWS",
                                           100_000)))
    sizes = _serve_request_sizes(rng, smoke_rows)
    global_metrics.reset_latency("serve/request")

    async def run():
        try:
            await replay(server, "rank", x[:smoke_rows], sizes,
                         raw_score=True)
        finally:
            await server.close()

    t0 = time.time()
    asyncio.run(run())
    serve_rps = smoke_rows / (time.time() - t0)
    lat = global_metrics.latency_summary("serve/request")

    unit = ("rows/sec (N=%d, %d queries x %d docs, %d iters"
            % (n, n_query, qsize, warmup + iters))
    if platform != "tpu":
        unit += ", platform=%s" % platform
    unit += ")"
    result = {
        "metric": "rank_train_rows_per_sec",
        "value": round(rank_rps, 1),
        "unit": unit,
        # anchor: lambdarank vs pointwise binary training, same shape
        "vs_baseline": round(rank_rps / max(binary_rps, 1e-9), 4),
        "rank": {
            "train_rows_per_sec": round(rank_rps, 1),
            "binary_rows_per_sec": round(binary_rps, 1),
            "train_ndcg5": round(ndcg5, 4),
            "serve_rows_per_sec": round(serve_rps, 1),
            "serve_p50_ms": lat["p50_ms"],
            "serve_p99_ms": lat["p99_ms"],
            "serve_requests": len(sizes),
        },
    }
    _emit(result)
    print("# platform=%s rank=%.0f rows/s binary=%.0f rows/s "
          "ndcg@5=%.3f serve=%.0f rows/s p50=%.2fms p99=%.2fms "
          "(first train warmup %.1fs)"
          % (platform, rank_rps, binary_rps, ndcg5, serve_rps,
             lat["p50_ms"], lat["p99_ms"], warm_time), file=sys.stderr)


def _serve_request_sizes(rng, total_rows: int):
    """Mixed-traffic request sizes for the serving trace: ~3/4 of
    requests are small (1..64 rows, the low-latency path), the rest
    medium batches (256..2048) — small requests dominate the request
    COUNT while medium ones carry most of the rows, the shape the
    micro-batcher exists for."""
    small = (1, 2, 4, 8, 16, 32, 64)
    medium = (256, 512, 1024, 2048)
    sizes = []
    done = 0
    i = 0
    while done < total_rows:
        pick = (medium[int(rng.randint(len(medium)))] if i % 4 == 3
                else small[int(rng.randint(len(small)))])
        sizes.append(min(pick, total_rows - done))
        done += sizes[-1]
        i += 1
    return sizes


def _measure_serve():
    """Serving SLO bench: an open-loop synthetic arrival trace of
    mixed-size requests replays through serve/ModelServer (warm shape
    buckets, AOT low-latency path, deadline-bounded coalescing);
    emits served rows/sec + request p50/p95/p99. vs_baseline anchors
    against the no-scheduler alternative measured in the same run: the
    SAME request list dispatched sequentially straight into the engine."""
    import asyncio

    n = int(os.environ.get("BENCH_ROWS", 2_000_000))
    t = int(os.environ.get("BENCH_PREDICT_TREES", 100))
    leaves = int(os.environ.get("BENCH_PREDICT_LEAVES", 255))
    f = 28
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", 8192))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", 2.0))

    import jax
    from lightgbm_tpu.compile_cache import configure as _cache_configure
    _cache_configure("auto")
    from lightgbm_tpu.model_io import LoadedModel
    from lightgbm_tpu.serve import ModelRegistry, ModelServer, replay
    from lightgbm_tpu.obs.metrics import global_metrics

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    trees = _random_trees(rng, t, leaves, f)
    model = LoadedModel()
    model.trees = trees
    model.num_tree_per_iteration = 1
    model.objective_str = "binary sigmoid:1"
    model.max_feature_idx = f - 1

    registry = ModelRegistry()
    registry.load("bench", model=model)
    server = ModelServer(registry, max_batch_rows=max_batch,
                         max_wait_ms=max_wait_ms)
    data = rng.randn(n, f)
    sizes = _serve_request_sizes(rng, n)
    bounds = np.concatenate([[0], np.cumsum(sizes)])

    server.warm("bench", f)

    # parity probe: served bytes must equal direct predict bytes on
    # both paths (small -> lowlat, medium -> coalesced)
    async def probe():
        idx = [i for i, s in enumerate(sizes[:64]) if s <= 64][:2] + \
              [i for i, s in enumerate(sizes[:64]) if s > 64][:2]
        outs = await asyncio.gather(*[
            server.predict("bench", data[bounds[i]:bounds[i + 1]],
                           raw_score=True) for i in idx])
        ok = all(np.array_equal(
            out, model.predict(data[bounds[i]:bounds[i + 1]],
                               raw_score=True))
            for i, out in zip(idx, outs))
        return ok

    bit_equal = asyncio.run(probe())

    # no-scheduler baseline: the same requests, sequential engine calls
    n_base = min(len(sizes), int(os.environ.get("BENCH_SERVE_BASE_REQS",
                                                400)))
    t0 = time.time()
    for i in range(n_base):
        model.predict_raw(data[bounds[i]:bounds[i + 1]])
    direct_rps = float(bounds[n_base]) / (time.time() - t0)

    # bulk engine capacity (informative anchor for the JSON line)
    bulk_rows = int(min(n, 1 << 20))
    model.predict_raw(data[:bulk_rows])  # warm the full-chunk bucket
    t0 = time.time()
    model.predict_raw(data[:bulk_rows])
    bulk_rps = bulk_rows / (time.time() - t0)

    # two trace halves: a zero-gap burst measures sustainable CAPACITY
    # (per-request scheduling included — the headline rows/sec), then
    # the second half replays at 70% of that capacity with Poisson
    # arrivals so p50/p99 reflect steady-state service, not the
    # unbounded queue growth of an over-saturated open loop
    half = max(len(sizes) // 2, 1)
    sizes_cap, sizes_slo = sizes[:half], (sizes[half:] or sizes[:half])
    data_slo = data[bounds[half]:] if sizes[half:] else data

    async def burst():
        await replay(server, "bench", data, sizes_cap, raw_score=True)

    t0 = time.time()
    asyncio.run(burst())
    served_rps = float(bounds[half]) / (time.time() - t0)

    offered_rps = float(os.environ.get("BENCH_SERVE_LOAD", 0.7)) \
        * served_rps
    gaps = rng.exponential(
        np.asarray(sizes_slo, np.float64) / offered_rps)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    global_metrics.reset_latency("serve/request")

    async def timed():
        try:
            return await replay(server, "bench", data_slo, sizes_slo,
                                raw_score=True, arrival_s=arrivals)
        finally:
            await server.close()

    asyncio.run(timed())
    lat = global_metrics.latency_summary("serve/request")

    unit = ("rows/sec (N=%d, T=%d, %d leaves, %d requests, "
            "offered=%.0f rows/s" % (n, t, leaves, len(sizes),
                                     offered_rps))
    if platform != "tpu":
        unit += ", platform=%s" % platform
    if not bit_equal:
        unit += ", PARITY-MISMATCH"
    unit += ")"
    result = {
        "metric": "serve_rows_per_sec",
        "value": round(served_rps, 1),
        "unit": unit,
        # anchor: speedup over sequential per-request engine dispatch
        "vs_baseline": round(served_rps / max(direct_rps, 1e-9), 4),
        "serve_p50_ms": lat["p50_ms"],
        "serve_p95_ms": lat["p95_ms"],
        "serve_p99_ms": lat["p99_ms"],
        "serve_rows_per_sec": round(served_rps, 1),
        "direct_rows_per_sec": round(direct_rps, 1),
        "bulk_rows_per_sec": round(bulk_rps, 1),
    }
    _emit(result)
    print("# platform=%s serve=%.0f rows/s direct=%.0f rows/s "
          "bulk=%.0f rows/s p50=%.2fms p99=%.2fms bit_equal=%s"
          % (platform, served_rps, direct_rps, bulk_rps,
             lat["p50_ms"], lat["p99_ms"], bit_equal), file=sys.stderr)


def _measure_fleet():
    """Fleet chaos bench (serve/fleet.py): the --serve open-loop trace
    fronted by an N-replica FleetRouter with one replica KILLED mid-run.
    Emits `fleet_availability` (fraction of requests served despite the
    kill — failover retries absorb the dead replica; perf-gate check 12
    holds it >= 0.999) plus the fleet p50/p99 against a single-replica
    reference replayed in the same run, the failover/quarantine
    counters, and a served-vs-direct bit-parity verdict."""
    import asyncio

    n = int(os.environ.get("BENCH_ROWS", 500_000))
    t = int(os.environ.get("BENCH_PREDICT_TREES", 100))
    leaves = int(os.environ.get("BENCH_PREDICT_LEAVES", 255))
    f = 28
    n_replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", 3))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", 8192))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", 2.0))

    import jax
    from lightgbm_tpu.compile_cache import configure as _cache_configure
    _cache_configure("auto")
    from lightgbm_tpu.model_io import LoadedModel
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.serve import (InProcessReplica, FleetRouter,
                                    ModelRegistry, ModelServer, replay)

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    trees = _random_trees(rng, t, leaves, f)

    def make_replica(i: int) -> InProcessReplica:
        # each replica packs its own registry from the SAME trees —
        # the bit-identical-pack contract the failover math rests on
        model = LoadedModel()
        model.trees = trees
        model.num_tree_per_iteration = 1
        model.objective_str = "binary sigmoid:1"
        model.max_feature_idx = f - 1
        registry = ModelRegistry()
        registry.load("bench", model=model)
        return InProcessReplica(f"r{i}", ModelServer(
            registry, max_batch_rows=max_batch, max_wait_ms=max_wait_ms))

    replicas = [make_replica(i) for i in range(n_replicas)]
    fleet = FleetRouter(replicas, probe_interval_ms=10.0,
                        breaker_reset_s=0.25).start()
    data = rng.randn(n, f)
    sizes = _serve_request_sizes(rng, n)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    for rep in replicas:
        # the process-wide compile cache makes replicas 1..N-1 warm
        # from replica 0's compiles
        rep.server.warm("bench", f)
    ref_model = replicas[0].server.registry.get("bench").model

    # single-replica reference: the same trace shape straight through
    # one ModelServer (what --serve measures), for the p99 comparison
    half = max(len(sizes) // 2, 1)
    global_metrics.reset_latency("serve/request")
    t0 = time.time()
    asyncio.run(replay(replicas[0].server, "bench",
                       data[:bounds[half]], sizes[:half], raw_score=True))
    single_rps = float(bounds[half]) / (time.time() - t0)
    single_lat = global_metrics.latency_summary("serve/request")

    # fleet phase: open-loop Poisson arrivals at 70% of the measured
    # single-replica capacity; replica 0 dies at the 40% mark
    offered_rps = float(os.environ.get("BENCH_SERVE_LOAD", 0.7)) \
        * single_rps
    gaps = rng.exponential(np.asarray(sizes, np.float64) / offered_rps)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    kill_idx = max(int(0.4 * len(sizes)), 1)
    lat_all: list = []
    lat_post_kill: list = []
    state = {"failed": 0, "kill_t": None}

    async def one(i: int) -> None:
        if arrivals[i] > 0:
            await asyncio.sleep(float(arrivals[i]))
        if i == kill_idx:
            replicas[0].fail_dispatch = True  # SIGKILL stand-in
            state["kill_t"] = time.perf_counter()
        t_req = time.perf_counter()
        try:
            await fleet.predict("bench", data[bounds[i]:bounds[i + 1]],
                                raw_score=True)
        except Exception:
            state["failed"] += 1
            return
        dt = time.perf_counter() - t_req
        lat_all.append(dt)
        if state["kill_t"] is not None and \
                t_req >= state["kill_t"]:
            lat_post_kill.append(dt)

    async def fleet_phase() -> None:
        await asyncio.gather(*[one(i) for i in range(len(sizes))])

    t0 = time.time()
    asyncio.run(fleet_phase())
    fleet_wall = time.time() - t0

    # bit parity: fleet answers (now riding the survivors) vs direct
    async def probe() -> bool:
        idx = list(range(min(4, len(sizes))))
        outs = await asyncio.gather(*[
            fleet.predict("bench", data[bounds[i]:bounds[i + 1]],
                          raw_score=True) for i in idx])
        return all(np.array_equal(
            out, ref_model.predict(data[bounds[i]:bounds[i + 1]],
                                   raw_score=True))
            for i, out in zip(idx, outs))

    bit_equal = asyncio.run(probe())
    fstats = fleet.stats()
    counters = fstats["counters"]

    async def teardown() -> None:
        fleet.stop()
        for rep in replicas:
            await rep.server.close()

    asyncio.run(teardown())

    served = len(lat_all)
    total = served + state["failed"]
    availability = served / max(total, 1)
    q = (lambda a, p: float(np.percentile(np.asarray(a) * 1e3, p))
         if a else 0.0)
    fleet_summary = {
        "availability": round(availability, 6),
        "requests": total,
        "served": served,
        "failed": state["failed"],
        "replicas": n_replicas,
        "failovers": int(counters.get("fleet/failovers", 0)),
        "quarantines": int(counters.get("fleet/quarantines", 0)),
        "killed_quarantined": bool(
            fstats["replicas"]["r0"]["quarantined"]),
        "p50_ms": round(q(lat_all, 50), 3),
        "p99_ms": round(q(lat_all, 99), 3),
        "failover_p99_ms": round(q(lat_post_kill, 99), 3),
        "single_p50_ms": single_lat["p50_ms"],
        "single_p99_ms": single_lat["p99_ms"],
        "single_rows_per_sec": round(single_rps, 1),
        "rows_per_sec": round(float(bounds[-1]) / max(fleet_wall, 1e-9),
                              1),
        "parity_ok": bool(bit_equal),
    }
    unit = ("fraction served (N=%d, T=%d, %d leaves, %d requests, "
            "%d replicas, kill@40%%" % (n, t, leaves, total, n_replicas))
    if platform != "tpu":
        unit += ", platform=%s" % platform
    if not bit_equal:
        unit += ", PARITY-MISMATCH"
    unit += ")"
    result = {
        "metric": "fleet_availability",
        "value": round(availability, 6),
        "unit": unit,
        # the anchor IS the availability target: 1.0 = no request lost
        "vs_baseline": round(availability, 6),
        "fleet": fleet_summary,
    }
    _emit(result)
    print("# platform=%s availability=%.6f served=%d/%d failovers=%d "
          "quarantines=%d fleet_p99=%.2fms single_p99=%.2fms "
          "bit_equal=%s"
          % (platform, availability, served, total,
             fleet_summary["failovers"], fleet_summary["quarantines"],
             fleet_summary["p99_ms"], fleet_summary["single_p99_ms"],
             bit_equal), file=sys.stderr)


def _measure_continual():
    """Continual-training bench (resilience/continual.py): BENCH_ROWS
    of Higgs-shaped data ingested in BENCH_CONTINUAL_GENERATIONS
    chunks, one generation per chunk (init_model continuation +
    eval-anomaly gate + validated hot-swap into a live ModelRegistry).
    Emits ingested rows/sec plus the `continual` summary dict —
    swap/rollback overhead share included, which perf-gate check 8
    caps. vs_baseline anchors against the no-continual alternative
    measured in the same run: ONE monolithic train on the full data
    for the same total iteration count (what a fleet would rerun from
    scratch on every refresh)."""
    n = int(os.environ.get("BENCH_ROWS", 40_000))
    gens = int(os.environ.get("BENCH_CONTINUAL_GENERATIONS", 5))
    rounds = int(os.environ.get("BENCH_CONTINUAL_ROUNDS", 10))
    f = 28

    import jax
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import ModelRegistry

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    logit = X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.3 * X[:, 2] * X[:, 3]
    y = (logit + 0.2 * rng.randn(n) > 0.5).astype(np.float32)

    params = {"objective": "binary", "max_bin": 63, "num_leaves": 255,
              "learning_rate": 0.1, "min_sum_hessian_in_leaf": 100,
              "verbosity": -1, "tpu_continual_rounds": rounds,
              "tpu_continual_eval_fraction": 0.2}
    registry = ModelRegistry()
    trainer = lgb.ContinualTrainer(params, num_features=f,
                                   registry=registry,
                                   serve_name="bench-continual")
    bounds = np.linspace(0, n, gens + 1).astype(int)
    t0 = time.perf_counter()
    for g in range(gens):
        s, e = bounds[g], bounds[g + 1]
        trainer.push_rows(X[s:e], label=y[s:e])
        trainer.step()
    wall = time.perf_counter() - t0

    # the no-continual anchor: one monolithic train over everything,
    # same total iteration budget, measured in the same run
    t0 = time.perf_counter()
    lgb.train(dict(params), lgb.Dataset(X, label=y),
              num_boost_round=gens * rounds)
    mono_wall = time.perf_counter() - t0

    summary = trainer.summary()
    overhead = summary["swap_seconds_total"] + max(
        wall - summary["train_seconds_total"]
        - summary["swap_seconds_total"], 0.0)
    record = {
        "metric": "continual_rows_per_sec",
        "value": round(n / wall, 3),
        "unit": f"rows/sec (n={n} gens={gens} rounds={rounds} "
                f"platform={platform})",
        "vs_baseline": round(mono_wall / wall, 4),
        "continual": dict(summary,
                          wall_seconds=round(wall, 3),
                          overhead_seconds=round(overhead, 3),
                          swap_share=round(
                              summary["swap_seconds_total"] / wall, 6),
                          monolithic_wall_seconds=round(mono_wall, 3)),
    }
    _emit(record)
    print(f"# continual: {summary['generations']} generation(s), "
          f"{summary['rollbacks']} rollback(s), swap share "
          f"{record['continual']['swap_share']:.2%}", file=sys.stderr)


def _measure_stream():
    """Out-of-core streaming bench (tpu_stream, io/streaming.py +
    learner.StreamTreeGrower): trains the SAME Higgs-shaped fixture
    twice — resident (the anchor) and forced-streaming with a
    multi-slab plan — and emits streamed rows/sec, slab upload vs
    kernel wall seconds, the measured `stream_overlap_ratio` (fraction
    of upload time issued while device compute was in flight), and
    `vs_resident` (resident wall / streamed wall; perf-gate check 9
    holds the slowdown to the recorded ceiling)."""
    n = int(os.environ.get("BENCH_ROWS", 10_500_000))
    f = 28
    iters = int(os.environ.get("BENCH_ITERS", 10))
    warmup = 2

    import jax
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.streaming import global_stream_stats
    from lightgbm_tpu.ops.bin_pack import slab_align

    platform = jax.default_backend()
    rng = np.random.RandomState(0)
    x = rng.randn(n, f).astype(np.float32)
    logit = (x[:, 0] + 0.6 * x[:, 1] ** 2 + 0.4 * x[:, 2] * x[:, 3]
             - 0.3 * np.abs(x[:, 4]) + 0.5 * rng.randn(n))
    y = (logit > 0.2).astype(np.float32)

    base_params = {"objective": "binary", "num_leaves": 255,
                   "learning_rate": 0.1, "max_bin": 63,
                   "min_sum_hessian_in_leaf": 100, "min_data_in_leaf": 0,
                   "verbosity": -1}

    def timed_train(extra):
        params = dict(base_params, **extra)
        ds = lgb.Dataset(x, label=y, params=params)
        ds.construct()
        bst = lgb.Booster(params, ds)
        for _ in range(warmup):
            bst.update()
        jax.block_until_ready(bst._gbdt.scores)
        t0 = time.perf_counter()
        for _ in range(iters):
            bst.update()
        jax.block_until_ready(bst._gbdt.scores)
        return bst, time.perf_counter() - t0

    # resident anchor (same shape, same iteration count, same run) —
    # tpu_stream pinned OFF so a capacity-constrained host can't
    # silently stream the anchor and gate streaming against itself.
    # The anchor booster is dropped before the streamed half runs: its
    # device-resident bins/scores must not occupy the HBM the streamed
    # measurement is supposed to have free.
    anchor, resident_wall = timed_train({"tpu_stream": "off"})
    del anchor

    # forced streaming with a REAL multi-slab plan: ~4 slabs (or the
    # smallest aligned slab when the fixture is tiny)
    align = slab_align(int(base_params["max_bin"]))
    slab_rows = max(align, (n // 4) // align * align)
    global_stream_stats.reset()
    bst, stream_wall = timed_train({"tpu_stream": "on",
                                    "tpu_stream_slab_rows": slab_rows})
    stats = global_stream_stats.summary()
    plan = bst._gbdt._stream

    rows_per_sec = n * iters / stream_wall
    record = {
        "metric": "stream_rows_per_sec",
        "value": round(rows_per_sec, 3),
        "unit": f"boosted rows/sec (n={n}, 255 leaves, 63 bins, "
                f"{plan.n_slabs} slabs, platform={platform})",
        "vs_baseline": round(resident_wall / stream_wall, 4),
        "stream": dict(
            stats,
            slab_rows=int(plan.slab_rows),
            n_slabs=int(plan.n_slabs),
            stream_overlap_ratio=stats["overlap_ratio"],
            upload_seconds=stats["upload_seconds_total"],
            kernel_seconds=stats["kernel_seconds_total"],
            stream_wall_seconds=round(stream_wall, 3),
            resident_wall_seconds=round(resident_wall, 3),
            vs_resident=round(resident_wall / stream_wall, 4),
        ),
    }
    _emit(record)
    print(f"# stream: {plan.n_slabs} slab(s) x {plan.slab_rows} rows, "
          f"overlap={stats['overlap_ratio']:.2%}, "
          f"upload={stats['upload_seconds_total']:.2f}s "
          f"kernel={stats['kernel_seconds_total']:.2f}s, "
          f"resident {resident_wall:.2f}s vs streamed "
          f"{stream_wall:.2f}s", file=sys.stderr)


# one small train, run twice in fresh interpreter processes sharing one
# fresh compile-cache dir: the SECOND run's compile_s_total is what a
# warm-started replica/trainer actually pays (obs/xla measures the real
# lower+compile wall time per program boundary)
_COLDSTART_CHILD = r'''
import json, os, sys, time
sys.path.insert(0, os.environ["COLDSTART_REPO"])
from lightgbm_tpu.obs.xla import global_xla
global_xla.enable()
from lightgbm_tpu.compile_cache import configure
configure("on", os.environ["COLDSTART_CACHE_DIR"])
import numpy as np
import lightgbm_tpu as lgb
n = int(os.environ.get("COLDSTART_ROWS", "20000")); f = 28
rng = np.random.RandomState(0)
x = rng.randn(n, f).astype(np.float32)
y = (x[:, 0] + 0.6 * x[:, 1] ** 2 > 0.2).astype(np.float32)
params = {"objective": "binary",
          "num_leaves": int(os.environ.get("COLDSTART_LEAVES", "63")),
          "max_bin": 63,
          "min_sum_hessian_in_leaf": 100, "min_data_in_leaf": 0,
          "verbosity": -1}
t0 = time.perf_counter()
ds = lgb.Dataset(x, label=y, params=params)
ds.construct()
bst = lgb.train(params, ds,
                num_boost_round=int(os.environ.get("COLDSTART_ITERS", "2")))
t1 = time.perf_counter()
bst.predict(x[:8])
first_pred_s = time.perf_counter() - t1
s = global_xla.summary()
print("COLDSTART " + json.dumps({
    "compile_s_total": s["compile_s_total"],
    "trace_s_total": s["trace_s_total"],
    "cache_load_s_total": s["cache_load_s_total"],
    "n_cache_hits": s["n_cache_hits"], "n_programs": s["n_programs"],
    "wall_s": round(time.perf_counter() - t0, 3),
    "first_pred_s": round(first_pred_s, 4)}), flush=True)
'''


def _coldstart_child_run(cache_dir: str, rows: int) -> dict:
    """One interpreter-fresh train against `cache_dir`; returns the
    child's COLDSTART json dict (raises on a dead/invalid child)."""
    env = dict(os.environ)
    # the parent may itself run under a warm cache; the cold/warm pair
    # must only ever see the dedicated fresh dir or the "cold" half
    # measures nothing — and an inherited JAX_COMPILATION_CACHE_DIR
    # would outrank it (compile_cache.configure). This deliberately
    # fresh directory is the one place a cache path is not the
    # environment's or the checkout's.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["COLDSTART_REPO"] = os.path.dirname(os.path.abspath(__file__))
    env["COLDSTART_CACHE_DIR"] = cache_dir
    env["COLDSTART_ROWS"] = str(rows)
    out = subprocess.run([sys.executable, "-c", _COLDSTART_CHILD],
                         env=env, capture_output=True, text=True,
                         timeout=float(os.environ.get(
                             "BENCH_COLDSTART_TIMEOUT", 600)))
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("COLDSTART "):
            return json.loads(line[len("COLDSTART "):])
    raise RuntimeError(f"coldstart child died rc={out.returncode}: "
                       f"{out.stderr[-800:]}")


def _measure_coldstart():
    """Cold-start bench (ISSUE 14): (1) the SAME small train run in two
    fresh interpreter processes sharing one fresh persistent-cache dir —
    the cold run pays real XLA compiles, the warm rerun's
    ``compile_s_total`` (obs/xla, the real per-program lower+compile
    wall time) should be ~zero; (2) serialized-artifact serving — a
    ModelServer stood up against a saved artifact store must serve its
    first low-latency request with ZERO serve/lowlat compiles, counted
    through the obs recompile counters. Emits
    ``coldstart_compile_reduction`` (cold/warm compile seconds) plus the
    ``coldstart`` summary dict perf-gate check 10 caps."""
    import asyncio
    import shutil

    n = int(os.environ.get("BENCH_ROWS", 20_000))
    import jax
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from lightgbm_tpu.model_io import LoadedModel
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.serve import (ModelRegistry, ModelServer,
                                    SERVE_LOWLAT_TAG, serialize_available)

    cache_dir = tempfile.mkdtemp(prefix="coldstart_cache_")
    art_dir = tempfile.mkdtemp(prefix="coldstart_art_")
    try:
        # a chip belongs to one process at a time: the two children run
        # BEFORE this parent initialises a backend (imports above only;
        # the first backend touch is default_backend() below) and each
        # has exited before the next thing that needs the chip starts
        cold = _coldstart_child_run(cache_dir, n)
        warm = _coldstart_child_run(cache_dir, n)
        platform = jax.default_backend()
        # real compile seconds only: a cache-warm process LOADS its
        # programs (cache_load_s_total, reported alongside) — the floor
        # keeps the ratio finite when warm compiles are exactly zero
        reduction = cold["compile_s_total"] / max(warm["compile_s_total"],
                                                 1e-2)

        # -- phase 2: artifact-store serving restore (in-process; the
        # counters, not process identity, prove no compile ran: a fresh
        # LowLatencyPredictor shares nothing with the exporter but the
        # on-disk artifacts)
        f = 28
        rng = np.random.RandomState(0)
        trees = _random_trees(
            rng, int(os.environ.get("BENCH_COLDSTART_TREES", 50)), 63, f)
        model = LoadedModel()
        model.trees = trees
        model.num_tree_per_iteration = 1
        model.objective_str = "binary sigmoid:1"
        model.max_feature_idx = f - 1

        reg_a = ModelRegistry(artifact_dir=art_dir)
        entry_a = reg_a.load("bench", model=model)
        c0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        t0 = time.perf_counter()
        n_progs = entry_a.lowlat.warm(f)
        export_s = time.perf_counter() - t0
        export_compiles = global_metrics.recompiles(SERVE_LOWLAT_TAG) - c0
        req = rng.randn(4, f)
        ref = entry_a.lowlat(req)

        # replica restart: a fresh registry/server against the store
        reg_b = ModelRegistry(artifact_dir=art_dir)
        entry_b = reg_b.load("bench", model=model)
        server = ModelServer(reg_b)
        c1 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        loads0 = global_metrics.counters.get("serve/aot_loads", 0)
        t0 = time.perf_counter()
        entry_b.lowlat.warm(f)
        restore_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = asyncio.run(server.predict("bench", req, raw_score=True))
        first_req_s = time.perf_counter() - t0
        restore_compiles = global_metrics.recompiles(SERVE_LOWLAT_TAG) - c1
        restore_loads = global_metrics.counters.get("serve/aot_loads",
                                                    0) - loads0
        # ref is raw [B, K]; server.predict squeezes K=1 to [B]
        bit_equal = bool(np.array_equal(
            np.squeeze(np.asarray(ref, np.float64)),
            np.squeeze(np.asarray(out, np.float64))))
        asyncio.run(server.close())
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(art_dir, ignore_errors=True)

    unit = ("x warm/cold compile reduction (train n=%d, %d programs"
            % (n, cold["n_programs"]))
    if platform != "tpu":
        unit += ", platform=%s" % platform
    if not bit_equal:
        unit += ", PARITY-MISMATCH"
    unit += ")"
    record = {
        "metric": "coldstart_compile_reduction",
        "value": round(reduction, 2),
        "unit": unit,
        # anchor: how much of the cold compile bill warm start removes
        "vs_baseline": round(reduction, 2),
        "coldstart": {
            "cold_compile_s": cold["compile_s_total"],
            "warm_compile_s": warm["compile_s_total"],
            "compile_reduction": round(reduction, 2),
            "cold_trace_s": cold.get("trace_s_total", 0.0),
            "warm_trace_s": warm.get("trace_s_total", 0.0),
            "cold_cache_load_s": cold.get("cache_load_s_total", 0.0),
            "warm_cache_load_s": warm.get("cache_load_s_total", 0.0),
            "warm_cache_hits": warm.get("n_cache_hits", 0),
            "cold_wall_s": cold["wall_s"],
            "warm_wall_s": warm["wall_s"],
            "cold_first_pred_s": cold["first_pred_s"],
            "warm_first_pred_s": warm["first_pred_s"],
            "artifact_serialize_available": serialize_available(),
            "artifact_programs": int(n_progs),
            "artifact_export_compiles": int(export_compiles),
            "artifact_export_s": round(export_s, 3),
            "artifact_restore_s": round(restore_s, 4),
            "restore_aot_loads": int(restore_loads),
            "restore_lowlat_compiles": int(restore_compiles),
            "first_request_s": round(first_req_s, 4),
            "restore_bit_identical": bit_equal,
        },
    }
    _emit(record)
    print(f"# coldstart: compile {cold['compile_s_total']:.2f}s cold -> "
          f"{warm['compile_s_total']:.2f}s warm ({reduction:.1f}x); "
          f"artifact restore {restore_s*1e3:.0f}ms / "
          f"{restore_compiles} compiles / {restore_loads} loads, "
          f"first request {first_req_s*1e3:.0f}ms bit_equal={bit_equal}",
          file=sys.stderr)


_MODE_MEASURE = {"train": _measure, "predict": _measure_predict,
                 "serve": _measure_serve, "fleet": _measure_fleet,
                 "continual": _measure_continual,
                 "stream": _measure_stream, "coldstart": _measure_coldstart,
                 "shap": _measure_shap, "rank": _measure_rank}


def _emit_partial_obs(mode: str, exc) -> None:
    """A failed measurement still surfaces its partial obs summary
    (phase self-times + compile/recompile attribution so far) as one
    stderr comment line before the failure propagates."""
    try:
        partial = {"metric": _MODE_METRIC.get(mode, mode), "partial": True,
                   "error": repr(exc)[:300]}
        if _telemetry_enabled():
            from lightgbm_tpu.obs import global_tracer
            phases = {name: round(agg["seconds"], 4)
                      for name, agg in global_tracer.summary().items()}
            if phases:
                partial["phases"] = phases
            from lightgbm_tpu.obs.xla import global_xla
            xs = global_xla.summary()
            if xs["n_programs"]:
                partial["compile_s_total"] = xs["compile_s_total"]
                partial["n_recompiles_by_phase"] = \
                    xs["n_recompiles_by_phase"]
        print("# obs-partial: " + json.dumps(partial), file=sys.stderr,
              flush=True)
    except Exception:
        pass  # the partial dump must never mask the real failure


if __name__ == "__main__":
    main()
