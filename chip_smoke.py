#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls —
``lgb.Dataset`` -> ``lgb.train`` -> ``Booster.predict`` ->
``serve.ModelServer`` — on ONE TPU chip, at the flagship configuration's
full width (Higgs shape: 28 features, 255 leaves, 63 bins, every ``tpu_*``
parameter at its default), on seeded synthetic data, and checks what comes
out by the repo's own means. Depth is cut (5 boosting rounds); nothing else.

    python chip_smoke.py [--rows N] [--seed S]   # one chip, five phases
    python chip_smoke.py --chips 4               # ONLY the sharded phase

Phases (default run): 1 device, 2 train, 3 compare on the chip (Pallas vs
the plain XLA contraction, int8 vs f32), 4 predict (device engine vs host
tree walk), 5 serve (in-process server + artifact-store restore). With
``--chips 4``: the device phase, then ``tree_learner=data`` over a
four-device mesh against the serial model trained on device 0 in the same
process, and one ``tree_learner=voting`` iteration — and no other phase.

Contract: the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
and everything else is printed on earlier lines. Any phase that raises ends
the run non-zero with no such line; no ``try/except`` turns a failed phase
into a printed note. Without an accelerator (``JAX_PLATFORMS=cpu``, or no
chip) it exits non-zero in phase 1. One process touches JAX; no child needs
the chip. The times printed are plain wall-clock notes of one run, not
benchmark results.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

import numpy as np

NUM_FEATURES = 28
ROUNDS = 5
N_COMPARE = 200_000   # rows of the on-chip comparisons and of phase 4
N_HELD_OUT = 50_000   # fresh rows every AUC is taken on

# the flagship configuration (bench.py, docs/GPU-Performance.rst:108-123)
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
          "min_sum_hessian_in_leaf": 100, "learning_rate": 0.1,
          "verbosity": -1}
# the plain reference on the same chip: one-hot XLA contraction at f32-
# faithful precision, gradients materialised outside the histogram pass
XLA_REFERENCE = {"tpu_hist_impl": "xla", "tpu_hist_precision": "highest",
                 "tpu_fused_grad": "off"}

# Tolerances, and why.
# 5 rounds at lr=0.1 on this data reach ~0.9 held-out AUC on CPU; 0.85
# leaves room for the chip's bf16 histograms and still rejects a model
# that learned nothing (0.5).
MIN_AUC = 0.85
# Same data, same algorithm: the default path differs from the XLA
# reference only by bf16 rounding of the histogram operands, which can
# flip near-tied splits deep in a tree but not the quality of the model.
AUC_TOL_VS_REFERENCE = 2e-3
# The first tree's leaf count. With min_sum_hessian_in_leaf=100 a 200k-row
# tree stops short of 255 leaves where no child would keep 100 of hessian
# (~400 rows); a candidate whose child sits within bf16 rounding of that
# bound is allowed on one path and not on the other, and each such flip
# moves the count by one and reshapes the subtree under it. First chip
# run (PR 21): 249 leaves default, 247 reference, 249 quantized. 5% of
# num_leaves still catches a kernel that truncates or mis-routes growth.
LEAF_COUNT_TOL = 0.05
# Quantized gradients (int8, stochastic rounding) are a different, noisier
# estimator by design; the reference reports ~1e-3 AUC cost on Higgs.
AUC_TOL_QUANTIZED = 5e-3
# Device traversal sums f32 leaf values in a fixed order; the host walk
# sums float64. 5 trees of |leaf| <= ~0.2: differences are ~1e-7.
PREDICT_TOL = 1e-5
# TreeSHAP's f32 recurrence against the f32 traversal (tools/check_shap.py
# and the verify recipe hold the same bound).
CONTRIB_SUM_TOL = 2e-3
# One voting iteration only has to have learned something.
MIN_AUC_VOTING = 0.7

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_events = {"programs": 0, "cache_hits": 0}


def log(msg: str = "") -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")
    log(f"  ok: {what}")


def make_data(n: int, seed: int):
    """Higgs-shaped synthetic rows, as bench.py's train mode makes them."""
    rng = np.random.RandomState(seed)
    x = rng.randn(n, NUM_FEATURES).astype(np.float32)
    logit = (x[:, 0] + 0.6 * x[:, 1] ** 2 + 0.4 * x[:, 2] * x[:, 3]
             - 0.3 * np.abs(x[:, 4]) + 0.5 * rng.randn(n))
    return x, (logit > 0.2).astype(np.float32)


def _watch_compiles() -> None:
    """Count every program this process acquires (compiled or loaded from
    the persistent cache) and every persistent-cache hit, by JAX's own
    monitoring events."""
    import jax.monitoring as monitoring

    def on_duration(event, duration, **kw):
        if event == _BACKEND_COMPILE:
            _events["programs"] += 1

    def on_event(event, **kw):
        if event == _CACHE_HIT:
            _events["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
def phase_device(chips: int) -> dict:
    log("== phase 1: device")
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX's default backend is "
                 f"{backend!r} (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    devs = jax.devices()
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX reports {len(devs)}")
    import importlib.metadata as md
    import jaxlib
    log(f"  platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)}")
    log(f"  jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={md.version('libtpu')}")
    # the persistent compile cache is armed BEFORE the first compile
    from lightgbm_tpu import compile_cache, native
    compile_cache.configure("auto")
    _watch_compiles()
    log(f"  compile cache: {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', 'unset')}, "
        f"{compile_cache.cache_size_bytes() >> 20} MiB at start)")
    had_lib = os.path.exists(native._LIB_PATH)
    log("  native parser/binner: "
        + (f"loaded {os.path.basename(native._LIB_PATH)} "
           f"({'found on disk' if had_lib else 'built on this machine'})"
           if native.available() else "NOT available - NumPy path bins"))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_line(dev) -> str:
    st = dev.memory_stats()
    return (f"device {dev.id}: in_use={st['bytes_in_use'] / 2**20:.0f} MiB "
            f"peak={st['peak_bytes_in_use'] / 2**20:.0f} MiB "
            f"limit={st['bytes_limit'] / 2**20:.0f} MiB")


def timed_train(params, x, y, rounds=ROUNDS):
    """``lgb.train`` through the public API, with the wall clock and the
    program count read after every iteration (clock stopped by
    ``block_until_ready`` on the scores)."""
    import jax
    import lightgbm_tpu as lgb
    t0 = time.perf_counter()
    ds = lgb.Dataset(x, label=y, params=params)
    ds.construct()
    bin_s = time.perf_counter() - t0
    marks = []

    def after_iteration(env):
        jax.block_until_ready(env.model._gbdt.scores)
        marks.append((time.perf_counter(), _events["programs"]))

    t1 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=rounds,
                    callbacks=[after_iteration])
    stamps = [t1] + [m[0] for m in marks]
    iter_s = [b - a for a, b in zip(stamps, stamps[1:])]
    new_programs = marks[-1][1] - marks[0][1]
    return bst, bin_s, iter_s, new_programs


def check_mosaic_ran(gbdt) -> None:
    """The iteration program the chip just ran holds the Mosaic kernel:
    not the XLA twin, not interpret mode."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas_histogram import _resolve_interpret
    check(gbdt._hist_impl == "pallas", "resolved histogram impl is pallas")
    check(_resolve_interpret(None) is False, "Pallas interpret mode is off")
    args = (gbdt.bins_fm, tuple(gbdt._valid_bins), gbdt._obj_state(),
            gbdt.scores, gbdt._sample_mask, tuple(gbdt._valid_scores),
            jnp.int32(gbdt.iter), jnp.float32(gbdt.shrinkage_rate))
    text = gbdt._fused.lower(*args).compile().as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, f"compiled boosting/fused_iter holds {n} tpu_custom_call "
                 "(Mosaic) ops")


def phase_train(rows: int, seed: int):
    log(f"== phase 2: train (N={rows}, {NUM_FEATURES} features, "
        f"{PARAMS['num_leaves']} leaves, {PARAMS['max_bin']} bins, "
        f"{ROUNDS} rounds)")
    import jax
    t0 = time.perf_counter()
    x, y = make_data(rows, seed)
    log(f"  data made in {time.perf_counter() - t0:.1f} s")
    hits0 = _events["cache_hits"]
    bst, bin_s, iter_s, new_programs = timed_train(PARAMS, x, y)
    log(f"  bin seconds: {bin_s:.2f}")
    log(f"  first iteration (set-up + compile) seconds: {iter_s[0]:.2f} "
        f"(persistent-cache hits so far: {_events['cache_hits'] - hits0})")
    log("  per-iteration seconds after it: "
        + " ".join(f"{s:.4f}" for s in iter_s[1:]))
    log("  " + _memory_line(jax.devices()[0]))
    check(new_programs == 0,
          f"{new_programs} programs compiled or loaded after iteration 1")
    check_mosaic_ran(bst._gbdt)
    return bst, x, y


def auc(bst, x, y) -> float:
    from lightgbm_tpu.metrics import _auc
    return _auc(y, np.asarray(bst.predict(x, raw_score=True), np.float64))


def first_tree(bst):
    """(root feature, root threshold bin, leaf count, every split as a
    (feature, bin) pair) of the first tree."""
    tree = bst._gbdt.models[0][0]
    n = tree.num_leaves - 1
    splits = sorted(zip(tree.split_feature[:n].tolist(),
                        tree.threshold_bin[:n].tolist()))
    return (int(tree.split_feature[0]), int(tree.threshold_bin[0]),
            int(tree.num_leaves), splits)


def common_splits(a, b) -> int:
    """Size of the multiset intersection of two trees' (feature, bin)
    splits — how much of the tree two runs agree on, for the log."""
    from collections import Counter
    return sum((Counter(a[3]) & Counter(b[3])).values())


def describe(name: str, bst, xh, yh):
    tree = first_tree(bst)
    a = auc(bst, xh, yh)
    log(f"  {name}: first tree root=(feature {tree[0]}, bin {tree[1]}) "
        f"leaves={tree[2]}; held-out AUC={a:.5f}")
    return tree, a


def phase_compare(x, y, seed: int) -> None:
    log(f"== phase 3: compare on the chip (N={N_COMPARE}, {ROUNDS} rounds, "
        f"AUC on {N_HELD_OUT} fresh rows)")
    xc, yc = x[:N_COMPARE], y[:N_COMPARE]
    xh, yh = make_data(N_HELD_OUT, seed + 1)
    runs = {}
    for name, extra in (("default (pallas, bf16 hist, fused grad)", {}),
                        ("reference (xla, highest, unfused)", XLA_REFERENCE),
                        ("use_quantized_grad (int8 kernel)",
                         {"use_quantized_grad": True})):
        t0 = time.perf_counter()
        bst = timed_train({**PARAMS, **extra}, xc, yc)[0]
        runs[name] = describe(name, bst, xh, yh)
        log(f"    ({time.perf_counter() - t0:.1f} s incl. compile)")
        if extra.get("use_quantized_grad"):
            check(bst._gbdt._quant_enabled
                  and bst._gbdt._hist_impl == "pallas",
                  "quantized run took the int8 Pallas kernel")
        if extra is XLA_REFERENCE:
            check(bst._gbdt._hist_impl == "xla"
                  and bst._gbdt._fused_grad_fn is None,
                  "reference run took the unfused XLA contraction")
    (d_tree, d_auc), (r_tree, r_auc), (q_tree, q_auc) = runs.values()
    # deeper splits may differ in near-ties under bf16: printed, not
    # asserted. The root sees every row and must agree.
    log(f"  first-tree splits in common with the reference: default "
        f"{common_splits(d_tree, r_tree)}, quantized "
        f"{common_splits(q_tree, r_tree)} of {r_tree[2] - 1}")
    check(d_tree[:2] == r_tree[:2],
          f"same root split, default vs reference: {d_tree[:2]}")
    leaf_tol = int(LEAF_COUNT_TOL * PARAMS["num_leaves"])
    check(abs(d_tree[2] - r_tree[2]) <= leaf_tol,
          f"first-tree leaf counts {d_tree[2]} vs {r_tree[2]} within "
          f"{leaf_tol}")
    check(min(d_auc, r_auc) >= MIN_AUC,
          f"both held-out AUCs >= {MIN_AUC}")
    check(abs(d_auc - r_auc) <= AUC_TOL_VS_REFERENCE,
          f"|AUC default - reference| = {abs(d_auc - r_auc):.2e} <= "
          f"{AUC_TOL_VS_REFERENCE}")
    check(abs(q_auc - d_auc) <= AUC_TOL_QUANTIZED,
          f"|AUC quantized - default| = {abs(q_auc - d_auc):.2e} <= "
          f"{AUC_TOL_QUANTIZED}")


def phase_predict(bst, x) -> None:
    log(f"== phase 4: predict ({N_COMPARE} rows, device engine vs host "
        "tree walk)")
    xp = x[:N_COMPARE]
    gbdt = bst._gbdt
    t0 = time.perf_counter()
    dev = np.asarray(bst.predict(xp, raw_score=True), np.float64)
    t1 = time.perf_counter()
    dev2 = np.asarray(bst.predict(xp, raw_score=True), np.float64)
    t2 = time.perf_counter()
    host = gbdt._predict_raw_host(np.asarray(xp, np.float64), 0,
                                  len(gbdt.models))[:, 0]
    log(f"  device engine: first call {t1 - t0:.2f} s (compile), second "
        f"{t2 - t1:.3f} s; host walk {time.perf_counter() - t2:.1f} s")
    check(dev.shape == (len(xp),) and bool(np.all(np.isfinite(dev))),
          "raw scores finite, one per row")
    check(np.array_equal(dev, dev2), "device engine repeats bit for bit")
    diff = float(np.max(np.abs(dev - host)))
    check(diff <= PREDICT_TOL,
          f"max |device - host| raw score = {diff:.2e} <= {PREDICT_TOL}")


def phase_serve(bst, x) -> None:
    log("== phase 5: serve (in-process ModelRegistry + ModelServer, then "
        "artifact-store restore)")
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.serve import (ModelRegistry, ModelServer,
                                    SERVE_LOWLAT_TAG)
    # 1-64 rows ride the low-latency AOT ladder, more coalesce in the
    # micro-batcher: both routes, predict and explain
    predict_sizes = (1, 3, 17, 64, 65, 200, 333, 512)
    explain_sizes = (8, 100)
    lo = np.cumsum((0,) + predict_sizes + explain_sizes)
    blocks = [x[a:b] for a, b in zip(lo, lo[1:])]
    predict_blocks = blocks[:len(predict_sizes)]
    explain_blocks = blocks[len(predict_sizes):]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_art_") as art:
        reg = ModelRegistry(artifact_dir=art)
        entry = reg.load("smoke", booster=bst)
        server = ModelServer(reg)

        async def traffic():
            try:
                return await asyncio.gather(
                    *[server.predict("smoke", b, raw_score=True)
                      for b in predict_blocks],
                    *[server.explain("smoke", b) for b in explain_blocks])
            finally:
                await server.close()

        t0 = time.perf_counter()
        answers = asyncio.run(traffic())
        log(f"  {len(predict_blocks)} predict + {len(explain_blocks)} "
            f"explain requests answered in {time.perf_counter() - t0:.1f} s "
            "(cold: includes their compiles)")
        model = entry.model
        for b, got in zip(predict_blocks, answers):
            check(np.array_equal(got, model.predict(b, raw_score=True)),
                  f"predict response, {len(b)} rows, equals the direct "
                  "engine bit for bit")
        for b, got in zip(explain_blocks, answers[len(predict_blocks):]):
            check(np.array_equal(got, model.predict_contrib(b)),
                  f"explain response, {len(b)} rows, equals "
                  "predict_contrib bit for bit")
            raw = np.asarray(model.predict(b, raw_score=True)).ravel()
            err = float(np.max(np.abs(got.sum(axis=1) - raw))
                        / max(np.max(np.abs(raw)), 1.0))
            check(err < CONTRIB_SUM_TOL,
                  f"contributions sum to the raw score (rel. {err:.1e})")

        # replica restart: a second registry shares nothing with the
        # first but the artifact directory
        n = entry.lowlat.warm(NUM_FEATURES)
        req = x[1000:1005]
        ref = entry.lowlat(req)
        reg_b = ModelRegistry(artifact_dir=art)
        entry_b = reg_b.load("smoke", booster=bst)
        compiles0 = global_metrics.recompiles(SERVE_LOWLAT_TAG)
        loads0 = global_metrics.counters.get("serve/aot_loads", 0)
        programs0 = _events["programs"]
        t0 = time.perf_counter()
        n_b = entry_b.lowlat.warm(NUM_FEATURES)
        restore_s = time.perf_counter() - t0
        out = entry_b.lowlat(req)
        loads = global_metrics.counters.get("serve/aot_loads", 0) - loads0
        log(f"  restored {n_b} low-latency programs in {restore_s:.2f} s")
        check(n_b == n and loads == n,
              f"all {n} programs of the ladder loaded from the store")
        check(global_metrics.recompiles(SERVE_LOWLAT_TAG) == compiles0
              and _events["programs"] == programs0,
              "restore compiled nothing")
        check(np.array_equal(ref, out),
              "restored programs answer bit for bit")


# ---------------------------------------------------------------------------
def phase_sharded(rows: int, seed: int) -> None:
    log(f"== sharded phase: tree_learner=data over 4 devices vs serial on "
        f"device 0 (N={rows}, {ROUNDS} rounds)")
    import jax
    x, y = make_data(rows, seed)
    xh, yh = make_data(N_HELD_OUT, seed + 1)
    mesh4 = {"tree_learner": "data", "tpu_num_shards": 4}
    runs, models = {}, {}
    for name, extra in (
            ("serial, device 0", {}),
            ("data, tpu_hist_reduce default", mesh4),
            ("data, tpu_hist_reduce=psum",
             {**mesh4, "tpu_hist_reduce": "psum"})):
        bst, bin_s, iter_s, new_programs = timed_train(
            {**PARAMS, **extra}, x, y)
        runs[name] = describe(name, bst, xh, yh)
        log(f"    first iteration {iter_s[0]:.1f} s, then "
            + " ".join(f"{s:.3f}" for s in iter_s[1:])
            + f" s; programs after iteration 1: {new_programs}")
        gbdt = bst._gbdt
        if extra:
            check(gbdt.mesh.size == 4 and gbdt._hist_impl == "pallas",
                  "4-device mesh, per-shard Pallas kernel")
            log(f"    hist_reduce resolved to {gbdt._hist_reduce}; bins "
                f"sharding {gbdt.bins_fm.sharding.spec}")
            shard_rows = {s.device.id: s.data.shape[1]
                          for s in gbdt.bins_fm.addressable_shards}
            check(sorted(shard_rows) == [d.id for d in jax.devices()[:4]]
                  and set(shard_rows.values()) == {rows // 4},
                  f"bin rows spread over four devices: {shard_rows}")
        for dev in jax.devices():
            log("    " + _memory_line(dev))
        models[name] = [np.concatenate([t.split_feature, t.threshold_bin,
                                        t.leaf_value])
                        for it in gbdt.models for t in it]
    serial, scatter, psum = runs.values()
    log(f"  first-tree splits in common with serial: scatter "
        f"{common_splits(scatter[0], serial[0])}, psum "
        f"{common_splits(psum[0], serial[0])} of {serial[0][2] - 1}")
    check(serial[0][:2] == scatter[0][:2] == psum[0][:2],
          f"same first-tree root split on all three: {serial[0][:2]}")
    counts = [r[0][2] for r in runs.values()]
    leaf_tol = int(LEAF_COUNT_TOL * PARAMS["num_leaves"])
    check(max(counts) - min(counts) <= leaf_tol,
          f"first-tree leaf counts {counts} within {leaf_tol}")
    aucs = [r[1] for r in runs.values()]
    check(min(aucs) >= MIN_AUC, f"all held-out AUCs >= {MIN_AUC}")
    check(max(aucs) - min(aucs) <= AUC_TOL_VS_REFERENCE,
          f"AUC spread {max(aucs) - min(aucs):.2e} <= "
          f"{AUC_TOL_VS_REFERENCE}")
    # tests/test_scatter.py asserts this on virtual CPU devices; real ICI
    # may order the two reductions differently, so it is reported only
    _, m_scatter, m_psum = models.values()
    same = all(np.array_equal(a, b) for a, b in zip(m_scatter, m_psum))
    log(f"  scatter and psum models bit-identical on this mesh (every "
        f"tree's splits, threshold bins and leaf values): {same}")

    log("== sharded phase: one tree_learner=voting iteration")
    t0 = time.perf_counter()
    bst = timed_train({**PARAMS, "tree_learner": "voting",
                       "tpu_num_shards": 4}, x, y, rounds=1)[0]
    _, a = describe("voting, 1 iteration", bst, xh, yh)
    log(f"    ({time.perf_counter() - t0:.1f} s incl. compile)")
    check(a >= MIN_AUC_VOTING, f"voting AUC >= {MIN_AUC_VOTING}")
    for dev in jax.devices():
        log("    " + _memory_line(dev))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 20,
                    help="training rows (default 1,048,576)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rows < N_COMPARE:
        ap.error(f"--rows must be at least {N_COMPARE}")
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded(args.rows, args.seed)
    else:
        bst, x, y = phase_train(args.rows, args.seed)
        phase_compare(x, y, args.seed)
        phase_predict(bst, x)
        phase_serve(bst, x)
    log(f"all phases passed in {time.perf_counter() - t0:.0f} s; programs "
        f"acquired: {_events['programs']}, of them persistent-cache hits: "
        f"{_events['cache_hits']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
