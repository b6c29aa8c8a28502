"""Out-of-core streaming training (tpu_stream; ISSUE 13).

Covers:
- slab packing/bounds (ops/bin_pack) and the shared double-buffered
  feed + stats (io/streaming) — the one pipeline behind predict chunks
  and training slabs;
- streamed-vs-resident bit-identity across the sampling matrix
  (plain/bagging/GOSS/DART/quantized/2-shard/RF) at a fits-in-HBM
  fixture (single-slab plan => the SAME fused program on an uploaded
  operand);
- slab-boundary semantics: int8-quantized streaming is bit-identical
  at ANY slab count (exact integer partial sums, uneven tails
  included), f32 multi-slab agrees to float-add-association tolerance;
- preflight honesty: a clamped HBM budget keeps ``fits`` False for
  resident while ``fits_streaming`` goes True with a ``tpu_stream``
  recommendation, and ``tpu_stream=auto`` then actually streams;
- PR-8 interplay: SIGTERM mid-stream checkpoints and the resumed run
  finishes bit-identically to the never-killed streamed run;
- knob honesty, obs meta/OpenMetrics export, and the quick-tier tools
  (tools/check_stream.py, perf-gate check 9).
"""

import json
import os
import re
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io import streaming as stream_mod
from lightgbm_tpu.io.streaming import (HostSlabBins, StreamStats,
                                       double_buffered,
                                       global_stream_stats)
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.ops import bin_pack as bp

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def _data(n=1500, f=6, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.3).astype(np.float32)
    return X, y


def _train(X, y, extra, iters=3, rounds=None, valid=None):
    params = {**dict(objective="binary", num_leaves=15, learning_rate=0.1,
                     max_bin=63, min_data_in_leaf=5, verbosity=-1),
              **extra}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    vs = (None if valid is None else
          [lgb.Dataset(valid[0], label=valid[1], params=params,
                       reference=ds)])
    return lgb.train(params, ds, num_boost_round=rounds or iters,
                     valid_sets=vs)


def _strip_params(s):
    """Streamed/resident models differ only in the echoed params block
    (tpu_stream on vs auto); strip it for bit-identity compares."""
    return re.sub(r"\nparameters:.*?end of parameters", "", s, flags=re.S)


# ---------------------------------------------------------------------------
class TestSlabPacking:
    def test_bounds_are_section_aligned(self):
        align = bp.slab_align(15)  # vpb=2 -> 4096 rows
        assert align == 2 * bp.PACK_ALIGN
        bounds = bp.slab_bounds(10_000, 1, 15)
        assert bounds[0] == (0, align)
        assert bounds[-1][1] == 10_000
        for lo, hi in bounds[:-1]:
            assert (hi - lo) == align

    def test_single_slab_when_rows_cover(self):
        assert bp.slab_bounds(1000, 1000, 63) == [(0, 1000)]

    def test_pack_bins_range_matches_full_pack_slice(self):
        r = np.random.RandomState(1)
        bins = r.randint(0, 15, size=(4, 5000)).astype(np.uint8)
        slab = bp.pack_bins_range(bins, 15, 2048, 4096)
        assert isinstance(slab, bp.PackedBins)
        assert slab.num_data == 2048
        # unpacking the slab reproduces the raw slice exactly
        import jax.numpy as jnp
        dev = bp.PackedBins(jnp.asarray(slab.data), slab.num_data,
                            slab.vpb)
        assert np.array_equal(np.asarray(bp.unpack_bins(dev)),
                              bins[:, 2048:4096])

    def test_unpackable_width_returns_raw_slice(self):
        r = np.random.RandomState(1)
        bins = r.randint(0, 63, size=(4, 3000)).astype(np.uint8)
        slab = bp.pack_bins_range(bins, 63, 0, 2048)
        assert isinstance(slab, np.ndarray)
        assert np.array_equal(slab, bins[:, :2048])

    def test_host_slab_bins_plan(self):
        r = np.random.RandomState(2)
        bins = r.randint(0, 63, size=(3, 5000)).astype(np.uint8)
        plan = HostSlabBins(bins, 63, 2048)
        assert plan.n_slabs == 3
        assert plan.bounds == [(0, 2048), (2048, 4096), (4096, 5000)]
        assert plan.shape == (3, 5000)
        assert plan.nbytes_host == 3 * 5000


class TestDoubleBufferedFeed:
    def test_order_preserved(self):
        staged = []
        out = list(double_buffered([1, 2, 3], lambda x: staged.append(x)
                                   or x * 10))
        assert out == [10, 20, 30]
        assert staged == [1, 2, 3]

    def test_stage_runs_ahead_of_consumption(self):
        events = []
        gen = double_buffered([0, 1, 2], lambda i: events.append(
            ("stage", i)) or i)
        first = next(gen)
        events.append(("consume", first))
        # by the time item 0 is consumable, item 1 is already staged
        assert events == [("stage", 0), ("stage", 1), ("consume", 0)]

    def test_stats_overlap_accounting(self):
        st = StreamStats()
        items = [np.zeros(10, np.uint8)] * 3

        def stage(x):
            return x
        gen = double_buffered(items, stage, st)
        for _ in gen:
            st.note_dispatch()
        assert st.uploads_total == 3
        # items 0 and 1 stage before any compute dispatches; item 2
        # stages while item 0's dispatched compute is in flight
        assert st.overlapped_uploads_total == 1
        st.note_block(0.01)
        assert st.kernel_seconds_total > 0
        assert 0.0 <= st.overlap_ratio <= 1.0

    def test_empty(self):
        assert list(double_buffered([], lambda x: x)) == []


# ---------------------------------------------------------------------------
MATRIX = {
    "plain": {},
    "bagging": {"bagging_fraction": 0.7, "bagging_freq": 1},
    "goss": {"data_sample_strategy": "goss"},
    "dart": {"boosting": "dart", "drop_rate": 0.5, "max_drop": 5},
    "quantized": {"use_quantized_grad": True},
    "2shard": {"tree_learner": "data", "tpu_num_shards": 2},
    "rf": {"boosting": "rf", "bagging_fraction": 0.7, "bagging_freq": 1},
    # the stages both compositions share (ISSUE 30). "valid": a valid
    # set rides along; "slabs": streamed in two slabs, so the streamed
    # composition itself runs (int8 sums are exact at any slab count)
    "dart+valid": {"boosting": "dart", "drop_rate": 0.5, "max_drop": 5,
                   "valid": True},
    "quantized+renew": {"use_quantized_grad": True,
                        "quant_train_renew_leaf": True, "slabs": True},
    "goss+quantized": {"data_sample_strategy": "goss",
                       "use_quantized_grad": True},
    "multiclassova+dart": {"objective": "multiclassova", "num_class": 3,
                           "boosting": "dart", "drop_rate": 0.5,
                           "max_drop": 5},
}


class TestBitIdentity:
    @pytest.mark.parametrize("name", sorted(MATRIX))
    def test_streamed_matches_resident(self, name):
        extra = dict(MATRIX[name])
        valid = _data(400, seed=5) if extra.pop("valid", False) else None
        slabs = 2 if extra.pop("slabs", False) else 1
        stream = {"tpu_stream": "on",
                  "tpu_stream_slab_rows": 2048 if slabs == 2 else 0}
        X, y = _data(3000 if slabs == 2 else 1500)
        if "num_class" in extra:
            y = (np.abs(X[:, 0] * 3).astype(int) % 3).astype(np.float32)
        resident = _train(X, y, extra, valid=valid).model_to_string()
        streamed = _train(X, y, {**extra, **stream}, valid=valid)
        assert streamed._gbdt._stream.n_slabs == slabs
        assert _strip_params(streamed.model_to_string()) == \
            _strip_params(resident)

    def test_streamed_matches_resident_with_valid_set(self):
        X, y = _data()
        Xv, yv = _data(400, seed=5)

        def run(extra):
            params = dict(objective="binary", num_leaves=15,
                          max_bin=63, min_data_in_leaf=5,
                          verbosity=-1, **extra)
            ds = lgb.Dataset(X, label=y, params=params)
            vs = lgb.Dataset(Xv, label=yv, params=params, reference=ds)
            bst = lgb.train(params, ds, num_boost_round=3,
                            valid_sets=[vs])
            return bst.model_to_string()
        assert _strip_params(run({"tpu_stream": "on"})) == \
            _strip_params(run({}))

    def test_multiclassova_streams(self):
        X, _ = _data()
        y = (np.abs(X[:, 0] * 3).astype(int) % 3).astype(np.float32)
        extra = {"objective": "multiclassova", "num_class": 3}
        a = _train(X, y, extra, iters=2).model_to_string()
        b = _train(X, y, {**extra, "tpu_stream": "on"},
                   iters=2).model_to_string()
        assert _strip_params(a) == _strip_params(b)


class TestSlabBoundaries:
    """Multi-slab semantics at forced small slabs (tpu_stream_slab_rows)."""

    def test_quantized_bit_identical_across_slab_counts(self):
        # 2048-row slabs give [2048, 2048, 904]: an uneven tail AND a
        # slab exactly equal to the section alignment
        X, y = _data(5000)
        q = {"use_quantized_grad": True, "tpu_stream": "on"}
        one = _train(X, y, {**q, "tpu_stream_slab_rows": 4096}
                     ).model_to_string()
        three = _train(X, y, {**q, "tpu_stream_slab_rows": 2048}
                       ).model_to_string()
        assert _strip_params(one) == _strip_params(three)

    def test_quantized_exact_slab_multiple(self):
        # num_data an exact multiple of the slab size (no tail): the
        # exact integer accumulation makes streamed predictions
        # BIT-equal to resident quantized training (leaf values derive
        # from identical int32 histogram totals)
        X, y = _data(4096)
        q = {"use_quantized_grad": True}
        streamed = _train(X, y, {**q, "tpu_stream": "on",
                                 "tpu_stream_slab_rows": 2048})
        assert streamed._gbdt._stream.n_slabs == 2
        resident = _train(X, y, q)
        pr = resident.predict(X[:512], raw_score=True)
        ps = streamed.predict(X[:512], raw_score=True)
        assert np.array_equal(pr, ps)

    def test_f32_multi_slab_predictions_close(self):
        # f32 slab partials accumulate in slab order: association-only
        # drift vs the resident single contraction
        X, y = _data(5000)
        resident = _train(X, y, {})
        streamed = _train(X, y, {"tpu_stream": "on",
                                 "tpu_stream_slab_rows": 2048})
        pr = resident.predict(X[:512], raw_score=True)
        ps = streamed.predict(X[:512], raw_score=True)
        np.testing.assert_allclose(ps, pr, rtol=2e-4, atol=2e-4)

    def test_multi_slab_plan_shape(self):
        X, y = _data(5000)
        bst = _train(X, y, {"tpu_stream": "on",
                            "tpu_stream_slab_rows": 2048})
        plan = bst._gbdt._stream
        assert plan is not None and plan.n_slabs == 3
        assert plan.bounds[-1] == (4096, 5000)


# ---------------------------------------------------------------------------
class TestPreflight:
    def test_clamped_budget_recommends_streaming(self, monkeypatch):
        from lightgbm_tpu.obs import memory as obs_memory
        from lightgbm_tpu.config import Config
        params = {"objective": "binary", "num_leaves": 15,
                  "max_bin": 63, "tpu_fused_grad": "off",
                  "verbosity": -1}
        n, f = 5000, 6
        kw = obs_memory._resolve_train_knobs(
            Config.from_params(dict(params)), n, f, 1)
        kw["valid_rows"] = []
        resident = obs_memory.train_memory_model(**kw)["peak_bytes"]
        streamed = obs_memory.train_memory_model(
            **kw, stream_slab_rows=bp.slab_align(63))["peak_bytes"]
        assert streamed < resident
        clamp = (streamed + resident) // 2
        r = lgb.preflight(dict(params), shape=(n, f),
                          capacity_bytes=clamp)
        assert r.fits is False          # resident verdict stays honest
        assert r.fits_streaming is True
        recs = {x["knob"]: x for x in r.recommendations}
        assert "tpu_stream" in recs
        assert recs["tpu_stream"]["slab_rows"] >= bp.slab_align(63)
        assert "slab_rows" in r.render() or "tpu_stream" in r.render()

    def test_auto_streams_under_clamp(self, monkeypatch):
        from lightgbm_tpu.obs import memory as obs_memory
        from lightgbm_tpu.config import Config
        params = {"tpu_fused_grad": "off"}
        n = 5000
        X, y = _data(n)
        base = dict(objective="binary", num_leaves=15, max_bin=63,
                    min_data_in_leaf=5, verbosity=-1, **params)
        kw = obs_memory._resolve_train_knobs(
            Config.from_params(dict(base)), n, 6, 1)
        kw["valid_rows"] = []
        resident = obs_memory.train_memory_model(**kw)["peak_bytes"]
        streamed = obs_memory.train_memory_model(
            **kw, stream_slab_rows=bp.slab_align(63))["peak_bytes"]
        monkeypatch.setenv("LGBM_TPU_HBM_BYTES",
                           str((streamed + resident) // 2))
        bst = _train(X, y, params)
        plan = bst._gbdt._stream
        assert plan is not None and plan.n_slabs >= 2
        pred = bst.predict(X[:32])
        assert np.all(np.isfinite(pred))

    def test_auto_respects_preflight_off(self, monkeypatch):
        monkeypatch.setenv("LGBM_TPU_HBM_BYTES", "1000")
        X, y = _data(1200)
        bst = _train(X, y, {"tpu_preflight": "off"})
        assert bst._gbdt._stream is None

    def test_streaming_memory_model_published(self):
        X, y = _data(5000)
        _train(X, y, {"tpu_stream": "on", "tpu_stream_slab_rows": 2048})
        mm = global_metrics.meta.get("mem_model")
        assert mm and mm["stream_slab_rows"] == 2048
        # device bins budget = the double-buffered slab pair, not [F, N]
        assert mm["components"]["bins"] < 6 * 5000


class TestKnobs:
    def test_bad_value_raises(self):
        X, y = _data(600)
        with pytest.raises(ValueError, match="tpu_stream"):
            _train(X, y, {"tpu_stream": "sometimes"})

    def test_forced_on_ineligible_raises(self):
        X, _ = _data(600)
        y3 = (np.abs(X[:, 0] * 3).astype(int) % 3).astype(np.float32)
        # coupled multiclass resolves to exact-order growth: no twin
        with pytest.raises(ValueError, match="tpu_stream=on"):
            _train(X, y3, {"tpu_stream": "on", "objective": "multiclass",
                           "num_class": 3}, iters=1)

    def test_auto_ineligible_stays_resident(self):
        X, _ = _data(600)
        y3 = (np.abs(X[:, 0] * 3).astype(int) % 3).astype(np.float32)
        bst = _train(X, y3, {"objective": "multiclass", "num_class": 3},
                     iters=1)
        assert bst._gbdt._stream is None

    def test_off_never_streams(self, monkeypatch):
        monkeypatch.setenv("LGBM_TPU_HBM_BYTES", "1")
        X, y = _data(600)
        bst = _train(X, y, {"tpu_stream": "off"})
        assert bst._gbdt._stream is None


# ---------------------------------------------------------------------------
class TestResumeInterplay:
    def test_sigterm_mid_stream_resumes_bit_identically(self, tmp_path):
        """PR-8 interplay: a kill mid-streamed-run checkpoints at the
        iteration boundary; the resumed (still streamed) run finishes
        bit-identical to the never-killed streamed run."""
        from lightgbm_tpu.resilience import faults as faults_mod
        from lightgbm_tpu.resilience.errors import EXIT_PREEMPTED
        X, y = _data(5000)
        ck = str(tmp_path / "stream.ckpt")
        params = dict(objective="binary", num_leaves=15, max_bin=63,
                      min_data_in_leaf=5, verbosity=-1,
                      tpu_stream="on", tpu_stream_slab_rows=2048,
                      tpu_checkpoint_path=ck)
        straight = lgb.train(dict(params), lgb.Dataset(X, label=y),
                             num_boost_round=5).model_to_string()
        if os.path.exists(ck):  # no periodic snapshots were requested,
            os.remove(ck)       # but stay robust to engine behavior

        faults_mod.install(faults_mod.FaultPlan(kill_at_iter=2))
        try:
            with pytest.raises(SystemExit) as ei:
                lgb.train(dict(params), lgb.Dataset(X, label=y),
                          num_boost_round=5)
            assert ei.value.code == EXIT_PREEMPTED
        finally:
            faults_mod.reset()
        assert os.path.exists(ck)
        resumed = lgb.train(dict(params), lgb.Dataset(X, label=y),
                            num_boost_round=5)
        assert resumed.current_iteration() == 5
        assert resumed.model_to_string() == straight

    def test_resume_refuses_slab_drift(self, tmp_path):
        """A checkpoint taken under one slab plan must not silently
        resume under another (the f32 accumulation order would change
        mid-run)."""
        from lightgbm_tpu.resilience import faults as faults_mod
        from lightgbm_tpu.resilience.errors import (EXIT_PREEMPTED,
                                                    ResumeMismatchError)
        X, y = _data(5000)
        ck = str(tmp_path / "drift.ckpt")
        params = dict(objective="binary", num_leaves=15, max_bin=63,
                      min_data_in_leaf=5, verbosity=-1,
                      tpu_stream="on", tpu_stream_slab_rows=2048,
                      tpu_checkpoint_path=ck)
        faults_mod.install(faults_mod.FaultPlan(kill_at_iter=1))
        try:
            with pytest.raises(SystemExit) as ei:
                lgb.train(dict(params), lgb.Dataset(X, label=y),
                          num_boost_round=4)
            assert ei.value.code == EXIT_PREEMPTED
        finally:
            faults_mod.reset()
        params["tpu_stream_slab_rows"] = 4096
        with pytest.raises(ResumeMismatchError):
            lgb.train(dict(params), lgb.Dataset(X, label=y),
                      num_boost_round=4)


# ---------------------------------------------------------------------------
class TestObsExport:
    def test_stream_meta_and_families(self):
        X, y = _data(5000)
        global_stream_stats.reset()
        _train(X, y, {"tpu_stream": "on", "tpu_stream_slab_rows": 2048})
        sm = global_metrics.meta.get("stream")
        assert sm and sm["n_slabs"] == 3 and sm["slab_rows"] == 2048
        assert sm["uploads_total"] >= 3
        assert sm["overlap_ratio"] > 0.0
        assert sm["upload_seconds_total"] > 0.0
        from lightgbm_tpu.obs.export import render_openmetrics
        doc = render_openmetrics()
        for fam in ("lgbmtpu_stream_slabs_total",
                    "lgbmtpu_stream_upload_seconds_total",
                    "lgbmtpu_stream_overlap_ratio",
                    "lgbmtpu_stream_n_slabs"):
            assert fam in doc, fam

    def test_slow_path_streaming_publishes_meta(self):
        # RF rides the slow driver through the streamed grower adapter:
        # the same always-on accounting must flow (and the per-
        # iteration sync resets the overlap classifier's in-flight
        # count so later pipelines don't inherit stale dispatches)
        X, y = _data(5000)
        global_stream_stats.reset()
        global_metrics.set_meta("stream", None)
        _train(X, y, {"boosting": "rf", "bagging_fraction": 0.7,
                      "bagging_freq": 1, "tpu_stream": "on",
                      "tpu_stream_slab_rows": 2048})
        sm = global_metrics.meta.get("stream")
        assert sm and sm["iterations_total"] == 3
        assert sm["uploads_total"] >= 3
        assert global_stream_stats._inflight == 0

    def test_single_slab_streaming_uploads_once(self):
        X, y = _data(1200)
        global_stream_stats.reset()
        _train(X, y, {"tpu_stream": "on"}, iters=3)
        st = global_stream_stats.summary()
        # the immutable single slab stages once and is cached — not
        # re-uploaded per iteration
        assert st["uploads_total"] == 1
        assert st["bytes_uploaded_total"] > 0
        assert st["iterations_total"] == 3


# ---------------------------------------------------------------------------
class TestToolsWiring:
    @pytest.mark.slow
    def test_check_stream_tool(self):
        import check_stream
        assert check_stream.main() == 0

    def _floor(self):
        return {"stream": {"max_overhead_vs_resident": 1.25,
                           "max_overhead_vs_resident_cpu": 2.6,
                           "min_overlap_ratio": 0.05}}

    def _candidate(self, tmp_path, vs_resident, overlap,
                   platform="cpu"):
        rec = {"metric": "stream_rows_per_sec", "value": 1.0,
               "unit": f"rows/sec (platform={platform})",
               "vs_baseline": vs_resident,
               "stream": {"vs_resident": vs_resident,
                          "stream_overlap_ratio": overlap,
                          "n_slabs": 4}}
        p = tmp_path / "BENCH_cand.json"
        p.write_text(json.dumps(rec))
        return str(p)

    def test_gate_check9_passes(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_stream_overhead(
            self._floor(), failures,
            self._candidate(tmp_path, vs_resident=0.5, overlap=0.9))
        assert failures == []

    def test_gate_check9_fails_on_slowdown_and_overlap(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_stream_overhead(
            self._floor(), failures,
            self._candidate(tmp_path, vs_resident=0.2, overlap=0.01))
        assert len(failures) == 2
        assert "resident wall-time" in failures[0]
        assert "overlap ratio" in failures[1]

    def test_gate_check9_accelerator_ceiling(self, tmp_path):
        import check_perf_gate
        failures = []
        check_perf_gate.check_stream_overhead(
            self._floor(), failures,
            self._candidate(tmp_path, vs_resident=0.5, overlap=0.9,
                            platform="tpu"))
        assert failures and "1.25x" in failures[0]

    def test_gate_check9_graceful_skip(self, tmp_path, capsys):
        import check_perf_gate
        failures = []
        check_perf_gate.check_stream_overhead(self._floor(), failures,
                                              str(tmp_path / "nope.json"))
        assert failures == []
        assert "skipped" in capsys.readouterr().out
