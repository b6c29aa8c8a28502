"""Observability egress + XLA introspection: the OpenMetrics renderer
(obs/export.py), the textfile flusher, the HTTP endpoint smoke
(tools/check_metrics_endpoint.py), and the obs/xla.py program
introspector (AOT routing, cost capture, fallback safety, disabled
fast path)."""

import os
import sys

import numpy as np
import pytest

from lightgbm_tpu.obs.export import (MetricsTextfileFlusher,
                                     render_openmetrics)
from lightgbm_tpu.obs.metrics import MetricsRegistry, global_metrics
from lightgbm_tpu.obs.xla import (XlaIntrospector, aot_cost_summary,
                                  instrumented_jit)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from check_metrics_endpoint import validate_exposition  # noqa: E402

pytestmark = pytest.mark.quick


# ---------------------------------------------------------------------------
class TestRenderOpenmetrics:
    def _fresh_registry(self):
        m = MetricsRegistry()
        m.enabled = False
        m.inc_counter("serve/requests", 3)
        m.inc_counter("serve/registry_hit", 2)
        m.note_latency("serve/request", 0.004)
        m.note_latency("serve/request", 0.008)
        m.note_predict(100, 0.01)
        m.note_trace("boosting/grow")
        m.note_collective("psum", 4096)
        return m

    def test_document_is_valid_prometheus_text(self):
        text = render_openmetrics(self._fresh_registry())
        errors, families = validate_exposition(text)
        assert errors == []
        assert families["lgbmtpu_serve_requests_total"] == "counter"
        assert families["lgbmtpu_latency_seconds"] == "summary"
        assert families["lgbmtpu_host_info"] == "gauge"

    def test_counters_quantiles_and_host_labels_present(self):
        import socket
        text = render_openmetrics(self._fresh_registry())
        assert "lgbmtpu_serve_requests_total 3" in text
        assert "lgbmtpu_serve_registry_hit_total 2" in text
        assert ('lgbmtpu_latency_seconds{name="serve/request",'
                'quantile="0.99"}') in text
        assert 'lgbmtpu_latency_seconds_count{name="serve/request"} 2' \
            in text
        assert "lgbmtpu_predict_rows_total 100" in text
        assert 'lgbmtpu_jit_traces_total{tag="boosting/grow"} 1' in text
        assert "lgbmtpu_collective_bytes_total 4096" in text
        assert f'hostname="{socket.gethostname()}"' in text

    def test_meta_model_gauges_exported(self):
        m = self._fresh_registry()
        m.set_meta("mem_model", {"peak_bytes": 123456})
        m.set_meta("hist_traffic", {"hist_bytes_per_iter": 789})
        text = render_openmetrics(m)
        assert "lgbmtpu_mem_peak_model_bytes 123456" in text
        assert "lgbmtpu_hist_bytes_per_iter 789" in text
        errors, _ = validate_exposition(text)
        assert errors == []

    def test_extra_gauges_and_label_escaping(self):
        text = render_openmetrics(MetricsRegistry(),
                                  extra_gauges={"lgbmtpu_custom_gauge": 7})
        assert "lgbmtpu_custom_gauge 7" in text
        from lightgbm_tpu.obs.export import _label_value
        assert _label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'

    def test_validator_rejects_garbage(self):
        errors, _ = validate_exposition("not a metric line!!\n")
        assert errors
        errors, _ = validate_exposition(
            "# TYPE lgbmtpu_x counter\nlgbmtpu_x{bad-label=\"1\"} 1\n")
        assert errors
        # a sample without a TYPE header is flagged
        errors, _ = validate_exposition("lgbmtpu_orphan 1\n")
        assert errors


# ---------------------------------------------------------------------------
class TestTextfileFlusher:
    def test_unarmed_is_noop(self, monkeypatch):
        monkeypatch.delenv("LGBM_TPU_METRICS_FILE", raising=False)
        fl = MetricsTextfileFlusher()
        assert not fl.armed
        assert fl.maybe_flush() is False
        assert fl.flush() is False

    def test_armed_flushes_valid_document_atomically(self, monkeypatch,
                                                     tmp_path):
        path = str(tmp_path / "metrics.prom")
        monkeypatch.setenv("LGBM_TPU_METRICS_FILE", path)
        monkeypatch.setenv("LGBM_TPU_METRICS_FLUSH_SECS", "0")
        fl = MetricsTextfileFlusher()
        assert fl.armed and fl.interval_s == 0.0
        assert fl.maybe_flush() is True
        assert os.path.exists(path)
        assert not os.path.exists(path + ".tmp")  # rename, not write
        with open(path) as fh:
            errors, families = validate_exposition(fh.read())
        assert errors == [] and families

    def test_interval_throttles(self, monkeypatch, tmp_path):
        monkeypatch.setenv("LGBM_TPU_METRICS_FILE",
                           str(tmp_path / "m.prom"))
        monkeypatch.setenv("LGBM_TPU_METRICS_FLUSH_SECS", "3600")
        fl = MetricsTextfileFlusher()
        assert fl.maybe_flush() is True
        assert fl.maybe_flush() is False  # inside the interval
        assert fl.maybe_flush(force=True) is True

    def test_training_hook_writes_file(self, monkeypatch, tmp_path):
        """The boosting loop's per-iteration hook flushes when armed —
        no telemetry enable required (counters are always-on)."""
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs import export as export_mod
        path = str(tmp_path / "train.prom")
        monkeypatch.setenv("LGBM_TPU_METRICS_FILE", path)
        monkeypatch.setenv("LGBM_TPU_METRICS_FLUSH_SECS", "0")
        export_mod.global_flusher.rearm()
        try:
            rng = np.random.RandomState(0)
            X = rng.randn(300, 6)
            y = (X[:, 0] > 0).astype(np.float64)
            lgb.train({"objective": "binary", "num_leaves": 7,
                       "verbosity": -1}, lgb.Dataset(X, label=y),
                      num_boost_round=2)
        finally:
            monkeypatch.delenv("LGBM_TPU_METRICS_FILE")
            export_mod.global_flusher.rearm()
        assert os.path.exists(path)
        with open(path) as fh:
            errors, _ = validate_exposition(fh.read())
        assert errors == []


# ---------------------------------------------------------------------------
class TestXlaIntrospector:
    def test_enabled_routes_aot_and_records_cost(self):
        import jax
        from jax.experimental.compilation_cache import \
            compilation_cache as cc
        # A persistent-cache-served compile is attributed to
        # cache_load_s_total, NOT compile_s_total — so if a prior run
        # already wrote this tiny program to the disk cache (conftest
        # arms it), the compile_s_total assertions below would see 0.
        # Pin the test to real compiles by detaching the disk cache:
        # the flag AND the already-initialised cache object (resetting
        # the directory alone leaves the live cache serving hits).
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            reg = XlaIntrospector()
            reg.enable()
            calls = []

            def f(x):
                calls.append(1)
                return (x * 2.0).sum()

            g = instrumented_jit("test/prog", f, phase="testing",
                                 registry=reg)
            a = np.ones((64, 4), np.float32)
            out1 = g(a)
            out2 = g(a)  # same shape bucket: no second compile
            assert float(out1) == float(out2) == 512.0
            assert reg.n_programs == 1
            recs = reg.records()
            assert recs[0]["tag"] == "test/prog"
            assert recs[0]["phase"] == "testing"
            assert recs[0]["compile_or_load_s"] > 0
            assert recs[0]["trace_lower_s"] > 0
            assert recs[0]["cache_hit"] is False
            assert "64x4" in recs[0]["shapes"]
            g(np.ones((128, 4), np.float32))  # new bucket: +1 program
            assert reg.n_programs == 2
            s = reg.summary()
            assert s["n_recompiles_by_phase"] == {"testing": 2}
            assert s["compile_s_total"] > 0
            assert s["n_cache_hits"] == 0
            assert s["by_tag"]["test/prog"]["programs"] == 2
            # the boundary's result equals bare jit's bit-for-bit
            assert float(g(a)) == float(jax.jit(f)(a))
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()

    def test_cost_analysis_fields_when_backend_exposes_them(self):
        reg = XlaIntrospector()
        reg.enable()
        g = instrumented_jit("test/cost", lambda x: x @ x.T, registry=reg)
        g(np.ones((32, 8), np.float32))
        rec = reg.records()[0]
        # CPU XLA exposes both analyses; tolerate absence elsewhere but
        # under the test conftest (CPU) they must be captured
        assert rec.get("flops", 0) > 0
        assert rec.get("bytes_accessed", 0) > 0
        assert rec.get("argument_bytes", 0) >= 32 * 8 * 4

    def test_compiled_unavailable_keeps_results_and_counters(
            self, monkeypatch):
        """Failing to take the Compiled after the call costs the cost
        analysis only: the call itself went through jit, its result and
        its first-dispatch counters stand."""
        reg = XlaIntrospector()
        reg.enable()
        g = instrumented_jit("test/fb", lambda x: x + 1, registry=reg)
        jitted = g.__wrapped_jit__

        def boom(*a, **k):
            raise RuntimeError("no AOT here")

        monkeypatch.setattr(jitted, "lower", boom)
        for _ in range(2):
            out = g(np.arange(4.0, dtype=np.float32))
            np.testing.assert_array_equal(np.asarray(out),
                                          [1.0, 2.0, 3.0, 4.0])
        assert reg.n_programs == 1
        assert reg.records()[0]["compile_or_load_s"] > 0
        assert "flops" not in reg.records()[0]
        assert "test/fb" in reg.summary()["aot_fallbacks"]

    @pytest.mark.parametrize("when", ["trace", "run"])
    def test_errors_reach_the_caller_as_jit_raised_them(self, when):
        """The boundary catches nothing around the call: a failing
        program is not retried on another route (its donated arguments
        may be gone), and nothing is recorded as a fallback."""
        import jax
        import jax.numpy as jnp
        reg = XlaIntrospector()
        reg.enable()
        runs = []

        def host_side(x):
            runs.append(1)
            raise FloatingPointError("device said no")

        def f(x, buf):
            if when == "trace":
                raise KeyError("bad program")
            return jax.pure_callback(
                host_side, jax.ShapeDtypeStruct(x.shape, x.dtype), x) + buf

        g = instrumented_jit("test/raises", f, phase="train", registry=reg,
                             donate_argnums=(1,))
        x, buf = jnp.ones(4), jnp.zeros(4)
        with pytest.raises(KeyError if when == "trace" else Exception) as e:
            jax.block_until_ready(g(x, buf))
        if when == "trace":
            assert reg.n_programs == 0 and not buf.is_deleted()
        else:
            assert "device said no" in str(e.value)
            assert runs == [1]  # ran once: no second route was tried
        assert "aot_fallbacks" not in reg.summary()

    def test_aot_cost_summary_shape(self):
        cost = aot_cost_summary(lambda x: (x * x).sum(),
                                np.ones((16, 16), np.float32))
        if cost is None:  # backend without analyses: the skip contract
            return
        assert cost["compile_s"] > 0
        assert cost.get("argument_bytes", 0) >= 16 * 16 * 4

    def test_lowlat_compiles_recorded_when_enabled(self):
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs.xla import global_xla
        from lightgbm_tpu.serve import SERVE_LOWLAT_TAG, ModelRegistry
        rng = np.random.RandomState(0)
        X = rng.randn(240, 5)
        y = (X[:, 0] > 0).astype(np.float64)
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=2)
        registry = ModelRegistry()
        entry = registry.load("m", booster=bst)
        was = global_xla.enabled
        n0 = global_xla.n_programs
        global_xla.enable()
        try:
            entry.lowlat_predict(X[:3])
        finally:
            if not was:
                global_xla.disable()
        recs = [r for r in global_xla.records()[n0:]
                if r["tag"] == SERVE_LOWLAT_TAG]
        assert recs and recs[0]["phase"] == "serve"
        assert recs[0]["compile_or_load_s"] > 0


# ---------------------------------------------------------------------------
def test_check_metrics_endpoint_smoke():
    """The full endpoint smoke (train, serve, scrape, validate,
    readiness flip) — the quick-tier wiring for the CI tool."""
    import check_metrics_endpoint
    assert check_metrics_endpoint.main() == 0
    # the smoke leaves global serve counters behind; no global tracer
    # or metrics enable leaks
    assert not global_metrics.enabled


# ---------------------------------------------------------------------------
def test_bench_partial_obs_line_on_failed_attempt(monkeypatch, capsys):
    """bench.py satellite: a failed measurement emits its partial obs
    phase summary + compile attribution as one stderr comment line
    before the failure propagates (bench.main re-raises: exit != 0)."""
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench
    from lightgbm_tpu.obs.trace import global_tracer
    from lightgbm_tpu.obs.xla import global_xla
    monkeypatch.setenv("LGBM_TPU_TELEMETRY", "1")
    was = global_tracer.enabled
    global_tracer.enable()
    try:
        with global_tracer.span("train/doomed"):
            pass
        bench._emit_partial_obs("train", RuntimeError("chip lost"))
    finally:
        if not was:
            global_tracer.disable()
        global_tracer.reset()
        global_xla.disable()
    err = capsys.readouterr().err
    lines = [ln for ln in err.splitlines()
             if ln.startswith("# obs-partial: ")]
    assert len(lines) == 1
    rec = json.loads(lines[0][len("# obs-partial: "):])
    assert rec["partial"] is True
    assert "chip lost" in rec["error"]
    assert rec["metric"] == "boosting_iters_per_sec_higgs_shape"
    assert "train/doomed" in rec["phases"]
    # ... and main() lets the failure out instead of printing a result
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setitem(bench._MODE_MEASURE, "train",
                        lambda: (_ for _ in ()).throw(
                            RuntimeError("chip lost")))
    with pytest.raises(RuntimeError, match="chip lost"):
        bench.main()
    assert capsys.readouterr().out == ""
