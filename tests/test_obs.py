"""Telemetry subsystem tests: span tracer (nesting, self-time, Chrome
export), metrics registry (recompile counter, disabled fast path),
telemetry callbacks, the timer facade, and the log.py custom-logger
round trip."""

import json
import os
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import log
from lightgbm_tpu.obs.metrics import MetricsRegistry, global_metrics
from lightgbm_tpu.obs.trace import Tracer, _NULL_SPAN
from lightgbm_tpu.timer import Timer

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from check_trace import check_trace  # noqa: E402

from conftest import make_binary  # noqa: E402


# ---------------------------------------------------------------------------
# span tracer
class TestTracer:
    def test_nesting_and_self_time(self):
        tr = Tracer()
        tr.enable()
        with tr.span("outer"):
            time.sleep(0.02)
            with tr.span("inner"):
                time.sleep(0.02)
        s = tr.summary()
        assert set(s) == {"outer", "inner"}
        assert s["outer"]["count"] == 1 and s["inner"]["count"] == 1
        # parent total covers the child; parent self excludes it
        assert s["outer"]["seconds"] >= s["inner"]["seconds"]
        assert abs(s["outer"]["self_seconds"]
                   - (s["outer"]["seconds"] - s["inner"]["seconds"])) < 1e-9
        assert s["inner"]["self_seconds"] == pytest.approx(
            s["inner"]["seconds"])
        assert s["outer"]["self_seconds"] >= 0.015
        assert s["inner"]["seconds"] >= 0.015

    def test_sibling_spans_accumulate(self):
        tr = Tracer()
        tr.enable()
        for _ in range(3):
            with tr.span("phase"):
                pass
        assert tr.summary()["phase"]["count"] == 3

    def test_depth_recorded(self):
        tr = Tracer()
        tr.enable()
        with tr.span("a"):
            with tr.span("b"):
                pass
        events = {e["name"]: e for e in tr.chrome_events()}
        assert events["a"]["args"]["depth"] == 0
        assert events["b"]["args"]["depth"] == 1

    def test_chrome_export_valid(self, tmp_path):
        tr = Tracer()
        tr.enable()
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        path = str(tmp_path / "trace.json")
        tr.export_chrome(path)
        with open(path) as fh:
            doc = json.load(fh)  # loadable JSON
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(spans) == 2
        for ev in spans:
            assert isinstance(ev["name"], str)
            assert ev["ts"] >= 0 and ev["dur"] >= 0
        # checker accepts it
        ok, msg = check_trace(path)
        assert ok, msg

    def test_chrome_metadata_events(self, tmp_path):
        """Perfetto readability: the export carries ph:"M" process/thread
        naming — process_name, host/pid process_labels, and a
        thread_name for every recorded thread."""
        import threading
        tr = Tracer()
        tr.enable()
        with tr.span("main_phase"):
            pass

        # record a span from a named worker thread
        def worker():
            with tr.span("worker_phase"):
                pass
        t = threading.Thread(target=worker, name="lgbm-worker")
        t.start()
        t.join()
        events = tr.chrome_events()
        meta = [e for e in events if e["ph"] == "M"]
        by_name = {}
        for e in meta:
            by_name.setdefault(e["name"], []).append(e)
        assert by_name["process_name"][0]["args"]["name"].startswith(
            "lightgbm_tpu")
        labels = by_name["process_labels"][0]["args"]["labels"]
        assert "hostname=" in labels and "pid=" in labels
        thread_names = {e["args"]["name"] for e in by_name["thread_name"]}
        assert "lgbm-worker" in thread_names
        # metadata precedes spans and the validator enforces it
        assert events[0]["ph"] == "M"
        path = str(tmp_path / "trace.json")
        tr.export_chrome(path)
        ok, msg = check_trace(path)
        assert ok, msg
        assert "metadata" in msg

    def test_check_trace_requires_metadata_from_our_producer(self,
                                                            tmp_path):
        doc = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 1, "dur": 2, "pid": 7,
             "tid": 9}],
            "otherData": {"producer": "lightgbm_tpu.obs.trace"}}
        p = tmp_path / "t.json"
        p.write_text(json.dumps(doc))
        ok, msg = check_trace(str(p))
        assert not ok and "process_name" in msg
        # foreign traces without metadata stay acceptable
        doc.pop("otherData")
        p.write_text(json.dumps(doc))
        ok, _ = check_trace(str(p))
        assert ok
        # malformed metadata payload is rejected everywhere
        doc["traceEvents"].insert(0, {"name": "thread_name", "ph": "M",
                                      "args": {}})
        p.write_text(json.dumps(doc))
        ok, msg = check_trace(str(p))
        assert not ok and "args.name" in msg

    def test_check_trace_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("not json {")
        ok, _ = check_trace(str(p))
        assert not ok
        p.write_text(json.dumps({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 100, "dur": 5},
            {"name": "b", "ph": "X", "ts": 50, "dur": 5},
        ]}))
        ok, msg = check_trace(str(p))
        assert not ok and "monotonicity" in msg

    def test_disabled_is_shared_noop(self, monkeypatch):
        monkeypatch.delenv("LGBM_TPU_TRACE", raising=False)
        monkeypatch.delenv("LGBM_TPU_TIMETAG", raising=False)
        tr = Tracer()
        assert not tr.enabled
        cm = tr.span("anything")
        assert cm is _NULL_SPAN  # no allocation on the disabled path
        with cm:
            pass
        assert tr.summary() == {}
        assert tr._events == []

    def test_block_waits_on_device_work(self):
        import jax.numpy as jnp
        tr = Tracer()
        tr.enable()
        with tr.span("device", block=lambda: out):
            out = jnp.arange(1024.0).sum()
        assert tr.summary()["device"]["count"] == 1


# ---------------------------------------------------------------------------
# metrics registry
class TestMetrics:
    def test_disabled_records_nothing(self):
        m = MetricsRegistry()
        m.disable()
        m.begin_iteration(0)
        m.observe("x", 1.0)
        m.inc("y")
        m.end_iteration()
        assert m.history == [] and m._current is None
        assert m.snapshot() is None

    def test_iteration_lifecycle(self):
        m = MetricsRegistry()
        m.enabled = True  # direct flag: avoid touching the global tracer
        m.begin_iteration(3)
        m.observe("leaves_grown", 31)
        m.inc("jit_recompiles")
        m.end_iteration()
        snap = m.snapshot()
        assert snap["iteration"] == 3
        assert snap["leaves_grown"] == 31
        assert snap["jit_recompiles"] == 1
        assert snap["iteration_seconds"] >= 0.0

    def test_recompile_counter_once_per_shape(self):
        import jax
        m = MetricsRegistry()
        fn = jax.jit(m.wrap_traced("f", lambda x: x * 2))
        a = np.ones(8, np.float32)
        fn(a)
        fn(a)  # cache hit: no new trace
        assert m.recompiles("f") == 1
        fn(np.ones(16, np.float32))  # shape change: exactly one retrace
        assert m.recompiles("f") == 2
        fn(np.ones(16, np.float32))
        assert m.recompiles("f") == 2

    def test_op_level_note_trace_does_not_inflate_jit_recompiles(self):
        m = MetricsRegistry()
        m.enabled = True
        m.begin_iteration(0)
        # inner op call sites fire many times per program compile; only
        # top-level program wrappers feed the jit_recompiles metric
        m.note_trace("ops/split_search")
        m.note_trace("ops/split_search")
        m.note_trace("ops/histogram")
        m.note_trace("prog", top_level=True)
        m.end_iteration()
        assert m.snapshot()["jit_recompiles"] == 1
        assert m.recompiles("ops/split_search") == 2

    def test_collective_accounting(self):
        m = MetricsRegistry()
        m.note_collective("psum", 4096)
        m.note_collective("all_gather", 128)
        assert m.collective_calls == 2
        assert m.collective_bytes == 4096 + 128
        assert m.trace_counts["collective/psum"] == 1

    def test_concurrent_recording_is_lossless(self):
        """Regression for the unsynchronized read-modify-write in
        LatencyReservoir.note / inc_counter / note_predict: serve/
        records from the asyncio loop AND its executor thread, so
        concurrent notes must not lose updates."""
        import threading
        m = MetricsRegistry()
        threads, per_thread = 8, 2000

        def hammer(tid):
            for i in range(per_thread):
                m.note_latency("serve/request", 0.001 * (tid + 1))
                m.inc_counter("serve/requests")
                m.note_predict(rows=3, seconds=0.002)

        ts = [threading.Thread(target=hammer, args=(t,))
              for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = threads * per_thread
        res = m.latency("serve/request")
        assert res.count == total
        assert m.counter("serve/requests") == total
        assert m.predict_rows_total == 3 * total
        assert m.latency("predict").count == total
        assert m.predict_seconds_total == pytest.approx(0.002 * total)
        # reservoir stayed bounded and readable
        assert len(res._samples) == min(total, res.capacity)
        assert res.summary()["count"] == total

    def test_per_device_memory_stats_shape(self):
        """Per-device stats: None on CPU (no memory_stats), a list of
        per-ordinal dicts on accelerator backends — end_iteration folds
        sum/max so multi-chip runs don't under-report peak."""
        stats = MetricsRegistry.per_device_memory_stats()
        if stats is None:
            return  # CPU backend under conftest
        assert all("device" in s for s in stats)
        assert [s["device"] for s in stats] == sorted(
            s["device"] for s in stats)

    def test_end_iteration_folds_max_and_sum(self, monkeypatch):
        m = MetricsRegistry()
        m.enabled = True
        fake = [{"device": 0, "bytes_in_use": 10, "peak_bytes_in_use": 40},
                {"device": 1, "bytes_in_use": 30, "peak_bytes_in_use": 90}]
        monkeypatch.setattr(MetricsRegistry, "per_device_memory_stats",
                            staticmethod(lambda: fake))
        m.begin_iteration(0)
        m.end_iteration()
        snap = m.snapshot()
        assert snap["device_bytes_in_use"] == 40       # fleet sum
        assert snap["device_peak_bytes_in_use"] == 90  # worst device
        assert snap["device_peak_bytes_per_device"] == [40, 90]

    def test_phase_sink_uses_self_time(self):
        m = MetricsRegistry()
        m.enabled = True
        m.begin_iteration(0)
        m.phase_sink("train/grow", dur_s=1.0, self_s=0.75)
        m.phase_sink("train/grow", dur_s=0.5, self_s=0.25)
        m.end_iteration()
        assert m.snapshot()["phases"]["train/grow"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# training integration
def _train_with_telemetry(n_rounds=4, **extra_params):
    X, y = make_binary(400, 6)
    rec = {}
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              **extra_params}
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=n_rounds,
                    callbacks=[lgb.record_telemetry(rec)])
    return bst, rec


class TestTelemetryTraining:
    def setup_method(self):
        from lightgbm_tpu.obs.trace import global_tracer
        self._tracer_was_enabled = global_tracer.enabled
        global_metrics.disable()
        global_metrics.reset()

    def teardown_method(self):
        # metrics.enable() also switches the global tracer on; restore
        # both so later (unrelated) tests run with telemetry truly off
        from lightgbm_tpu.obs.trace import global_tracer
        global_metrics.disable()
        global_metrics.reset()
        if not self._tracer_was_enabled:
            global_tracer.disable()

    def test_record_telemetry_populates_across_iterations(self):
        bst, rec = _train_with_telemetry(4)
        assert bst.current_iteration() == 4
        # every list is iteration-aligned (None-padded where absent)
        assert all(len(v) == 4 for v in rec.values()), \
            {k: len(v) for k, v in rec.items()}
        assert all(1 <= v <= 7 for v in rec["leaves_grown"])
        assert all(v > 0 for v in rec["grad_norm"])
        assert rec["iteration"] == [0, 1, 2, 3]
        # fused-path compile shows up as a recompile on iteration 0;
        # non-compiling iterations hold the None placeholder
        assert rec["jit_recompiles"][0] >= 1
        assert rec["jit_recompiles"][-1] is None
        # phase times flowed from tracer spans into the iteration dicts
        assert any(k.startswith("phase/") for k in rec)

    def test_telemetry_enable_is_scoped_to_the_run(self):
        from lightgbm_tpu.obs.trace import global_tracer
        assert not global_metrics.enabled
        tracer_was = global_tracer.enabled
        _train_with_telemetry(2)
        # the callback's opt-in must not outlive its train() call
        assert not global_metrics.enabled
        assert global_tracer.enabled == tracer_was

    def test_log_telemetry_prints(self, capsys):
        X, y = make_binary(300, 6)
        lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2,
                  callbacks=[lgb.log_telemetry(period=1)])
        out = capsys.readouterr().out
        assert "iter=" in out and "leaves_grown=" in out

    def test_disabled_training_records_nothing(self):
        X, y = make_binary(300, 6)
        global_metrics.disable()
        h0 = len(global_metrics.history)
        lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3)
        assert len(global_metrics.history) == h0
        assert global_metrics._current is None

    def test_trace_output_param_writes_trace(self, tmp_path):
        from lightgbm_tpu.obs.trace import global_tracer
        path = str(tmp_path / "train_trace.json")
        X, y = make_binary(300, 6)
        was_enabled = global_tracer.enabled
        prev_path = global_tracer.trace_path
        try:
            lgb.train({"objective": "binary", "num_leaves": 7,
                       "verbosity": -1, "trace_output": path},
                      lgb.Dataset(X, label=y), num_boost_round=2)
            global_tracer.export_chrome(path)
        finally:
            global_tracer.trace_path = prev_path
            if not was_enabled:
                global_tracer.disable()
        ok, msg = check_trace(path)
        assert ok, msg
        names = {e["name"] for e in json.load(open(path))["traceEvents"]}
        assert "train/iteration" in names

    def test_histogram_recompile_counted_on_new_shape(self):
        from lightgbm_tpu.ops import histogram as hist_ops
        import jax.numpy as jnp
        before = global_metrics.recompiles("ops/histogram")
        bins = jnp.zeros((3, 64), jnp.int32)
        g = jnp.ones(64); h = jnp.ones(64); mk = jnp.ones(64)
        hist_ops.build_histogram(bins, g, h, mk, max_bins=4, impl="xla")
        after_first = global_metrics.recompiles("ops/histogram")
        assert after_first >= before + 1
        hist_ops.build_histogram(bins, g, h, mk, max_bins=4, impl="xla")
        assert global_metrics.recompiles("ops/histogram") == after_first


# ---------------------------------------------------------------------------
# timer facade
class TestTimerFacade:
    def test_timed_nests_with_self_time(self):
        tr = Tracer()
        timer = Timer(tracer=tr)
        tr.enabled = True  # enable without installing exit-print
        with timer.timed("outer"):
            with timer.timed("inner"):
                time.sleep(0.01)
        s = timer.summary()
        assert s["outer"]["seconds"] >= s["inner"]["seconds"]
        assert s["outer"]["self_seconds"] == pytest.approx(
            s["outer"]["seconds"] - s["inner"]["seconds"], abs=1e-9)
        assert "phase timers" in timer.report()

    def test_global_timer_shares_global_tracer(self):
        from lightgbm_tpu.timer import global_timer
        from lightgbm_tpu.obs.trace import global_tracer
        assert global_timer._tracer is global_tracer


# ---------------------------------------------------------------------------
# log.py custom logger round trip
class _CollectingLogger:
    def __init__(self):
        self.lines = []

    def my_info(self, msg):
        self.lines.append(("info", msg))

    def my_warning(self, msg):
        self.lines.append(("warning", msg))

    def my_debug(self, msg):
        self.lines.append(("debug", msg))


class TestRegisterLogger:
    def _restore(self):
        log._logger = None
        log._info_method = "info"
        log._warning_method = "warning"
        log._debug_method = None
        log.set_verbosity(1)

    def test_round_trip_all_levels(self, capsys):
        logger = _CollectingLogger()
        try:
            log.register_logger(logger, info_method_name="my_info",
                                warning_method_name="my_warning",
                                debug_method_name="my_debug")
            log.set_verbosity(2)  # debug level
            log.info("i")
            log.warning("w")
            log.debug("d")
            assert ("info", "i") in logger.lines
            assert ("warning", "w") in logger.lines
            # Debug routed through the registered method, not print
            assert ("debug", "d") in logger.lines
            assert capsys.readouterr().out == ""
        finally:
            self._restore()

    def test_debug_falls_back_to_info_method(self):
        logger = _CollectingLogger()
        try:
            log.register_logger(logger, info_method_name="my_info",
                                warning_method_name="my_warning")
            log.set_verbosity(2)
            log.debug("d")
            assert ("info", "d") in logger.lines  # via info override
        finally:
            self._restore()

    def test_invalid_logger_rejected(self):
        with pytest.raises(TypeError):
            log.register_logger(object())
        logger = _CollectingLogger()
        with pytest.raises(TypeError):
            log.register_logger(logger, info_method_name="my_info",
                                warning_method_name="my_warning",
                                debug_method_name="nope")


# ---------------------------------------------------------------------------
# disabled-path cost of the introspection layer (exporter, xla, request
# tracing): telemetry off means the first-dispatch counters and nothing
# else — no cost analysis, no published meta, nothing allocated per call
class TestDisabledIntrospectionLayer:
    def test_xla_introspector_disabled_keeps_counters_only(self,
                                                          monkeypatch):
        """One dispatch path on both settings, jit's own: with telemetry
        off a program outside training leaves its first-dispatch
        counters and nothing else — no Compiled is taken (a broken
        ``lower`` is never reached), no cost analysis."""
        from lightgbm_tpu.obs.xla import XlaIntrospector, instrumented_jit
        reg = XlaIntrospector()
        assert not reg.enabled  # env-gated, off under the test env
        g = instrumented_jit("off/prog", lambda x: x * 3, phase="predict",
                             registry=reg)

        def boom(*a, **k):
            raise AssertionError("lower() reached with telemetry off")

        monkeypatch.setattr(g.__wrapped_jit__, "lower", boom)
        a = np.ones(4, np.float32)
        np.testing.assert_array_equal(np.asarray(g(a)), [3.0] * 4)
        np.testing.assert_array_equal(np.asarray(g(a)), [3.0] * 4)
        assert reg.n_programs == 1  # the second call acquired nothing
        rec = reg.records()[0]
        assert rec["trace_lower_s"] > 0 and rec["compile_or_load_s"] > 0
        assert rec["cache_hit"] in (True, False)
        assert "flops" not in rec and "argument_bytes" not in rec
        assert "aot_fallbacks" not in reg.summary()

    def test_flusher_unarmed_is_attribute_check(self, monkeypatch,
                                                tmp_path):
        from lightgbm_tpu.obs.export import MetricsTextfileFlusher
        monkeypatch.delenv("LGBM_TPU_METRICS_FILE", raising=False)
        fl = MetricsTextfileFlusher()
        assert not fl.armed
        assert fl.maybe_flush() is False
        assert list(tmp_path.iterdir()) == []

    def test_span_args_disabled_returns_shared_noop(self):
        tr = Tracer()
        assert tr.span("x", args={"trace_id": "t"}) is _NULL_SPAN
        tr.add_complete_span("late", 0, 100, args={"trace_id": "t"})
        assert tr._events == [] and tr.summary() == {}

    def test_enabled_span_args_reach_chrome_events(self):
        tr = Tracer()
        tr.enable()
        with tr.span("phase", args={"k": "v"}):
            pass
        tr.add_complete_span("late", 10, 100, args={"trace_id": "t-1"})
        by_name = {e["name"]: e for e in tr.chrome_events()
                   if e["ph"] == "X"}
        assert by_name["phase"]["args"]["k"] == "v"
        assert by_name["phase"]["args"]["depth"] == 0  # std args kept
        assert by_name["late"]["args"]["trace_id"] == "t-1"
        assert by_name["late"]["dur"] == pytest.approx(0.1)  # us

    def test_metrics_enable_arms_xla_and_restore_disarms(self):
        from lightgbm_tpu.obs.trace import global_tracer
        from lightgbm_tpu.obs.xla import global_xla
        assert not global_metrics.enabled and not global_xla.enabled
        tracer_was = global_tracer.enabled
        _train_with_telemetry(2)
        # the scoped enable armed the introspector for the run only
        assert not global_xla.enabled
        assert not global_metrics.enabled
        assert global_tracer.enabled == tracer_was


# ---------------------------------------------------------------------------
# structured JSON log mode (LGBM_TPU_LOG_JSON)
class TestJsonLogMode:
    def test_json_records_carry_host_labels(self, capsys):
        import socket
        log.set_json_mode(True)
        log.set_verbosity(1)  # earlier trainings lower the threshold
        try:
            log.info("hello world")
            log.warning("watch out")
        finally:
            log.set_json_mode(False)
        lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
        recs = [json.loads(ln) for ln in lines]
        assert [r["msg"] for r in recs] == ["hello world", "watch out"]
        assert [r["level"] for r in recs] == ["Info", "Warning"]
        for r in recs:
            assert r["hostname"] == socket.gethostname()
            assert r["pid"] == str(os.getpid())
            assert r["ts"] > 0

    def test_env_var_arms_json_mode(self, monkeypatch, capsys):
        import importlib
        monkeypatch.setenv("LGBM_TPU_LOG_JSON", "1")
        importlib.reload(log)
        try:
            log.set_verbosity(1)
            log.info("from env")
            rec = json.loads(capsys.readouterr().out.strip())
            assert rec["msg"] == "from env"
        finally:
            monkeypatch.delenv("LGBM_TPU_LOG_JSON")
            importlib.reload(log)
        assert not log._json_mode

    def test_registered_logger_bypasses_json_wrapping(self, capsys):
        logger = _CollectingLogger()
        log.set_json_mode(True)
        log.set_verbosity(1)
        try:
            log.register_logger(logger, info_method_name="my_info",
                                warning_method_name="my_warning")
            log.info("plain")
            assert ("info", "plain") in logger.lines  # raw msg, not JSON
            assert capsys.readouterr().out == ""
        finally:
            log.set_json_mode(False)
            log._logger = None
            log._info_method = "info"
            log._warning_method = "warning"
            log._debug_method = None
