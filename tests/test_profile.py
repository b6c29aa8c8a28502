"""Device-time attribution (obs/profile.py), the crash flight
recorder (obs/flightrec.py), the ``lgbmtpu_profile_*`` egress, the
Chrome-trace device lane, perf-gate check 11, concurrent /metrics
scrapes under live training, and the bench trend report."""

import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.export import (MetricsHTTPEndpoint,
                                     OPENMETRICS_CONTENT_TYPE,
                                     negotiate_content_type,
                                     render_openmetrics)
from lightgbm_tpu.obs.flightrec import (FORMAT, FlightRecorder,
                                        global_flightrec, validate_dump)
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.obs.profile import DEVICE_LANE_NAME, global_profile
from lightgbm_tpu.obs.xla import global_xla, instrumented_jit

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
from check_metrics_endpoint import validate_exposition  # noqa: E402

pytestmark = pytest.mark.quick


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    # global_metrics.enable() cascades to the tracer / watermarks / xla
    # / health registries but disable() does not — restore the whole
    # fan-out or the next test file inherits an armed tracer
    from lightgbm_tpu.obs.health import global_health
    from lightgbm_tpu.obs.memory import global_watermarks
    from lightgbm_tpu.obs.trace import global_tracer
    global_profile.reset()
    global_flightrec.reset()
    global_metrics.reset()
    global_metrics.disable()
    global_xla.reset()
    global_xla.disable()
    global_tracer.disable()
    global_tracer.reset()
    global_watermarks.disable()
    global_health.reset()
    global_health.disable()


def _binary_fixture(n=400, f=6, seed=0):
    r = np.random.RandomState(seed)
    x = r.randn(n, f)
    y = ((x[:, 1] + x[:, 3]) > 0.2).astype(np.float64)
    return x, y


# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_bound_and_dropped_count(self, tmp_path):
        rec = FlightRecorder(capacity=16)
        rec.enable(path=str(tmp_path / "fr.json"))
        for i in range(40):
            rec.record("iteration", iteration=i, trees=i)
        assert len(rec.events()) == 16
        path = rec.dump(reason="test")
        doc = json.load(open(path))
        assert validate_dump(doc) == []
        assert doc["format"] == FORMAT
        assert doc["n_recorded"] == 40
        assert doc["n_dropped"] == 24
        # the ring kept the NEWEST events — a black box records the end
        assert doc["events"][-1]["iteration"] == 39

    def test_record_accepts_any_payload(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.enable(path=str(tmp_path / "fr.json"))
        rec.record("serve_request", model="m",
                   weird=object(), arr=np.arange(3), nested={"a": (1, 2)})
        doc = json.load(open(rec.dump(reason="test")))
        assert validate_dump(doc) == []

    def test_disarmed_records_nothing(self):
        rec = FlightRecorder(capacity=8)
        rec.record("iteration", iteration=0)
        assert rec.events() == []
        assert rec.maybe_dump(reason="x") is None

    def test_maybe_dump_needs_events(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.enable(path=str(tmp_path / "fr.json"))
        assert rec.maybe_dump(reason="empty") is None
        rec.record("checkpoint", iteration=3, path="/x")
        assert rec.maybe_dump(reason="full") is not None

    def test_validate_dump_flags_violations(self):
        assert validate_dump([]) != []
        assert any("format" in e for e in validate_dump(
            {"format": "bogus", "reason": "r", "dumped_at_unix": 1.0,
             "n_recorded": 0, "n_dropped": 0, "events": []}))
        bad_seq = {"format": FORMAT, "reason": "r",
                   "dumped_at_unix": 1.0, "n_recorded": 2,
                   "n_dropped": 0,
                   "events": [{"seq": 5, "ts_unix": 1.0, "kind": "a"},
                              {"seq": 4, "ts_unix": 1.0, "kind": "b"}]}
        assert any("not increasing" in e for e in validate_dump(bad_seq))

    def test_train_records_iterations_and_checkpoints(self, tmp_path):
        x, y = _binary_fixture()
        ckpt = str(tmp_path / "t.ckpt")
        global_flightrec.enable(path=str(tmp_path / "fr.json"))
        params = {"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "tpu_checkpoint_path": ckpt,
                  "tpu_checkpoint_every": 2}
        lgb.train(params, lgb.Dataset(x, label=y, params=params),
                  num_boost_round=4)
        kinds = [e["kind"] for e in global_flightrec.events()]
        assert kinds.count("iteration") == 4
        assert "checkpoint" in kinds


# ---------------------------------------------------------------------------
class TestProfileWindow:
    def test_window_lifecycle_idempotent(self):
        global_profile.reset()
        global_profile.start_window()
        global_profile.start_window()  # no nested window
        s = global_profile.stop_window()
        assert s["n_windows"] == 1
        s2 = global_profile.stop_window()  # idempotent
        assert s2["n_windows"] == 1
        assert s2["window_wall_s"] == pytest.approx(
            s["window_wall_s"], abs=1e-3)

    def test_timed_dispatch_attribution_and_bit_identity(self):
        import jax.numpy as jnp
        global_xla.enable()  # AOT entries are what stop_window reruns

        def _sq(v):
            return jnp.sum(v * v)

        fn = instrumented_jit("test/profile_sq", _sq, phase="train")
        v = jnp.arange(128, dtype=jnp.float32)
        off = fn(v)  # compile + run outside any window
        global_profile.reset()
        global_profile.start_window()
        on = fn(v)
        on2 = fn(v)
        s = global_profile.stop_window()
        assert float(on) == float(off) == float(on2)  # sync, no values
        assert s["device_seconds_by_tag"]["test/profile_sq"] > 0.0
        assert s["calls_by_tag"]["test/profile_sq"] == 2
        assert s["phase_by_tag"]["test/profile_sq"] == "train"
        assert s["source"] == "fallback"
        # the retained executable was micro-rerun at window close
        assert s["rerun_seconds_by_tag"]["test/profile_sq"] >= 0.0

    def test_no_capture_outside_window(self):
        import jax.numpy as jnp
        fn = instrumented_jit("test/profile_idle", lambda v: v + 1)
        global_profile.reset()
        fn(jnp.arange(8))
        s = global_profile.summary()
        assert "test/profile_idle" not in s["device_seconds_by_tag"]

    def test_summary_live_while_capturing(self):
        global_profile.reset()
        global_profile.start_window()
        s = global_profile.summary()
        assert s["window_wall_s"] >= 0.0
        assert global_profile.capturing
        global_profile.stop_window()


# ---------------------------------------------------------------------------
class TestRoofline:
    def test_device_peaks_table_and_env_override(self, monkeypatch):
        from lightgbm_tpu.hostenv import device_peaks
        cpu, v5e = device_peaks("cpu"), device_peaks("TPU v5 lite")
        assert 0 < cpu["flops_per_s"] < v5e["flops_per_s"]
        assert 0 < cpu["bytes_per_s"] < v5e["bytes_per_s"]
        # the published v5e peaks (Google Cloud documentation, "TPU v5e")
        assert v5e == {"flops_per_s": 1.97e14, "int8_ops_per_s": 3.93e14,
                       "bytes_per_s": 8.19e11}
        # a device that is not in the table is an error, not a default
        for kind in ("tpu", "TPU v4", "unknown"):
            with pytest.raises(KeyError, match="no roofline peaks"):
                device_peaks(kind)
        monkeypatch.setenv("LGBM_TPU_PEAK_FLOPS", "1e9")
        monkeypatch.setenv("LGBM_TPU_PEAK_BYTES_PER_S", "2e9")
        over = device_peaks("cpu")
        assert over["flops_per_s"] == pytest.approx(1e9)
        assert over["bytes_per_s"] == pytest.approx(2e9)

    def test_join_with_cost_analysis(self):
        import jax.numpy as jnp
        global_xla.enable()

        def _mm(a):
            return a @ a

        fn = instrumented_jit("test/roofline_mm", _mm, phase="train")
        a = jnp.ones((64, 64), dtype=jnp.float32)
        global_profile.reset()
        global_profile.start_window()
        fn(a)
        global_profile.stop_window()
        rl = global_profile.roofline(
            device_kind="cpu",
            peaks={"bytes_per_s": 1e10, "flops_per_s": 1e11})
        row = rl["by_tag"]["test/roofline_mm"]
        assert row["device_s"] > 0 and row["calls"] == 1
        assert rl["peaks"]["bytes_per_s"] == 1e10
        assert rl["ridge_flops_per_byte"] == pytest.approx(10.0)
        if "bytes_per_call" in row:  # backend exposed cost analysis
            assert row["achieved_bytes_per_s"] > 0
            assert row["bytes_utilization"] > 0
            assert row["verdict"] in ("memory-bound", "compute-bound")
        else:
            assert row["verdict"] == "unknown"

    def test_fields_absent_when_unattributable(self):
        global_profile.reset()
        global_profile.start_window()
        rl_empty = global_profile.roofline(
            device_kind="cpu", peaks={"bytes_per_s": 1.0,
                                      "flops_per_s": 1.0})
        global_profile.stop_window()
        assert rl_empty["by_tag"] == {}


# ---------------------------------------------------------------------------
class TestTrainKnob:
    def test_window_knob_attributes_and_preserves_model(self):
        x, y = _binary_fixture()
        base = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
        params = dict(base, tpu_profile="window", tpu_profile_window=2)
        global_profile.reset()
        bst = lgb.train(params, lgb.Dataset(x, label=y, params=params),
                        num_boost_round=5)
        s = global_profile.stop_window()
        assert any(t.startswith("boosting/")
                   for t in s["device_seconds_by_tag"])
        assert s["mode"] == "window"
        assert 0.0 < s["coverage"] <= 1.5
        global_profile.reset()
        bst_off = lgb.train(base,
                            lgb.Dataset(x, label=y, params=base),
                            num_boost_round=5)

        def strip(m):
            return "\n".join(l for l in m.splitlines()
                             if not l.startswith("[tpu_profile"))

        assert strip(bst.model_to_string()) == \
            strip(bst_off.model_to_string())

    def test_bench_knob_leaves_window_open(self):
        x, y = _binary_fixture(n=200)
        params = {"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "tpu_profile": "bench"}
        global_profile.reset()
        lgb.train(params, lgb.Dataset(x, label=y, params=params),
                  num_boost_round=2)
        assert global_profile.capturing  # bench mode: caller closes
        s = global_profile.stop_window()
        assert s["mode"] == "bench"
        # bench windows open at iteration 0: both iterations attributed
        assert sum(s["calls_by_tag"].values()) >= 2

    def test_bad_knob_rejected(self):
        x, y = _binary_fixture(n=120)
        params = {"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "tpu_profile": "sometimes"}
        with pytest.raises(ValueError, match="tpu_profile"):
            lgb.train(params, lgb.Dataset(x, label=y, params=params),
                      num_boost_round=1)


# ---------------------------------------------------------------------------
class TestExport:
    def test_negotiation(self):
        assert negotiate_content_type(
            "application/openmetrics-text; version=1.0.0"
        ) == OPENMETRICS_CONTENT_TYPE
        assert negotiate_content_type("text/plain").startswith(
            "text/plain")
        assert negotiate_content_type(None).startswith("text/plain")

    def test_document_is_eof_terminated(self):
        text = render_openmetrics()
        assert text.splitlines()[-1] == "# EOF"
        assert validate_exposition(text)[0] == []

    def test_profile_families_present_after_capture(self):
        import jax.numpy as jnp
        fn = instrumented_jit("test/export_prof", lambda v: v * 2)
        global_profile.reset()
        global_profile.start_window()
        fn(jnp.arange(16))
        global_profile.stop_window()
        text = render_openmetrics()
        errors, families = validate_exposition(text)
        assert errors == []
        for fam in ("lgbmtpu_profile_window_seconds",
                    "lgbmtpu_profile_coverage",
                    "lgbmtpu_profile_device_seconds_total",
                    "lgbmtpu_profile_calls_total"):
            assert fam in families, fam
        assert 'tag="test/export_prof"' in text

    def test_no_capture_no_profile_families(self):
        global_profile.reset()
        assert "lgbmtpu_profile_" not in render_openmetrics()


# ---------------------------------------------------------------------------
class TestChromeDeviceLane:
    def test_device_lane_merged_and_trace_valid(self, tmp_path):
        from lightgbm_tpu.obs.trace import Tracer, global_tracer
        from check_trace import check_trace
        x, y = _binary_fixture()
        params = {"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "tpu_profile": "window",
                  "tpu_profile_window": 2}
        global_tracer.enable()
        try:
            global_profile.reset()
            lgb.train(params, lgb.Dataset(x, label=y, params=params),
                      num_boost_round=4)
            global_profile.stop_window()
            path = str(tmp_path / "trace.json")
            global_tracer.export_chrome(path)
        finally:
            global_tracer.disable()
            global_tracer.reset()
        ok, msg = check_trace(path)
        assert ok, msg
        assert "device-lane slice" in msg
        doc = json.load(open(path))
        lane_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("ph") == "M"
                     and e.get("name") == "process_name"
                     and e["args"]["name"] == DEVICE_LANE_NAME}
        assert len(lane_pids) == 1
        spans = [e for e in doc["traceEvents"]
                 if e.get("ph") == "X" and e["pid"] in lane_pids]
        assert spans and all(e["args"]["source"] == "fallback"
                             for e in spans)

    def test_no_slices_no_lane(self):
        global_profile.reset()
        assert global_profile.device_lane_events(pid=99) == []


# ---------------------------------------------------------------------------
class TestConcurrentScrapes:
    def test_scrapes_stay_valid_during_live_training(self):
        """Satellite 3: a ThreadingHTTPServer scrape racing live
        train_one_iter counter updates must never return a torn or
        invalid exposition — every body lints line-by-line and stays
        EOF-terminated."""
        global_metrics.enable()
        endpoint = MetricsHTTPEndpoint(render_openmetrics, port=0)
        stop = threading.Event()
        bodies, errors = [], []

        def scrape_loop():
            while not stop.is_set():
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{endpoint.port}/metrics",
                            timeout=5) as resp:
                        body = resp.read().decode()
                except Exception as exc:  # pragma: no cover
                    errors.append(f"scrape failed: {exc}")
                    return
                lint, _ = validate_exposition(body)
                if lint:
                    errors.append(f"torn exposition: {lint[:3]}")
                if body.splitlines()[-1] != "# EOF":
                    errors.append("missing # EOF terminator")
                bodies.append(body)

        threads = [threading.Thread(target=scrape_loop)
                   for _ in range(4)]
        for t in threads:
            t.start()
        try:
            x, y = _binary_fixture(n=600)
            params = {"objective": "binary", "num_leaves": 15,
                      "verbosity": -1}
            lgb.train(params, lgb.Dataset(x, label=y, params=params),
                      num_boost_round=8)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            endpoint.close()
        assert errors == []
        assert len(bodies) >= 8  # the race actually ran


# ---------------------------------------------------------------------------
class TestPerfGateCheck11:
    def _floor(self):
        return {"profile": {"min_coverage": 0.2, "max_coverage": 1.5,
                            "min_utilization": 1e-6}}

    def _candidate(self, tmp_path, coverage=0.6, util=0.01):
        # vs_baseline matches the best recorded cpu round so the
        # trajectory check (gate check 3) stays green for this
        # synthetic candidate whatever the repo's bench history holds
        import check_perf_gate as gate
        best = max([r.get("vs_baseline", 0.0) or 0.0
                    for _, r in gate._load_bench_lines()
                    if gate._platform_of(r.get("unit", "")) == "cpu"],
                   default=1.0)
        rec = {"metric": "boosting_iters_per_sec_higgs_shape",
               "value": 1.0, "vs_baseline": best or 1.0,
               "unit": "iters/sec (platform=cpu)",
               "device_seconds_by_tag": {"boosting/fused_iter": 0.5},
               "roofline": {
                   "platform": "cpu", "coverage": coverage,
                   "peaks": {"bytes_per_s": 1e10, "flops_per_s": 1e11},
                   "by_tag": {"boosting/fused_iter": {
                       "device_s": 0.5, "calls": 3, "phase": "train",
                       "bytes_utilization": util,
                       "verdict": "memory-bound"}}}}
        path = tmp_path / "CAND.json"
        path.write_text(json.dumps(rec))
        return str(path)

    def test_pass_fail_and_skip(self, tmp_path):
        from check_perf_gate import check_profile_roofline
        floor = self._floor()
        failures = []
        check_profile_roofline(floor, failures,
                               self._candidate(tmp_path))
        assert failures == []
        check_profile_roofline(floor, failures,
                               self._candidate(tmp_path, coverage=0.01))
        assert len(failures) == 1 and "coverage" in failures[0]
        failures = []
        check_profile_roofline(floor, failures,
                               self._candidate(tmp_path, util=1e-9))
        assert len(failures) == 1 and "utilization" in failures[0]
        failures = []
        check_profile_roofline({}, failures,
                               self._candidate(tmp_path))
        assert failures == []  # no floor section -> skip

    def test_gate_main_passes_on_repo_state(self, tmp_path):
        from check_perf_gate import main as gate_main
        assert gate_main([self._candidate(tmp_path)]) == 0


# ---------------------------------------------------------------------------
class TestBenchReport:
    def test_collect_fishes_both_shapes(self, tmp_path):
        import bench_report
        bare = {"metric": "m", "value": 1.0,
                "unit": "iters/sec (platform=cpu)"}
        (tmp_path / "BENCH_r01.json").write_text(json.dumps(bare))
        line = json.dumps(dict(bare, value=2.0))
        wrapper = {"n": 2, "cmd": "bench", "rc": 0,
                   "tail": f"noise\n{line}\n"}
        (tmp_path / "BENCH_r02.json").write_text(json.dumps(wrapper))
        (tmp_path / "MULTICHIP_r01.json").write_text(
            json.dumps({"rc": 1, "skipped": True, "tail": "no line"}))
        recs = bench_report.collect(repo=str(tmp_path))
        assert [(f, r["value"]) for f, r in recs] == [
            ("BENCH_r01.json", 1.0), ("BENCH_r02.json", 2.0)]

    def test_regression_flagged_across_trajectory(self):
        import bench_report
        recs = [("BENCH_r01.json", {"metric": "m", "value": 1.0,
                                    "unit": "u (platform=cpu)"}),
                ("BENCH_r02.json", {"metric": "m", "value": 0.5,
                                    "unit": "u (platform=cpu)"})]
        report = bench_report.build_report(recs, max_drop=0.10)
        assert len(report["regressions"]) == 1
        assert "BENCH_r02.json" in report["regressions"][0]
        md = bench_report.render_markdown(report)
        assert "REGRESSION" in md
        clean = bench_report.build_report(recs[:1], max_drop=0.10)
        assert clean["regressions"] == []
        assert "No rounds below" in bench_report.render_markdown(clean)

    def test_report_on_repo_records_runs(self):
        import bench_report
        report = bench_report.build_report(bench_report.collect(), 0.10)
        bench_report.render_markdown(report)  # must not raise


# ---------------------------------------------------------------------------
class TestCheckProfileTool:
    def test_validator_passes(self):
        """The quick-tier wiring for tools/check_profile.py: the full
        fallback-attribution + roofline + egress + bit-identity +
        flight-recorder pipeline on the CPU fixture."""
        import check_profile
        assert check_profile.main() == 0
