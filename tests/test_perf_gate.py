"""tools/check_perf_gate.py — the CI perf-regression gate over the
BENCH_*.json trajectory and the histogram traffic-model floor
(ISSUE 7 satellite; ROADMAP item 4's driver-visible-proof debt)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_perf_gate  # noqa: E402


def test_gate_passes_on_repo_state(capsys):
    assert check_perf_gate.main([]) == 0
    out = capsys.readouterr().out
    assert "perf gate OK" in out
    assert "13-pass schedule" in out


def test_gate_reduction_floor_is_acceptance_number():
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    assert floor["hist"]["min_bytes_reduction"] >= 1.8


def test_gate_fails_on_traffic_regression(tmp_path, capsys):
    """A candidate whose own hist_bytes_reduction fell below the floor
    (scheduler/encoding regression) must fail the gate — the ratio is
    N-invariant, so it holds at any row count."""
    fat = {"metric": "boosting_iters_per_sec_higgs_shape",
           "value": 1.0, "vs_baseline": 1.0,
           "unit": "iters/sec (platform=cpu)",
           "hist_bytes_per_iter": int(12e9),
           "hist_bytes_reduction": 1.0}
    cand = tmp_path / "BENCH_candidate.json"
    cand.write_text(json.dumps(fat))
    assert check_perf_gate.main([str(cand)]) == 1
    assert "hist_bytes_reduction" in capsys.readouterr().out


def test_gate_accepts_unpacked_train_config_candidate(tmp_path):
    """The standard 63-bin train bench (no packing, ~1.35x reduction,
    bytes far above the packed fixture floor) must PASS: absolute bytes
    are not comparable across configs/row counts, only the ratio is."""
    ok = {"metric": "boosting_iters_per_sec_higgs_shape",
          "value": 50.0, "vs_baseline": 13.0,
          "unit": "iters/sec (N=10500000)",
          "hist_bytes_per_iter": int(6.0e9),
          "hist_bytes_reduction": 1.35}
    cand = tmp_path / "BENCH_candidate.json"
    cand.write_text(json.dumps(ok))
    assert check_perf_gate.main([str(cand)]) == 0


def test_gate_fails_on_throughput_drop(tmp_path, capsys, monkeypatch):
    """A candidate >10% below the recorded same-platform floor fails.
    The recorded trajectory is made here: the repo carries no
    BENCH_*.json of its own."""
    records = tmp_path / "records"
    records.mkdir()
    (records / "BENCH_r01.json").write_text(json.dumps(
        {"metric": "boosting_iters_per_sec_higgs_shape", "value": 0.35,
         "vs_baseline": 0.09, "unit": "iters/sec (N=50000, platform=cpu)"}))
    monkeypatch.setattr(check_perf_gate, "REPO", str(records))
    slow = {"metric": "boosting_iters_per_sec_higgs_shape",
            "value": 0.01, "vs_baseline": 0.09 * 0.5,
            "unit": "iters/sec (platform=cpu)"}
    cand = tmp_path / "BENCH_candidate.json"
    cand.write_text(json.dumps(slow))
    assert check_perf_gate.main([str(cand)]) == 1
    assert "dropped" in capsys.readouterr().out


def test_gate_includes_memory_ceiling(capsys):
    """The gate now recomputes the analytic peak-memory model against
    the recorded ceiling (ISSUE 8) — its line must appear in a passing
    run, and the floor file must carry the memory section."""
    assert check_perf_gate.main([]) == 0
    assert "memory model" in capsys.readouterr().out
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    assert floor["memory"]["max_peak_model_bytes"] > 0
    assert floor["memory"]["model_vs_measured_band"] == 1.5


def test_phase_trajectory_flags_regression():
    """A phase that blew past its recorded floor fails; phases below
    the absolute-noise floor are ignored."""
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    lines = [
        ("BENCH_a.json", {"unit": "iters/sec (platform=cpu)",
                          "phases": {"train/iteration": 1.0,
                                     "tiny": 0.01}}),
        ("BENCH_b.json", {"unit": "iters/sec (platform=cpu)",
                          "phases": {"train/iteration": 2.0,
                                     "tiny": 0.09}}),
    ]
    failures = []
    check_perf_gate.check_phase_trajectory(floor, failures, lines)
    assert len(failures) == 1 and "train/iteration" in failures[0]

    ok_lines = [
        ("BENCH_a.json", {"unit": "iters/sec (platform=cpu)",
                          "phases": {"train/iteration": 1.0}}),
        ("BENCH_b.json", {"unit": "iters/sec (platform=cpu)",
                          "phases": {"train/iteration": 1.2}}),
    ]
    failures = []
    check_perf_gate.check_phase_trajectory(floor, failures, ok_lines)
    assert failures == []


def test_phase_trajectory_skips_without_summaries(capsys):
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    failures = []
    check_perf_gate.check_phase_trajectory(
        floor, failures, [("BENCH_a.json", {"unit": "iters/sec"})])
    assert failures == []
    assert "skipped" in capsys.readouterr().out


def test_gate_parses_driver_wrapper_shape():
    """The driver stores bench output as {"n","cmd","rc","tail"}; the
    gate must dig the contract line out of `tail`."""
    rec = check_perf_gate._extract_metric_record({
        "n": 9, "rc": 0,
        "tail": 'noise\n{"metric": "boosting_iters_per_sec_higgs_shape", '
                '"value": 1.5, "vs_baseline": 0.39, "unit": "iters/sec"}\n'})
    assert rec is not None and rec["vs_baseline"] == 0.39
    assert check_perf_gate._extract_metric_record({"tail": "junk"}) is None


def test_xla_cross_check_runs_and_agrees(capsys):
    """Check 5 (ISSUE 9): the compiled packed+int8 wave kernel's
    argument bytes agree with the analytic traffic model per-pass
    within the declared band, and the memory model's operand/slab
    components cover the executable's buffers — on the CPU backend the
    check must RUN (not skip)."""
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    assert floor["xla"]["arg_bytes_band"] >= 1.0
    failures = []
    check_perf_gate.check_xla_cost_model(floor, failures)
    out = capsys.readouterr().out
    assert failures == []
    assert "xla vs traffic model" in out
    assert "xla vs memory model" in out
    assert "skipped" not in out


def test_xla_cross_check_flags_model_divergence(capsys):
    """A traffic model that diverged from what XLA streams must fail
    the band: simulate by shrinking the declared band to ~0."""
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    floor["xla"] = dict(floor["xla"], arg_bytes_band=1.0000001,
                        min_bytes_accessed_ratio=1e9)
    failures = []
    check_perf_gate.check_xla_cost_model(floor, failures)
    # the tight band trips at least the bytes-accessed ratio check
    assert any("xla cross-check" in f for f in failures)


def test_xla_cross_check_skips_gracefully(capsys, monkeypatch):
    """No cost analysis on the backend => skip, never fail."""
    import lightgbm_tpu.obs.xla as obs_xla
    monkeypatch.setattr(obs_xla, "aot_cost_summary",
                        lambda *a, **k: None)
    with open(check_perf_gate.FLOOR_PATH) as fh:
        floor = json.load(fh)
    failures = []
    check_perf_gate.check_xla_cost_model(floor, failures)
    assert failures == []
    assert "skipped" in capsys.readouterr().out

    # a missing floor section also skips
    failures = []
    check_perf_gate.check_xla_cost_model({}, failures)
    assert failures == []
