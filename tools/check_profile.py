#!/usr/bin/env python
"""CI validator for the device-time attribution pillar
(obs/profile.py) and the crash flight recorder (obs/flightrec.py).

Runs the whole plumbing on the CPU fixture — the profiler-free
fallback path re-times the instrumented_jit dispatches inline, so a
host with no TPU exercises the exact attribution/rollup/export code a
device capture feeds:

1. **Fallback attribution** — a knob-armed capture window
   (``tpu_profile=window``) over a small training run must attribute
   device seconds and calls to the training program tag(s) the run
   dispatched, with window coverage (attributed seconds over window
   wall time) inside the perf_floor.json ``profile`` band — the same
   band perf-gate check 11 holds bench records to. A second, manual
   window around a predict call must attribute ``predict/traversal``.
2. **Roofline** — the measured-vs-peak join must carry a valid
   memory-bound/compute-bound verdict per attributed tag, and (CPU
   exposes cost analysis) at least one tag must join achieved bytes/s
   + utilization against the hostenv.device_peaks row.
3. **OpenMetrics egress** — render_openmetrics() must surface every
   ``lgbmtpu_profile_*`` family, lint clean line-by-line
   (check_metrics_endpoint.validate_exposition), and stay
   ``# EOF``-terminated.
4. **Bit-identity** — the model trained with the capture window armed
   must serialize byte-for-byte identical to the same fixture trained
   with profiling off: attribution is a sync, never a value change.
5. **Flight recorder** — with the recorder armed, an injected
   poisoned-label fault under ``tpu_health=error`` must raise
   NonFiniteError AND leave a schema-valid dump
   (flightrec.validate_dump) containing the fault_injection event, the
   health_anomaly event, and the offending iteration's entry — the
   postmortem a dead run leaves behind.

Exit 0 = pass. Usage: python tools/check_profile.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

import numpy as np  # noqa: E402

_PROFILE_FAMILIES = [
    "lgbmtpu_profile_window_seconds",
    "lgbmtpu_profile_coverage",
    "lgbmtpu_profile_device_seconds_total",
    "lgbmtpu_profile_calls_total",
    "lgbmtpu_profile_achieved_bytes_per_second",
    "lgbmtpu_profile_utilization",
]


def _fail(msg: str) -> int:
    print(f"FAIL: {msg}")
    return 1


def main() -> int:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs.export import render_openmetrics
    from lightgbm_tpu.obs.flightrec import global_flightrec, validate_dump
    from lightgbm_tpu.obs.health import HealthError, global_health
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.obs.profile import global_profile
    from lightgbm_tpu.obs.xla import global_xla
    from lightgbm_tpu.resilience import faults
    from check_metrics_endpoint import validate_exposition

    with open(os.path.join(_REPO, "tools", "perf_floor.json")) as fh:
        band = json.load(fh)["profile"]
    min_cov = float(band["min_coverage"])
    max_cov = float(band["max_coverage"])

    rng = np.random.RandomState(0)
    n, f = 800, 8
    x = rng.randn(n, f)
    y = ((x[:, 2] + x[:, 4]) > 0.3).astype(np.float64)
    base = {"objective": "binary", "num_leaves": 7,
            "min_data_in_leaf": 5, "verbosity": -1}

    # --- 1. fallback attribution over a knob-armed window ------------
    global_metrics.enable()
    global_xla.enable()
    global_profile.reset()
    params = dict(base, tpu_profile="window", tpu_profile_window=3)
    bst = lgb.train(params, lgb.Dataset(x, label=y, params=params),
                    num_boost_round=6)
    model_profiled = bst.model_to_string()
    s = global_profile.stop_window()  # idempotent: the tick closed it
    secs = s.get("device_seconds_by_tag", {})
    if not secs:
        return _fail("capture window attributed no device seconds")
    train_tags = [t for t in secs
                  if t.startswith(("boosting/", "parallel/", "stream/"))]
    if not train_tags:
        return _fail(f"no training program tag attributed; got "
                     f"{sorted(secs)}")
    for tag in train_tags:
        if s["calls_by_tag"].get(tag, 0) <= 0 or secs[tag] <= 0.0:
            return _fail(f"tag {tag!r} has no calls/seconds")
    cov = s.get("coverage")
    if cov is None:
        return _fail("window summary carries no coverage")
    if not (min_cov <= cov <= max_cov):
        return _fail(f"window coverage {cov:.2%} outside the "
                     f"[{min_cov:.0%}, {max_cov:.0%}] floor band")
    print(f"# fallback attribution: {sorted(train_tags)} captured, "
          f"coverage {cov:.2%}: OK")

    # --- 1b. predict attribution over a manual window ----------------
    global_profile.start_window()
    pred_prof = bst.predict(x[:256], raw_score=True)
    s2 = global_profile.stop_window()
    if s2["device_seconds_by_tag"].get("predict/traversal", 0.0) <= 0.0:
        return _fail("predict window did not attribute "
                     "predict/traversal; got "
                     f"{sorted(s2['device_seconds_by_tag'])}")
    print("# predict attribution: predict/traversal captured: OK")

    # --- 2. roofline join --------------------------------------------
    rl = global_profile.roofline()
    for tag, row in rl["by_tag"].items():
        if row.get("verdict") not in ("memory-bound", "compute-bound"):
            return _fail(f"roofline tag {tag!r} has verdict "
                         f"{row.get('verdict')!r}")
        if row.get("device_s", 0.0) <= 0.0:
            return _fail(f"roofline tag {tag!r} has no device seconds")
    joined = [t for t, row in rl["by_tag"].items()
              if "achieved_bytes_per_s" in row
              and "bytes_utilization" in row]
    if not joined:
        return _fail("no tag joined cost-analysis bytes into achieved "
                     "bytes/s + utilization (CPU exposes cost analysis)")
    peaks = rl.get("peaks", {})
    if not (peaks.get("bytes_per_s", 0) > 0
            and peaks.get("flops_per_s", 0) > 0):
        return _fail(f"roofline peaks row is degenerate: {peaks}")
    print(f"# roofline: {len(joined)}/{len(rl['by_tag'])} tag(s) "
          f"joined vs {rl['device_kind']} peaks: OK")

    # --- 3. OpenMetrics families -------------------------------------
    text = render_openmetrics()
    errors, families = validate_exposition(text)
    if errors:
        return _fail(f"exposition lint: {errors[:5]}")
    missing = [fam for fam in _PROFILE_FAMILIES if fam not in families]
    if missing:
        return _fail(f"lgbmtpu_profile_* families missing from "
                     f"/metrics: {missing}")
    if text.splitlines()[-1].strip() != "# EOF":
        return _fail("exposition is not '# EOF'-terminated")
    print(f"# OpenMetrics: all {len(_PROFILE_FAMILIES)} profile "
          "families surfaced, lint clean, EOF-terminated: OK")

    # --- 4. bit-identity: profiling must never change the model ------
    global_profile.reset()
    bst_off = lgb.train(base, lgb.Dataset(x, label=y, params=base),
                        num_boost_round=6)

    def _strip_knob_echo(model: str) -> str:
        # the serialized params block faithfully echoes the profile
        # knobs, which differ by construction; the trees must not
        return "\n".join(line for line in model.splitlines()
                         if not line.startswith("[tpu_profile"))

    if _strip_knob_echo(bst_off.model_to_string()) != \
            _strip_knob_echo(model_profiled):
        return _fail("model trained under the capture window differs "
                     "from the unprofiled model — the attribution sync "
                     "changed values")
    pred_off = bst_off.predict(x[:256], raw_score=True)
    if not np.array_equal(np.asarray(pred_prof), np.asarray(pred_off)):
        return _fail("profiled-window predictions differ from the "
                     "unprofiled model's")
    print("# bit-identity profiling on vs off: OK")

    # --- 5. flight recorder on an injected fault ---------------------
    dump_path = os.path.join(tempfile.gettempdir(),
                             f"flightrec_check_{os.getpid()}.json")
    try:
        global_flightrec.reset()
        global_flightrec.enable(path=dump_path)
        faults.install(faults.FaultPlan(poison_labels_at_iter=1))
        # regression: the poisoned NaN label flows straight into the
        # gradient (binary's label threshold would swallow it)
        params_h = dict(base, objective="regression",
                        tpu_health="error")
        raised = None
        try:
            lgb.train(params_h,
                      lgb.Dataset(x, label=x[:, 0].astype(np.float64),
                                  params=params_h),
                      num_boost_round=4)
        except HealthError as exc:
            raised = exc
        finally:
            faults.reset()
        if raised is None:
            return _fail("poisoned-label fault under tpu_health=error "
                         "did not raise a HealthError")
        if not os.path.exists(dump_path):
            return _fail("no flight-recorder dump written on the "
                         "injected fault")
        with open(dump_path) as fh:
            doc = json.load(fh)
        schema_errors = validate_dump(doc)
        if schema_errors:
            return _fail(f"flight-recorder dump schema: "
                         f"{schema_errors[:5]}")
        if doc.get("reason") != type(raised).__name__:
            return _fail(f"dump reason {doc.get('reason')!r} != raised "
                         f"{type(raised).__name__!r}")
        kinds = {e["kind"] for e in doc["events"]}
        for want in ("iteration", "fault_injection", "health_anomaly"):
            if want not in kinds:
                return _fail(f"dump lacks a {want!r} event; got "
                             f"{sorted(kinds)}")
        anomaly = [e for e in doc["events"]
                   if e["kind"] == "health_anomaly"][-1]
        bad_iter = anomaly.get("iteration")
        if not any(e["kind"] == "iteration"
                   and e.get("iteration") == bad_iter
                   for e in doc["events"]):
            return _fail(f"dump lacks the offending iteration "
                         f"{bad_iter}'s own event")
        print(f"# flight recorder: {type(raised).__name__} dump with "
              f"{len(doc['events'])} event(s) incl. iteration "
              f"{bad_iter}: OK")
    finally:
        global_flightrec.reset()
        global_flightrec.disable()
        if os.path.exists(dump_path):
            os.remove(dump_path)
        global_health.reset()
        global_profile.reset()
        global_metrics.reset()
        global_metrics.disable()
        global_xla.disable()
        # undo the rest of global_metrics.enable()'s fan-out so an
        # in-process caller (tests) doesn't inherit an armed tracer
        from lightgbm_tpu.obs.memory import global_watermarks
        from lightgbm_tpu.obs.trace import global_tracer
        global_health.disable()
        global_tracer.disable()
        global_tracer.reset()
        global_watermarks.disable()

    print("check_profile: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
