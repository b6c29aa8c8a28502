"""Device-time attribution: the fourth obs pillar (ISSUE 16).

Everything timed elsewhere in the obs stack is a host-side wall span,
and every device-side number is a *static* XLA cost-analysis estimate
(obs/xla.py). This module measures where device time actually goes,
keyed back to the existing obs program tags (``boosting/fused_iter``,
``boosting/grow``, ``predict/traversal``, ...):

* **Profiler capture** — ``jax.profiler.start_trace`` /
  ``stop_trace`` around a bounded window of training iterations or
  serve requests (armed by the ``tpu_profile=off/window/bench`` knob;
  ``LGBM_TPU_PROFILE_DIR`` selects the trace directory and turns the
  real profiler on). The emitted trace-events JSON is parsed into
  per-program device-busy seconds via the jitted function names
  ``instrumented_jit`` registers at wrap time.
* **Profiler-free fallback** — while a window is open, every
  ``instrumented_jit`` dispatch is re-timed with a
  ``jax.block_until_ready`` sync (``timed_call``), and the AOT
  executables obs/xla.py caches are re-run at window close
  (``block_until_ready`` micro-reruns, best-of-N) — so CPU CI
  exercises the identical attribution plumbing with no profiler.
* **Roofline layer** — ``roofline()`` joins measured device seconds
  with XLA cost-analysis flops/bytes (obs/xla.py) and the analytic
  ``learner.hist_traffic_model`` bytes already published under
  ``meta["hist_traffic"]``, divides by the per-device peaks tabled in
  ``hostenv.device_peaks`` (env-overridable), and emits achieved
  bytes/s + utilization-vs-peak + a memory-bound/compute-bound verdict
  per tag. Surfaced in bench JSON (``device_seconds_by_tag``,
  ``roofline``), OpenMetrics (``lgbmtpu_profile_*``), the Chrome trace
  (a separate device-lane pid, obs/trace.py) and perf-gate check 11.

Windows never nest; ``start_window``/``stop_window`` accumulate across
repeated windows. Capture changes no computed values (a sync is
observationally pure), so models are bit-identical profiling on vs off.
The disabled path is a single attribute check (``capturing``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .metrics import global_metrics

MAX_SLICES = 20000  # bounded per-call slice buffer for the trace lane
_ENV_DIR = "LGBM_TPU_PROFILE_DIR"
_ENV_MODE = "LGBM_TPU_PROFILE"

DEVICE_LANE_NAME = "lightgbm_tpu device"


def _default_device_kind() -> str:
    """``hostenv.device_peaks`` key of the process's default device:
    "cpu" on the CPU backend, else the device's ``device_kind``. A
    backend that cannot initialise raises — it is never read as CPU."""
    import jax
    dev = jax.devices()[0]
    return "cpu" if dev.platform == "cpu" else str(dev.device_kind)


def parse_trace_events(events: List[Dict[str, Any]],
                       name_to_tag: Dict[str, str]
                       ) -> Tuple[Dict[str, float],
                                  List[Tuple[str, float, float]]]:
    """Attribute profiler trace events to obs program tags.

    -> ({tag: device_busy_seconds}, [(tag, ts_us, dur_us), ...]).

    Pure function (importable for tests). Device pids are identified by
    ``process_name`` metadata (``/device:``, ``TPU``, ``GPU`` — the
    names the XLA profiler plugin emits); when no pid is identifiably a
    device (single-process CPU traces) every pid counts. A complete
    event is attributed to the tag whose registered jitted-function
    name appears in the event name, longest name first so e.g.
    ``_fused_iter_impl`` wins over ``_iter``."""
    dev_pids = set()
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            nm = str((ev.get("args") or {}).get("name", ""))
            if "/device:" in nm or nm.startswith(("TPU", "GPU", "Device")):
                dev_pids.add(ev.get("pid"))
    names = sorted(((n, t) for n, t in name_to_tag.items() if n),
                   key=lambda kv: -len(kv[0]))
    secs: Dict[str, float] = {}
    slices: List[Tuple[str, float, float]] = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        if dev_pids and ev.get("pid") not in dev_pids:
            continue
        dur = ev.get("dur")
        if not isinstance(dur, (int, float)) or dur <= 0:
            continue
        ev_name = str(ev.get("name", ""))
        for fname, tag in names:
            if fname in ev_name:
                secs[tag] = secs.get(tag, 0.0) + float(dur) / 1e6
                if len(slices) < MAX_SLICES:
                    ts = ev.get("ts")
                    slices.append((tag,
                                   float(ts) if isinstance(
                                       ts, (int, float)) else 0.0,
                                   float(dur)))
                break
    return secs, slices


def load_profiler_trace(log_dir: str) -> Optional[List[Dict[str, Any]]]:
    """Newest ``*.trace.json(.gz)`` under a ``jax.profiler`` log dir,
    parsed to its event list — or None when the profiler emitted no
    chrome-format trace (xplane-only versions)."""
    paths = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        paths.extend(glob.glob(os.path.join(log_dir, pat), recursive=True))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as fh:
                doc = json.load(fh)
        else:
            with open(path) as fh:
                doc = json.load(fh)
    except Exception:
        return None
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        return events if isinstance(events, list) else None
    return doc if isinstance(doc, list) else None


class ProfileRegistry:
    """Global device-time attribution state (see module docstring).

    ``capturing`` is the one-attribute fast gate obs/xla.py checks per
    dispatch; everything else only runs inside an open window."""

    def __init__(self) -> None:
        self.capturing = False
        self.mode = "off"
        self._lock = threading.Lock()
        self._fallback_s: Dict[str, float] = {}
        self._profiler_s: Dict[str, float] = {}
        self._calls: Dict[str, int] = {}
        self._phase: Dict[str, str] = {}
        self._rerun_s: Dict[str, float] = {}
        self._slices: List[Tuple[str, float, float, str]] = []
        self._dropped_slices = 0
        self._entries: Dict[str, Tuple[Any, tuple, dict]] = {}
        self._name_to_tag: Dict[str, str] = {}
        self._wall_s = 0.0
        self._t0: Optional[float] = None
        self._t0_ns = 0
        self._n_windows = 0
        self._trace_dir: Optional[str] = None
        self._tracing = False
        self.last_roofline: Optional[Dict[str, Any]] = None

    # -- registration (always-on, negligible) --------------------------
    def register_tag(self, tag: str, phase: Optional[str],
                     fn_name: str) -> None:
        """Called once per instrumented_jit wrap: maps the jitted
        function name back to the obs tag for profiler-trace parsing."""
        with self._lock:
            if fn_name:
                self._name_to_tag[fn_name] = tag
            if phase:
                self._phase.setdefault(tag, phase)

    # -- window lifecycle ----------------------------------------------
    def start_window(self, source: str = "window",
                     profile_dir: Optional[str] = None) -> None:
        """Open a capture window. Idempotent while one is open. When a
        profile dir is given (arg or LGBM_TPU_PROFILE_DIR) the real
        ``jax.profiler`` trace starts too; the fallback timing always
        runs so both paths share one attribution pipeline."""
        with self._lock:
            if self.capturing:
                return
            self._t0 = time.perf_counter()
            self._t0_ns = time.perf_counter_ns()
            self._n_windows += 1
            self.capturing = True
        if self.mode == "off":
            self.mode = source if source in ("window", "bench") else "window"
        target = profile_dir or os.environ.get(_ENV_DIR, "")
        if target:
            try:
                import jax.profiler
                jax.profiler.start_trace(target)
                self._trace_dir = target
                self._tracing = True
            except Exception:
                self._tracing = False

    def stop_window(self) -> Dict[str, Any]:
        """Close the window: stop/parse the profiler trace if one ran,
        micro-rerun the registered AOT executables, drop the retained
        call args, cache the roofline. Returns ``summary()``.
        Idempotent — safe to call with no window open."""
        with self._lock:
            was_open = self.capturing
            self.capturing = False
            if was_open and self._t0 is not None:
                self._wall_s += time.perf_counter() - self._t0
            self._t0 = None
        if not was_open:
            return self.summary()
        if self._tracing:
            self._tracing = False
            try:
                import jax.profiler
                jax.profiler.stop_trace()
                self._ingest_profiler_dir(self._trace_dir)
            except Exception:
                pass
        self._micro_rerun()
        with self._lock:
            self._entries.clear()  # drop retained device buffers
        try:
            self.last_roofline = self.roofline()
        except Exception:
            self.last_roofline = None
        return self.summary()

    maybe_stop = stop_window  # crash/egress-path alias (idempotent)

    def reset(self) -> None:
        """Testing hook: drop measurements; tag registrations persist
        (they are wrap-time facts, not window state)."""
        with self._lock:
            self.capturing = False
            self.mode = "off"
            self._fallback_s.clear()
            self._profiler_s.clear()
            self._calls.clear()
            self._rerun_s.clear()
            self._slices.clear()
            self._dropped_slices = 0
            self._entries.clear()
            self._wall_s = 0.0
            self._t0 = None
            self._n_windows = 0
            self._tracing = False
            self._trace_dir = None
            self.last_roofline = None

    # -- fallback measurement (obs/xla.py dispatch hooks) --------------
    def timed_call(self, tag: str, phase: Optional[str], fn: Callable,
                   args: tuple, kwargs: dict):
        """Run one dispatch with a device sync and attribute its wall
        time to `tag`. A sync changes no values — profiling on vs off
        is bit-identical — it only serializes the dispatch, which is
        the price of honest per-program time without a profiler."""
        t0 = time.perf_counter_ns()
        out = fn(*args, **kwargs)
        try:
            import jax
            jax.block_until_ready(out)
        except Exception:
            pass
        dt_ns = time.perf_counter_ns() - t0
        with self._lock:
            self._fallback_s[tag] = (self._fallback_s.get(tag, 0.0)
                                     + dt_ns / 1e9)
            self._calls[tag] = self._calls.get(tag, 0) + 1
            if phase:
                self._phase.setdefault(tag, phase)
            if len(self._slices) < MAX_SLICES:
                self._slices.append((tag, float(t0), float(dt_ns),
                                     "fallback"))
            else:
                self._dropped_slices += 1
        return out

    def register_entry(self, tag: str, phase: Optional[str], entry: Any,
                       args: tuple, kwargs: dict) -> None:
        """Retain the latest (executable, concrete args) per tag while a
        window is open, for ``stop_window``'s micro-reruns. Cleared at
        window close so device buffers are not pinned past it."""
        with self._lock:
            self._entries[tag] = (entry, args, kwargs)
            if phase:
                self._phase.setdefault(tag, phase)

    def _micro_rerun(self, reps: int = 2) -> None:
        """Re-time each retained AOT executable best-of-`reps` with
        block_until_ready — the pure device+runtime cost of one call,
        free of the Python dispatch the inline timing includes. Skips
        entries whose buffers were donated/freed (best-effort)."""
        with self._lock:
            items = list(self._entries.items())
        for tag, (entry, args, kwargs) in items:
            try:
                import jax
                best = None
                for _ in range(max(reps, 1)):
                    t0 = time.perf_counter()
                    out = entry(*args, **kwargs)
                    jax.block_until_ready(out)
                    dt = time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                with self._lock:
                    self._rerun_s[tag] = best
            except Exception:
                continue

    # -- profiler ingestion --------------------------------------------
    def _ingest_profiler_dir(self, log_dir: Optional[str]) -> None:
        if not log_dir:
            return
        events = load_profiler_trace(log_dir)
        if not events:
            return
        with self._lock:
            mapping = dict(self._name_to_tag)
        secs, slices = parse_trace_events(events, mapping)
        if not secs:
            return
        base_us = min(ts for _, ts, _ in slices) if slices else 0.0
        with self._lock:
            for tag, s in secs.items():
                self._profiler_s[tag] = self._profiler_s.get(tag, 0.0) + s
            for tag, ts_us, dur_us in slices:
                if len(self._slices) >= MAX_SLICES:
                    self._dropped_slices += 1
                    continue
                # rebase the profiler clock onto the window's
                # perf_counter_ns origin so host+device lanes align
                t0_ns = self._t0_ns + (ts_us - base_us) * 1e3
                self._slices.append((tag, t0_ns, dur_us * 1e3,
                                     "profiler"))

    # -- reporting ------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """Attribution snapshot; live-readable while capturing.
        ``device_seconds_by_tag`` prefers profiler-measured seconds per
        tag, falling back to the sync-timed dispatches."""
        with self._lock:
            fallback = dict(self._fallback_s)
            profiler = dict(self._profiler_s)
            calls = dict(self._calls)
            phase = dict(self._phase)
            rerun = dict(self._rerun_s)
            wall = self._wall_s
            if self.capturing and self._t0 is not None:
                wall += time.perf_counter() - self._t0
            n_windows = self._n_windows
            mode = self.mode
        merged = dict(fallback)
        merged.update(profiler)
        total = sum(merged.values())
        coverage = (total / wall) if wall > 0 else None
        out: Dict[str, Any] = {
            "mode": mode,
            "source": "profiler" if profiler else "fallback",
            "n_windows": n_windows,
            "window_wall_s": round(wall, 6),
            "device_seconds_total": round(total, 6),
            "device_seconds_by_tag": {t: round(s, 6)
                                      for t, s in merged.items()},
            "calls_by_tag": calls,
            "phase_by_tag": {t: phase.get(t, "") for t in merged},
        }
        if coverage is not None:
            out["coverage"] = round(coverage, 4)
        if rerun:
            out["rerun_seconds_by_tag"] = {t: round(s, 6)
                                           for t, s in rerun.items()}
        return out

    def roofline(self, device_kind: Optional[str] = None,
                 peaks: Optional[Dict[str, float]] = None
                 ) -> Dict[str, Any]:
        """Join measured device seconds with XLA cost-analysis flops /
        bytes and the analytic histogram-traffic bytes, against the
        device's published peaks (hostenv.device_peaks; a device that
        is not in that table raises): achieved bytes/s and flops/s,
        utilization-vs-peak, and a memory-bound / compute-bound verdict
        per tag. Fields are absent (not zero) where unattributable —
        check 11 skips gracefully on absence."""
        s = self.summary()
        device_kind = device_kind or _default_device_kind()
        if peaks is None:
            from ..hostenv import device_peaks
            peaks = device_peaks(device_kind)
        peak_b = float(peaks.get("bytes_per_s", 0.0))
        peak_f = float(peaks.get("flops_per_s", 0.0))
        ridge = (peak_f / peak_b) if peak_b > 0 and peak_f > 0 else None
        by_tag_cost: Dict[str, Any] = {}
        try:
            from .xla import global_xla
            by_tag_cost = global_xla.summary().get("by_tag", {})
        except Exception:
            pass
        hist = (global_metrics.meta or {}).get("hist_traffic") or {}
        by_tag: Dict[str, Dict[str, Any]] = {}
        for tag, dev_s in s["device_seconds_by_tag"].items():
            calls = int(s["calls_by_tag"].get(tag, 0))
            row: Dict[str, Any] = {"device_s": dev_s, "calls": calls,
                                   "phase": s["phase_by_tag"].get(tag, "")}
            cost = by_tag_cost.get(tag) or {}
            progs = max(int(cost.get("programs", 0)), 1)
            oi = None
            fl = cost.get("flops")
            byts = cost.get("bytes_accessed")
            if isinstance(byts, (int, float)) and byts > 0:
                bpc = byts / progs
                row["bytes_per_call"] = round(bpc, 1)
                if dev_s > 0 and calls > 0:
                    abps = bpc * calls / dev_s
                    row["achieved_bytes_per_s"] = round(abps, 1)
                    if peak_b > 0:
                        row["bytes_utilization"] = round(abps / peak_b, 8)
            if isinstance(fl, (int, float)) and fl > 0:
                fpc = fl / progs
                row["flops_per_call"] = round(fpc, 1)
                if dev_s > 0 and calls > 0:
                    afps = fpc * calls / dev_s
                    row["achieved_flops_per_s"] = round(afps, 1)
                    if peak_f > 0:
                        row["flops_utilization"] = round(afps / peak_f, 8)
                if isinstance(byts, (int, float)) and byts > 0:
                    oi = fl / byts
                    row["operational_intensity"] = round(oi, 4)
            if oi is not None and ridge is not None:
                row["verdict"] = ("memory-bound" if oi < ridge
                                  else "compute-bound")
            else:
                row["verdict"] = "unknown"
            by_tag[tag] = row
        out: Dict[str, Any] = {
            "device_kind": device_kind,
            "peaks": {"bytes_per_s": peak_b, "flops_per_s": peak_f},
            "window_wall_s": s["window_wall_s"],
            "source": s["source"],
            "by_tag": by_tag,
        }
        if ridge is not None:
            out["ridge_flops_per_byte"] = round(ridge, 4)
        if "coverage" in s:
            out["coverage"] = s["coverage"]
        if isinstance(hist.get("hist_bytes_per_iter"), (int, float)):
            out["model_hist_bytes_per_iter"] = hist["hist_bytes_per_iter"]
        return out

    # -- Chrome trace device lane (obs/trace.py merges these) ----------
    def device_lane_events(self, pid: int) -> List[Dict[str, Any]]:
        """Captured device slices as Chrome trace events on their own
        pid — metadata first (check_trace.py requires a process_name
        per pid and a thread_name per track), then the spans sorted by
        start so per-track ts stays monotonic."""
        with self._lock:
            slices = list(self._slices)
        if not slices:
            return []
        events: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": DEVICE_LANE_NAME}},
            {"name": "process_sort_index", "ph": "M", "pid": pid,
             "tid": 0, "args": {"sort_index": 1}},
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": "device programs (attributed)"}},
        ]
        for tag, t0_ns, dur_ns, source in sorted(slices,
                                                 key=lambda s: s[1]):
            events.append({"name": tag, "ph": "X", "pid": pid, "tid": 0,
                           "ts": t0_ns / 1e3, "dur": dur_ns / 1e3,
                           "args": {"tag": tag, "source": source}})
        return events


global_profile = ProfileRegistry()
