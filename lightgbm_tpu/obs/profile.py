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
  real profiler on). The window's ``.xplane.pb`` is read back through
  ``jax.profiler.ProfileData``: each device plane's "XLA Modules" line
  gives per-program device seconds (by the jitted function names
  ``instrumented_jit`` registers at wrap time), its "XLA Ops" line the
  self seconds of every instruction, which the **layer table** turns
  into ``device_seconds_by_layer``. The host planes hold the program's
  own spans (``obs/trace.py`` enters a ``TraceAnnotation("lgbm/<span>")``
  while a profiler session is live), on the clock of the device ops, so
  each long idle gap is put down to the innermost ``lgbm/`` span that
  covers it.
* **Layer table** — ``layer_table(tag)``: ``{instruction: layer}`` read
  from the optimised HLO of the executable a train-phase tag last
  acquired (``instrumented_jit`` takes its ``Compiled`` from jit's
  caches right after the first dispatch). The layers are the ``lgbm/<layer>``
  ``jax.named_scope``s of boosting.py / learner.py (``sample``,
  ``gradient``, ``hist``, ``split``, ``partition``, ``score``, ``renew``,
  ``valid``, ``records``, ``collective``); an instruction's layer is the
  path component after the LAST ``lgbm`` in its ``op_name``. Parsed on
  first request (one ``as_text()`` and one pass over it), never on the
  training path.
* **Profiler-free fallback** — while a window is open, every
  ``instrumented_jit`` dispatch is re-timed with a
  ``jax.block_until_ready`` sync (``timed_call``), and with telemetry
  on the programs obs/xla.py registered are re-run at window close
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
  (the sync-timed slices on a separate device-lane pid, obs/trace.py:
  they are on the spans' clock by construction; profiler-measured ops
  stay in the ``.xplane.pb``, beside the ``lgbm/`` spans) and perf-gate
  check 11.

Windows never nest; ``start_window``/``stop_window`` accumulate across
repeated windows. Capture changes no computed values (a sync is
observationally pure), so models are bit-identical profiling on vs off.
The disabled path is a single attribute check (``capturing``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from .metrics import global_metrics

MAX_SLICES = 20000  # bounded per-call slice buffer for the trace lane
_ENV_DIR = "LGBM_TPU_PROFILE_DIR"
_ENV_MODE = "LGBM_TPU_PROFILE"

DEVICE_LANE_NAME = "lightgbm_tpu device"
SPAN_PREFIX = "lgbm/"         # obs/trace.py's spans in a profiler trace
_DEVICE_PLANE = "/device:"    # "/device:TPU:0", "/device:GPU:0"
_OPS_LINE = "XLA Ops"         # one event per executed HLO instruction
_MODULES_LINE = "XLA Modules"  # one event per executed program


def _default_device_kind() -> str:
    """``hostenv.device_peaks`` key of the process's default device:
    "cpu" on the CPU backend, else the device's ``device_kind``. A
    backend that cannot initialise raises — it is never read as CPU."""
    import jax
    dev = jax.devices()[0]
    return "cpu" if dev.platform == "cpu" else str(dev.device_kind)


# ---------------------------------------------------------------------------
# the layer table: optimised HLO text -> {instruction: layer}
_INSTR_RE = re.compile(r"\s*(?:ROOT\s+)?(%?[A-Za-z_][\w.\-]*) = ")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def instruction_head(text: str) -> Optional[str]:
    """``%name = shape`` of one HLO instruction: the part before the
    opcode, which the compiled text and a device trace's event name
    print alike (the trace adds operand shapes after it). None for a
    line that is no instruction."""
    m = _INSTR_RE.match(text)
    if m is None:
        return None
    rest = text[m.end():]
    if rest.startswith("("):            # tuple shape: to the matching ")"
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return f"{m.group(1)} = {rest[:i + 1]}"
        return None
    return f"{m.group(1)} = {rest.split(' ', 1)[0]}"


def layer_of(op_name: str) -> Optional[str]:
    """The path component right after the last ``lgbm`` component of an
    ``op_name`` (innermost scope wins), or None without one."""
    scope = scope_of(op_name)
    return None if scope is None else scope.split("/", 1)[0]


def scope_of(op_name: str) -> Optional[str]:
    """The scopes of an ``op_name`` below its last ``lgbm`` component:
    the layer and the named scopes inside it, up to the first
    transformation (``jit(...)``, ``vmap(...)``) or the operation's own
    name (``lgbm/hist/w03/jit(f)/.../pallas_call``: ``hist/w03``), or
    None without an ``lgbm`` scope."""
    parts = op_name.split("/")
    for i in range(len(parts) - 2, -1, -1):
        if parts[i] == "lgbm":
            scope = [parts[i + 1]]
            for part in parts[i + 2:-1]:
                if "(" in part:
                    break
                scope.append(part)
            return "/".join(scope)
    return None


def parse_scope_tables(hlo_text: str
                       ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """({``instruction_head``: layer}, {``instruction_head``: scope})
    in one pass over an optimised HLO module: every instruction of every
    computation (fusion roots, ``while`` bodies, custom calls, what sits
    inside a fused computation) whose ``op_name`` lies under an
    ``lgbm/<layer>`` scope; ``scope_of`` names its scope below the
    layer. Instructions without ``op_name`` or without a scope are
    absent."""
    layers: Dict[str, str] = {}
    scopes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        op = _OP_NAME_RE.search(line)
        scope = scope_of(op.group(1)) if op else None
        if scope is not None:
            head = instruction_head(line)
            if head is not None:
                layers[head] = scope.split("/", 1)[0]
                scopes[head] = scope
    return layers, scopes


def parse_layer_table(hlo_text: str) -> Dict[str, str]:
    """{``instruction_head``: layer} of an optimised HLO module
    (``parse_scope_tables``' first table)."""
    return parse_scope_tables(hlo_text)[0]


# ---------------------------------------------------------------------------
# the window's .xplane.pb -> seconds by tag and by layer, idle gaps
def find_xplane(log_dir: str) -> Optional[str]:
    """Newest ``.xplane.pb`` a ``jax.profiler`` session left under
    `log_dir`, or None."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def self_times(events: Iterable[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float]]:
    """(name, start_ns, self_ns) of each ``(name, start_ns, dur_ns)``:
    its duration less what its children on the same line cover (a
    ``while`` spans the instructions of its body). Events nest properly
    on one line."""
    out: List[Tuple[str, float, float]] = []
    stack: List[list] = []   # [end, name, start, dur, covered]

    def close():
        end, name, start, dur, covered = stack.pop()
        out.append((name, start, max(0.0, dur - covered)))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            close()
        if stack:
            stack[-1][4] += min(dur, stack[-1][0] - start)
        stack.append([start + dur, name, start, dur, 0.0])
    while stack:
        close()
    return out


def _idle_gaps(busy: List[Tuple[float, float]], t0: float, t1: float
               ) -> List[Tuple[float, float]]:
    """(start_ns, dur_ns) of every stretch of [t0, t1] outside the
    (start, end) intervals of `busy`, longest first."""
    gaps = []
    at = t0
    for start, end in sorted(busy):
        if start > at:
            gaps.append((at, min(start, t1) - at))
        at = max(at, end)
        if at >= t1:
            break
    if t1 > at:
        gaps.append((at, t1 - at))
    return sorted(gaps, key=lambda g: -g[1])


def _span_at(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost (shortest) host span that covers `t`."""
    best = None
    for name, start, dur in spans:
        if start <= t <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else ""


def attribute_xspace(data, name_to_tag: Dict[str, str],
                     tables: Dict[str, Dict[str, str]],
                     n_gaps: int = 10) -> Dict[str, Any]:
    """Reduce a ``jax.profiler.ProfileData`` to what the registry
    reports (pure: tests feed it an XSpace built from text).

    -> ``by_tag`` {tag: device seconds} from the "XLA Modules" lines,
    ``by_layer`` {layer: self seconds} from the "XLA Ops" lines through
    `tables` ({tag: layer table}; an op takes the table of the program
    that was running, else any table that knows it), ``unattributed_s``
    (ops no table places) and ``idle_gaps``: the `n_gaps` longest
    stretches without a device op, each with the innermost ``lgbm/``
    host span over its midpoint. Seconds are summed over the device
    planes."""
    names = sorted(((n, t) for n, t in name_to_tag.items() if n),
                   key=lambda kv: -len(kv[0]))
    merged: Dict[str, str] = {}
    for table in tables.values():
        merged.update(table)
    by_tag: Dict[str, float] = {}
    by_layer: Dict[str, float] = {}
    unattributed = 0.0
    busy: List[Tuple[float, float]] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, float(ev.start_ns),
                              float(ev.duration_ns)) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
            continue
        if not plane.name.startswith(_DEVICE_PLANE):
            continue
        modules: List[Tuple[float, float, str]] = []  # (start, end, tag)
        ops: List[Tuple[str, float, float]] = []
        for line in plane.lines:
            if line.name == _MODULES_LINE:
                for ev in line.events:
                    tag = next((t for n, t in names if n in ev.name), None)
                    if tag is not None:
                        start = float(ev.start_ns)
                        modules.append((start, start + ev.duration_ns,
                                        tag))
                        by_tag[tag] = by_tag.get(tag, 0.0) \
                            + ev.duration_ns / 1e9
            elif line.name == _OPS_LINE:
                ops.extend((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)) for ev in line.events)
        modules.sort()
        starts = [m[0] for m in modules]
        for name, start, self_ns in self_times(ops):
            i = bisect.bisect_right(starts, start) - 1
            table = tables.get(modules[i][2]) \
                if i >= 0 and start < modules[i][1] else None
            head = instruction_head(name)
            layer = (table or merged).get(head) if head else None
            if layer is None:
                unattributed += self_ns / 1e9
            else:
                by_layer[layer] = by_layer.get(layer, 0.0) + self_ns / 1e9
        busy.extend((start, start + dur) for _, start, dur in ops)
    gaps: List[Tuple[str, float]] = []
    if busy:
        t0 = min([b[0] for b in busy] + [s[1] for s in spans])
        t1 = max([b[1] for b in busy] + [s[1] + s[2] for s in spans])
        gaps = [(_span_at(spans, start + dur / 2), dur / 1e9)
                for start, dur in _idle_gaps(busy, t0, t1)[:n_gaps]]
    return {"by_tag": by_tag, "by_layer": by_layer,
            "unattributed_s": unattributed, "idle_gaps": gaps}


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
        # tag -> [latest executable dispatched, its (layer table, scope
        # table) or None]
        self._programs: Dict[str, list] = {}
        self._layer_s: Dict[str, float] = {}
        self._unattributed_s = 0.0
        self._idle_gaps: List[Tuple[str, float]] = []
        self._wall_s = 0.0
        self._t0: Optional[float] = None
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

    def note_program(self, tag: str, compiled) -> None:
        """Called when a train-phase tag has acquired a program: keep
        the executable that ran (one reference; it holds no closure, so
        it may outlive its Booster) for ``layer_table``."""
        self._programs[tag] = [compiled, None]

    def _tables(self, tag: str):
        held = self._programs.get(tag)
        if held is None:
            return None
        if held[1] is None:
            try:
                held[1] = parse_scope_tables(held[0].as_text())
            except Exception:
                return None
        return held[1]

    def layer_table(self, tag: str) -> Optional[Dict[str, str]]:
        """{``instruction_head``: layer} of the executable last
        acquired under `tag` (see ``parse_scope_tables``); None when
        the tag has run no program or its text cannot be had."""
        tables = self._tables(tag)
        return None if tables is None else tables[0]

    def scope_table(self, tag: str) -> Optional[Dict[str, str]]:
        """{``instruction_head``: scope below the layer} of the same
        executable (``hist/w03``, ``split/apply/w00``, ...; see
        ``scope_of``), read in the same pass as ``layer_table``."""
        tables = self._tables(tag)
        return None if tables is None else tables[1]

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
        micro-rerun the registered programs, drop the retained
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
            self._layer_s.clear()
            self._unattributed_s = 0.0
            self._idle_gaps = []
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
        """Re-time each retained program best-of-`reps` with
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
        path = find_xplane(log_dir) if log_dir else None
        if path is None:
            return
        from jax.profiler import ProfileData
        with self._lock:
            mapping = dict(self._name_to_tag)
            tags = list(self._programs)
        tables = {t: self.layer_table(t) for t in tags}
        got = attribute_xspace(ProfileData.from_file(path), mapping,
                               {t: tb for t, tb in tables.items() if tb})
        with self._lock:
            for tag, secs in got["by_tag"].items():
                self._profiler_s[tag] = self._profiler_s.get(tag, 0.0) + secs
            for layer, secs in got["by_layer"].items():
                self._layer_s[layer] = self._layer_s.get(layer, 0.0) + secs
            self._unattributed_s += got["unattributed_s"]
            self._idle_gaps = sorted(self._idle_gaps + got["idle_gaps"],
                                     key=lambda g: -g[1])[:10]

    # -- reporting ------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """Attribution snapshot; live-readable while capturing.
        ``device_seconds_by_tag`` prefers profiler-measured seconds per
        tag, falling back to the sync-timed dispatches;
        ``device_seconds_by_layer`` exists only after a profiler window
        whose trace held device ops."""
        with self._lock:
            fallback = dict(self._fallback_s)
            profiler = dict(self._profiler_s)
            calls = dict(self._calls)
            phase = dict(self._phase)
            rerun = dict(self._rerun_s)
            layers = dict(self._layer_s)
            unattributed = self._unattributed_s
            gaps = list(self._idle_gaps)
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
        if layers or unattributed:
            # profiler windows only: self seconds of the device's ops by
            # lgbm/<layer> scope, what no scope claims, and the longest
            # stretches with no device op beside the host span over them
            out["device_seconds_by_layer"] = {
                name: round(s, 6) for name, s in sorted(layers.items())}
            out["unattributed_s"] = round(unattributed, 6)
            out["idle_gaps"] = [{"span": span, "seconds": round(s, 6)}
                                for span, s in gaps]
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
        """The sync-timed dispatches (on the spans' own clock) as Chrome
        trace events on their own pid — metadata first (check_trace.py requires a process_name
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


def layer_table(tag: str) -> Optional[Dict[str, str]]:
    """``global_profile.layer_table``: what the benchmark's per-layer
    readers join a device trace with."""
    return global_profile.layer_table(tag)


def scope_table(tag: str) -> Optional[Dict[str, str]]:
    """``global_profile.scope_table``: the scope of each instruction
    below its layer, for readers that tell a layer's passes apart."""
    return global_profile.scope_table(tag)
