"""Span tracer: nested named spans with self-time attribution.

The structured successor of the flat wall-clock ``timer.Timer``
(ref: Common::Timer / FunctionTimer, include/LightGBM/utils/common.h:
980,1044). Spans nest via a real stack, so a parent's *self* time —
total minus time spent inside child spans — is attributable, the way
the reference's ``FunctionTimer`` frames nest inside each other.

jax device work is asynchronous: a span that must charge dispatched
device work to itself passes ``block=`` a pytree of jax arrays (or a
zero-arg callable returning one) which is waited on before the clock
stops.

Export formats:
- ``summary()``   — aggregated {name: {seconds, self_seconds, count}}.
- ``export_chrome(path)`` — Chrome trace-event JSON (load in
  chrome://tracing or Perfetto); validated by ``tools/check_trace.py``.

Enabling:
- ``LGBM_TPU_TRACE=/path.json`` in the environment (or the
  ``trace_output`` train param) enables the tracer and writes the
  Chrome trace at interpreter exit.
- ``LGBM_TPU_TIMETAG=1`` (or ``enable()``) prints the aggregated
  summary at exit, exactly like the reference's atexit dump.

While a ``jax.profiler`` session is live (an operator's, the
``tpu_profile`` window's, a benchmark harness's), every span also enters
a ``jax.profiler.TraceAnnotation("lgbm/" + name)``, tracer enabled or
not: the profiler's ``.xplane.pb`` then holds the program's host spans,
nested as here, on the clock of the device ops.

When disabled and no profiler session is live, ``span()`` returns a
shared no-op context manager — no allocation, one attribute check and
one static call.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """Shared do-nothing context manager for the disabled fast path."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

ANNOTATION_PREFIX = "lgbm/"  # the spans' names in a profiler trace
_annotation_cls = None


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use (this
    module stays importable without touching jax)."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls


class _SpanFrame:
    """One live span (context manager); exists only while enabled."""
    __slots__ = ("tracer", "name", "block", "args", "t0", "child_ns",
                 "annotation")

    def __init__(self, tracer: "Tracer", name: str, block,
                 args=None, annotation=None) -> None:
        self.tracer = tracer
        self.name = name
        self.block = block
        self.args = args
        self.child_ns = 0
        self.annotation = annotation

    def set_metadata(self, **kw) -> None:
        """Attach facts known only inside the span (``TraceAnnotation``'s
        own method name, so a caller need not know which it holds)."""
        self.args = dict(self.args or {}, **kw)
        if self.annotation is not None:
            self.annotation.set_metadata(**kw)

    def __enter__(self) -> "_SpanFrame":
        if self.annotation is not None:
            self.annotation.__enter__()
        self.tracer._stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> bool:
        if self.block is not None and exc_type is None:
            # skip the device wait when the body raised (the timing is
            # garbage then, and block= lambdas commonly reference names
            # bound inside the span body); never let telemetry mask the
            # user's exception
            try:
                import jax
                b = self.block
                jax.block_until_ready(b() if callable(b) else b)
            except Exception:
                pass
        t1 = time.perf_counter_ns()
        tracer = self.tracer
        stack = tracer._stack
        # tolerate a mispaired exit (exception unwound past frames)
        while stack and stack[-1] is not self:
            stack.pop()
        if stack:
            stack.pop()
        dur = t1 - self.t0
        if stack:
            stack[-1].child_ns += dur
        tracer._record(self.name, self.t0, dur, dur - self.child_ns,
                       len(stack), self.args)
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc_val, exc_tb)
        return False


class Tracer:
    """Nested named spans, aggregation, and Chrome trace export."""

    # raw-event cap: aggregation (summary/report) is unbounded either
    # way; past the cap only the per-span Chrome events stop growing
    # (~50 B each -> ~50 MB ceiling), with the drop count reported in
    # the export. Keeps week-long LGBM_TPU_TIMETAG runs flat in memory.
    MAX_EVENTS = 1_000_000

    def __init__(self) -> None:
        self.enabled = False
        self.print_summary_at_exit = False
        self.trace_path: Optional[str] = None
        self._tls = threading.local()  # per-thread span stack
        self._lock = threading.Lock()  # guards _events/_agg/sinks
        self._dropped_events = 0
        # completed spans:
        # (name, start_ns, dur_ns, self_ns, depth, tid, args-or-None)
        self._events: List[tuple] = []
        self._thread_names: Dict[int, str] = {}  # tid -> thread name
        self._agg: Dict[str, List[float]] = {}  # name -> [total, self, count]
        self._sinks: List[Any] = []  # callables(name, dur_s, self_s)
        self._exported = False
        self._printed = False

        env_path = os.environ.get("LGBM_TPU_TRACE", "")
        if env_path:
            self.enable(path=env_path)
        if os.environ.get("LGBM_TPU_TIMETAG", "") not in ("", "0"):
            self.enable(print_at_exit=True)

    @property
    def _stack(self) -> List["_SpanFrame"]:
        """This thread's open-span stack — spans on one thread must never
        pop frames opened by another (e.g. a predict worker thread while
        the main thread trains)."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    # ------------------------------------------------------------------
    def enable(self, path: Optional[str] = None,
               print_at_exit: bool = False) -> None:
        self.enabled = True
        if path:
            self.trace_path = path
        if print_at_exit:
            self.print_summary_at_exit = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        self._stack.clear()
        with self._lock:
            self._events.clear()
            self._thread_names.clear()
            self._agg.clear()
            self._dropped_events = 0
        self._exported = False
        self._printed = False

    def add_sink(self, sink) -> None:
        """Register a callable(name, dur_seconds, self_seconds) invoked on
        every completed span (the metrics registry hooks phase times here)."""
        if sink not in self._sinks:
            self._sinks.append(sink)

    # ------------------------------------------------------------------
    def span(self, name: str, block: Optional[Any] = None,
             args: Optional[Dict[str, Any]] = None):
        """Time a nested phase. `args` (a small dict) rides into the
        Chrome event's ``args`` — the request-tracing link fields
        (trace_id, batch_id, ...) travel this way. While a profiler
        session is live the span is also a ``TraceAnnotation`` in the
        profiler's own trace; with the tracer disabled it is only that
        (and ``block`` is not waited on: the annotation then shows what
        the host really did). Disabled and no session: the shared no-op
        context manager, no allocation."""
        cls = _annotation()
        annotation = (cls(ANNOTATION_PREFIX + name) if cls.is_enabled()
                      else None)
        if not self.enabled:
            return _NULL_SPAN if annotation is None else annotation
        return _SpanFrame(self, name, block, args, annotation)

    def add_complete_span(self, name: str, start_ns: int, dur_ns: int,
                          args: Optional[Dict[str, Any]] = None,
                          tid: Optional[int] = None) -> None:
        """Record an already-timed span retroactively (the serve path
        emits per-request and per-batch attribution spans after the
        fact, once queue-wait and device time are known). Does not
        touch the live span stack and does not fire sinks — these are
        attribution records, not training phases."""
        if not self.enabled:
            return
        self._record(name, int(start_ns), int(dur_ns), int(dur_ns), 0,
                     args, tid=tid, fire_sinks=False)

    def _record(self, name: str, start_ns: int, dur_ns: int, self_ns: int,
                depth: int, args: Optional[Dict[str, Any]] = None,
                tid: Optional[int] = None, fire_sinks: bool = True) -> None:
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            if tid not in self._thread_names:
                # for the thread_name metadata events in chrome_events
                self._thread_names[tid] = threading.current_thread().name
            if len(self._events) < self.MAX_EVENTS:
                self._events.append((name, start_ns, dur_ns, self_ns,
                                     depth, tid, args))
            else:
                self._dropped_events += 1
            agg = self._agg.get(name)
            if agg is None:
                agg = self._agg[name] = [0.0, 0.0, 0]
            agg[0] += dur_ns * 1e-9
            agg[1] += self_ns * 1e-9
            agg[2] += 1
        if fire_sinks:
            for sink in self._sinks:
                sink(name, dur_ns * 1e-9, self_ns * 1e-9)

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, Dict[str, float]]:
        """Aggregated per-phase totals, reference-dump shaped."""
        with self._lock:
            items = [(n, list(a)) for n, a in self._agg.items()]
        return {name: {"seconds": agg[0], "self_seconds": agg[1],
                       "count": agg[2]}
                for name, agg in sorted(items)}

    def report(self) -> str:
        s = self.summary()
        lines = ["LightGBM-TPU phase timers:"]
        for name in sorted(s, key=lambda n: s[n]["seconds"], reverse=True):
            lines.append(f"  {name:32s} {s[name]['seconds']:10.3f}s "
                         f"(self {s[name]['self_seconds']:8.3f}s) "
                         f"x{int(s[name]['count'])}")
        return "\n".join(lines)

    def _metadata_events(self, pid: int, tids,
                         thread_names: Dict[int, str]) -> List[Dict[str, Any]]:
        """Chrome ``ph: "M"`` metadata: process_name / process_labels
        (host + shard identity from hostenv / the metrics meta) and a
        thread_name per recorded thread — without these, multi-thread
        and multi-process traces are anonymous pid/tid soup in
        Perfetto."""
        from ..hostenv import host_labels
        labels = host_labels()
        proc = "lightgbm_tpu"
        if "process_index" in labels:
            proc += (f" host{labels['process_index']}"
                     f"/{labels.get('num_processes', '?')}")
        try:  # shard labels (set by parallel learner setup)
            from .metrics import global_metrics
            mesh = global_metrics.meta.get("mesh_size")
            if mesh:
                labels["mesh_size"] = str(mesh)
            learner = global_metrics.meta.get("tree_learner")
            if learner:
                labels["tree_learner"] = str(learner)
        except Exception:
            pass
        events = [
            {"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": proc}},
            {"name": "process_labels", "ph": "M", "pid": pid,
             "args": {"labels": ",".join(
                 f"{k}={v}" for k, v in sorted(labels.items()))}},
        ]
        for tid in sorted(tids):
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": thread_names.get(tid, f"thread-{tid}")}})
        return events

    def chrome_events(self) -> List[Dict[str, Any]]:
        """Completed spans as Chrome trace-event dicts (phase "X",
        microsecond timestamps), sorted by start time — prefixed with
        the ``ph: "M"`` process/thread metadata events. Device slices
        captured by obs/profile.py ride along on their own pid so host
        spans and device programs render on one Perfetto timeline."""
        pid = os.getpid()
        with self._lock:
            snapshot = list(self._events)
            names = dict(self._thread_names)
        events = self._metadata_events(pid, {e[5] for e in snapshot}
                                       | set(names), names)
        for name, start_ns, dur_ns, self_ns, depth, tid, extra in sorted(
                snapshot, key=lambda e: e[1]):
            args: Dict[str, Any] = {"self_us": self_ns / 1000.0,
                                    "depth": depth}
            if extra:
                args.update(extra)
            events.append({
                "name": name,
                "ph": "X",
                "ts": start_ns / 1000.0,
                "dur": dur_ns / 1000.0,
                "pid": pid,
                "tid": tid,
                "args": args,
            })
        try:
            # device lane: the sync-timed dispatches, on the spans' own
            # perf_counter_ns clock
            from .profile import global_profile
            events.extend(global_profile.device_lane_events(pid + 1))
        except Exception:
            pass  # the host trace must export even if the lane cannot
        return events

    def export_chrome(self, path: str) -> None:
        doc = {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
            "otherData": {"producer": "lightgbm_tpu.obs.trace",
                          "dropped_events": self._dropped_events},
        }
        with open(path, "w") as fh:
            json.dump(doc, fh)

    def print_summary_once(self) -> None:
        """Print the aggregated report (at most once) — the USE_TIMETAG
        dump. Does NOT export the trace file; that stays an exit-time
        (or explicit export_chrome) action so a mid-run summary print
        cannot truncate the trace."""
        if self.print_summary_at_exit and self._agg and not self._printed:
            self._printed = True
            print(self.report(), flush=True)

    # ------------------------------------------------------------------
    def _at_exit(self) -> None:
        if self.trace_path and self._events and not self._exported:
            self._exported = True
            try:
                self.export_chrome(self.trace_path)
            except OSError as exc:
                print(f"[LightGBM-TPU] trace export to "
                      f"{self.trace_path} failed: {exc}", flush=True)
        self.print_summary_once()


global_tracer = Tracer()
atexit.register(global_tracer._at_exit)
