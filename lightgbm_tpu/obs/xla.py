"""XLA program introspection: per-executable cost analysis, compile
wall-time, and recompile attribution.

PR 4/5 built *analytic* traffic and memory models (trace-time shape
arithmetic); this module captures what XLA itself says about the
programs it actually compiled — ``compiled.cost_analysis()`` (flops,
bytes accessed) and ``compiled.memory_analysis()`` (argument / output /
temp bytes) — so the analytic models can be cross-validated without
silicon (tools/check_perf_gate.py's XLA band) and every recompile is
attributable to a phase and shape bucket instead of a bare counter.

Mechanics: ``instrumented_jit(tag, fn, phase=...)`` replaces the bare
``jax.jit(global_metrics.wrap_traced(tag, fn))`` at a program boundary.
There is ONE dispatch path, telemetry on or off: every call is
``jitted(*args)`` — jit's own C++ fast path, its own caches. What the
boundary adds around it is a thread-local frame that JAX's own
``backend_compile_duration`` event fills in when the call had to
acquire a program (compile it, or load it from the persistent cache).

- **Always** (two clock reads and a thread-local per call): the
  first-dispatch counters of each acquisition — ``trace_lower_s`` (the
  call's start to the start of the backend compile: Python tracing and
  lowering), ``compile_or_load_s`` (JAX's own compile duration: a
  compile, or the persistent cache's load), ``cache_hit`` — and the
  same two intervals as retroactive spans ``<phase>/trace_lower`` and
  ``<phase>/compile_or_load`` (``train/`` for the training programs)
  while the span tracer is on.
- **Train-phase tags, or telemetry on**: after an acquisition the
  ``jax.stages.Compiled`` of the program that just ran is taken with
  ``jitted.lower(*args).compile()``, which jit's caches serve (the
  same arguments give the same cached lowering and its executable:
  nothing is traced, lowered, compiled or loaded twice). Train-phase
  programs hand it to ``obs/profile.py`` for ``layer_table(tag)``;
  telemetry on reads its cost/memory analysis into the record.

The boundary catches nothing around the call: a trace, compile or
execution error reaches the caller as jit raised it. Only a failure to
take the ``Compiled`` afterwards is swallowed (recorded in
``aot_fallbacks``; the tag then has no layer table and no cost).

Cost analysis is enabled via ``LGBM_TPU_XLA_INTROSPECT=1``,
``global_xla.enable()``, or implicitly with the metrics registry
(``LGBM_TPU_TELEMETRY`` / the telemetry callbacks).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .metrics import global_metrics


def executable_cost(compiled) -> Dict[str, float]:
    """Cost/memory facts of a compiled XLA executable, normalized.

    Returns whichever of ``flops`` / ``bytes_accessed`` (HLO cost
    analysis) and ``argument_bytes`` / ``output_bytes`` / ``temp_bytes``
    (buffer assignment) this backend exposes — an empty dict when it
    exposes neither (the perf-gate band then skips gracefully)."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if isinstance(ca.get("flops"), (int, float)):
                out["flops"] = float(ca["flops"])
            if isinstance(ca.get("bytes accessed"), (int, float)):
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for src, dst in (("argument_size_in_bytes", "argument_bytes"),
                         ("output_size_in_bytes", "output_bytes"),
                         ("temp_size_in_bytes", "temp_bytes")):
            v = getattr(ma, src, None)
            if isinstance(v, (int, float)):
                out[dst] = float(v)
    except Exception:
        pass
    return out


def aot_cost_summary(fn: Callable, *args, **kwargs
                     ) -> Optional[Dict[str, float]]:
    """jit → lower → compile `fn` on the given concrete args and return
    its cost dict (``executable_cost`` + ``compile_s``), or None when
    the backend exposes no cost analysis at all — the graceful-skip
    contract check_perf_gate.py's XLA band is built on."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    dt = time.perf_counter() - t0
    cost = executable_cost(compiled)
    if not cost:
        return None
    cost["compile_s"] = dt
    return cost


_cache_hit_count = [0]  # process-wide persistent-compile-cache hits
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_tls = threading.local()  # .frame: the boundary call running on this thread


class _Acquisition:
    """What one boundary call saw of JAX's compile events. The LAST
    backend compile inside the call is the program's own (an eager op
    on concrete values inside the traced function compiles before it)."""
    __slots__ = ("t0", "hits0", "compile_end", "compile_s", "cache_hit")

    def __init__(self) -> None:
        self.compile_end = None
        self.hits0 = _cache_hit_count[0]
        self.t0 = time.perf_counter()


def _on_monitoring_event(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_hit_count[0] += 1


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    frame = getattr(_tls, "frame", None)
    if frame is None:
        return
    frame.compile_end = time.perf_counter()
    frame.compile_s = duration
    hits = _cache_hit_count[0]
    frame.cache_hit = hits > frame.hits0
    frame.hits0 = hits


def _install_listeners() -> bool:
    """Hear JAX's own compile events via jax.monitoring: the
    persistent-compile-cache hits, so a compile that was really a
    disk-cache LOAD can be attributed as one (``cache_load_s`` vs
    ``compile_s`` — the split bench.py --coldstart and perf-gate check
    10 are built on), and the backend compile's duration, which is how
    a boundary learns that its call acquired a program. Best-effort: a
    jax without the events records no acquisitions."""
    try:
        import jax.monitoring as monitoring
        monitoring.register_event_listener(_on_monitoring_event)
        monitoring.register_event_duration_secs_listener(_on_duration_event)
        return True
    except Exception:
        return False


_install_listeners()


def cache_hits() -> int:
    """Persistent-compile-cache hits observed in this process so far."""
    return _cache_hit_count[0]


def _shape_label(args, kwargs) -> str:
    """Compact human label for a call's shape bucket: the distinct
    non-scalar leaf shapes, largest first (enough to tell row buckets
    apart)."""
    import math
    import jax
    shapes = {tuple(getattr(x, "shape", ()) or ())
              for x in jax.tree_util.tree_leaves((args, kwargs))}
    ordered = sorted((s for s in shapes if s), key=lambda s: -math.prod(s))
    return ",".join("x".join(map(str, s)) for s in ordered[:4]) or "scalar"


class XlaIntrospector:
    """Global registry of compiled-program facts (see module docstring).

    ``records()`` returns one dict per compiled executable:
    ``{tag, phase, shapes, trace_lower_s, compile_or_load_s, cache_hit,
    flops?, bytes_accessed?, argument_bytes?, output_bytes?,
    temp_bytes?}``. ``summary()``
    aggregates them into the bench-JSON shape (``compile_s_total``,
    ``n_recompiles_by_phase``, per-tag totals)."""

    def __init__(self) -> None:
        self.enabled = os.environ.get(
            "LGBM_TPU_XLA_INTROSPECT", "") not in ("", "0")
        self._lock = threading.Lock()
        self._records: List[Dict[str, Any]] = []
        self._fallbacks: Dict[str, str] = {}  # tag -> first error

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._fallbacks.clear()

    def cache_hits(self) -> int:
        """Process-wide persistent-compile-cache hit count (module
        counter; here so boundary code holding the registry can diff
        it around a compile)."""
        return _cache_hit_count[0]

    # ------------------------------------------------------------------
    def note_compile(self, tag: str, phase: Optional[str], sig_label: str,
                     compile_s: float, compiled,
                     trace_s: float = 0.0,
                     cache_hit: bool = False) -> None:
        """Record one acquired program of `tag` (the lowlat AOT path
        calls this directly — it already owns its lower/compile).
        Always on; the executable's cost/memory analysis rides along
        only while the introspector is enabled (`compiled` may be None
        otherwise).

        `compile_s` is the BACKEND compile wall time; `trace_s` is the
        trace/lower time that precedes it (pure Python+jaxpr work no
        cache can skip). `cache_hit` marks a "compile" the persistent
        compilation cache actually served from disk — its wall time is
        attributed to ``cache_load_s_total`` instead of
        ``compile_s_total``, because a warm process LOADS, it does not
        compile. The split is what makes warm start measurable: a
        cache-warm rerun shows compile_s_total ~ 0 while trace/load
        totals stay honest.

        The record holds them as the first-dispatch counters
        ``trace_lower_s``, ``compile_or_load_s`` and ``cache_hit``."""
        rec: Dict[str, Any] = {"tag": tag, "phase": phase or tag,
                               "shapes": sig_label,
                               "trace_lower_s": float(trace_s),
                               "compile_or_load_s": float(compile_s),
                               "cache_hit": bool(cache_hit)}
        if self.enabled and compiled is not None:
            rec.update(executable_cost(compiled))
        with self._lock:
            self._records.append(rec)
        # always-current through obs meta, so bench.py and the
        # OpenMetrics exporter read one place (compiles are rare —
        # re-summarizing per compile is noise-free); only the global
        # introspector publishes — test-local registries must not
        # overwrite the run's meta
        if self.enabled and self is globals().get("global_xla"):
            global_metrics.set_meta("xla_programs", self.summary())

    def note_fallback(self, tag: str, error: str) -> None:
        with self._lock:
            self._fallbacks.setdefault(tag, error)

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._records]

    @property
    def n_programs(self) -> int:
        return len(self._records)

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            recs = [dict(r) for r in self._records]
            fallbacks = dict(self._fallbacks)
        by_phase: Dict[str, int] = {}
        by_tag: Dict[str, Dict[str, float]] = {}
        total = 0.0
        trace_total = 0.0
        load_total = 0.0
        n_hits = 0
        for r in recs:
            hit, secs = r["cache_hit"], r["compile_or_load_s"]
            if hit:
                load_total += secs
                n_hits += 1
            else:
                total += secs
            trace_total += r["trace_lower_s"]
            by_phase[r["phase"]] = by_phase.get(r["phase"], 0) + 1
            t = by_tag.setdefault(r["tag"], {
                "programs": 0, "compile_s": 0.0})
            t["programs"] += 1
            if hit:
                t["cache_load_s"] = round(t.get("cache_load_s", 0.0)
                                          + secs, 4)
            else:
                t["compile_s"] = round(t["compile_s"] + secs, 4)
            if r["trace_lower_s"]:
                t["trace_s"] = round(t.get("trace_s", 0.0)
                                     + r["trace_lower_s"], 4)
            for k in ("flops", "bytes_accessed"):
                if k in r:
                    t[k] = t.get(k, 0.0) + r[k]
        out: Dict[str, Any] = {
            "compile_s_total": round(total, 4),
            "trace_s_total": round(trace_total, 4),
            "cache_load_s_total": round(load_total, 4),
            "n_cache_hits": n_hits,
            "n_programs": len(recs),
            "n_recompiles_by_phase": by_phase,
            "by_tag": by_tag,
        }
        if fallbacks:
            out["aot_fallbacks"] = fallbacks
        return out


global_xla = XlaIntrospector()

# env-enabled telemetry (LGBM_TPU_TELEMETRY) arms the introspector too,
# matching obs/memory.py's watermark hook — metrics.enable() only runs
# for the programmatic path
if global_metrics.enabled:
    global_xla.enable()


def _persistent_cache_active() -> bool:
    """True when the XLA persistent compilation cache is configured.
    Thin delegate kept for callers/tests; the policy itself lives in
    ``compile_cache`` now (one module for every program boundary)."""
    from ..compile_cache import cache_active
    return cache_active()


def instrumented_jit(tag: str, fn: Callable, phase: Optional[str] = None,
                     registry: Optional[XlaIntrospector] = None,
                     **jit_kwargs) -> Callable:
    """``jax.jit(wrap_traced(tag, fn))`` whose calls leave the
    first-dispatch counters of every program they acquire (see module
    docstring); the layer table's executable for train-phase tags, cost
    analysis with telemetry on. Drop-in for the program-boundary jits
    (grower, fused iteration, predict traversal)."""
    import jax
    from ..compile_cache import donation_allowed
    from .health import global_health
    from .profile import global_profile
    from .trace import global_tracer
    reg = registry if registry is not None else global_xla
    # device-time attribution (obs/profile.py): the jitted function name
    # is what the profiler trace shows, so map it back to the obs tag
    global_profile.register_tag(tag, phase, getattr(fn, "__name__", tag))
    if not donation_allowed():
        # one policy (compile_cache.donation_allowed): donation is a
        # memory optimisation only, LGBM_TPU_NO_DONATE drops it
        jit_kwargs.pop("donate_argnums", None)
    jitted = jax.jit(global_metrics.wrap_traced(tag, fn), **jit_kwargs)
    # the layer table (obs/profile.layer_table) is for the training
    # programs; a serving executable is not pinned past its model
    keep_program = phase in ("train", "grow")
    span_root = "train" if keep_program else (phase or "xla")

    def _note_acquired(frame, args, kwargs):
        """The call just made compiled or loaded a program: leave its
        counters and spans, and take its Compiled where one is read."""
        compile_start = frame.compile_end - frame.compile_s
        if global_tracer.enabled:
            # perf_counter and the tracer's perf_counter_ns are one clock
            global_tracer.add_complete_span(
                span_root + "/trace_lower", int(frame.t0 * 1e9),
                int((compile_start - frame.t0) * 1e9), args={"tag": tag})
            global_tracer.add_complete_span(
                span_root + "/compile_or_load", int(compile_start * 1e9),
                int(frame.compile_s * 1e9),
                args={"tag": tag, "cache_hit": frame.cache_hit})
        compiled = None
        if keep_program or reg.enabled:
            try:
                # donated arguments are deleted by now; their avals and
                # shardings are all the cached lowering is looked up by
                compiled = jitted.lower(*args, **kwargs).compile()
            except Exception as exc:
                reg.note_fallback(tag, repr(exc))
        if keep_program and compiled is not None:
            global_profile.note_program(tag, compiled)
        reg.note_compile(tag, phase, _shape_label(args, kwargs),
                         frame.compile_s, compiled,
                         trace_s=compile_start - frame.t0,
                         cache_hit=frame.cache_hit)

    def _dispatch(*args, **kwargs):
        if reg.enabled and global_profile.capturing:
            # retain (program, latest args) for the window-close
            # block_until_ready micro-reruns; dropped at stop_window
            global_profile.register_entry(tag, phase, jitted, args, kwargs)
        outer = getattr(_tls, "frame", None)
        frame = _tls.frame = _Acquisition()
        try:
            out = jitted(*args, **kwargs)
        finally:
            _tls.frame = outer
        if frame.compile_end is not None:
            _note_acquired(frame, args, kwargs)
        return out

    def wrapper(*args, **kwargs):
        try:
            if global_profile.capturing:
                # open profile window: sync-timed dispatch attributes
                # this call's device time to the tag (values unchanged)
                return global_profile.timed_call(tag, phase, _dispatch,
                                                 args, kwargs)
            return _dispatch(*args, **kwargs)
        finally:
            # runtime collective attribution (obs/health.py): AFTER the
            # dispatch, so a first call's trace has already captured
            # this program's collective manifest. One attribute check
            # when health is disabled.
            if global_health.enabled:
                global_health.note_program_call(tag)

    wrapper.__name__ = getattr(fn, "__name__", tag)
    wrapper.__wrapped_jit__ = jitted  # escape hatch / tests
    wrapper.lower = jitted.lower  # AOT-shaped callers (tests) keep working
    return wrapper
