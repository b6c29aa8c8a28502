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

- **Disabled (default):** the wrapper forwards to the jitted callable
  after a single attribute check — the dispatch path, cache behavior
  and cost are exactly the uninstrumented ones.
- **Enabled:** calls route through an explicit AOT cache keyed by the
  abstract signature (treedef + leaf shape/dtype): a miss runs
  ``jitted.lower(...).compile()`` with the compile wall-clock timed,
  records the executable's cost/memory analysis into the global
  introspector, and every hit invokes the compiled executable
  directly. The compile is therefore measured exactly once per
  (tag, shape bucket) — it IS the program's real compile, not a
  duplicate — and tracing still runs through ``wrap_traced``, so the
  existing recompile counters keep counting.

Any lower/compile/AOT-call failure permanently falls the tag back to
the plain jitted path (recorded in ``aot_fallbacks``): introspection
must never take training down.

Enabled via ``LGBM_TPU_XLA_INTROSPECT=1``, ``global_xla.enable()``, or
implicitly with the metrics registry (``LGBM_TPU_TELEMETRY`` / the
telemetry callbacks).
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


def _on_monitoring_event(event: str, **kwargs) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache_hit_count[0] += 1


def _install_cache_hit_listener() -> bool:
    """Count persistent-compile-cache hits via jax.monitoring so a
    compile that was really a disk-cache LOAD can be attributed as one
    (``cache_load_s`` vs ``compile_s`` — the split bench.py --coldstart
    and perf-gate check 10 are built on). Best-effort: a jax without
    the event just leaves every compile counted as a compile."""
    try:
        import jax.monitoring as monitoring
        monitoring.register_event_listener(_on_monitoring_event)
        return True
    except Exception:
        return False


_install_cache_hit_listener()


def cache_hits() -> int:
    """Persistent-compile-cache hits observed in this process so far."""
    return _cache_hit_count[0]


def _sig_key(args, kwargs):
    """Hashable abstract signature of a call: pytree structure plus
    per-leaf (shape, dtype). Two calls with equal keys compile to the
    same program, so the key doubles as the shape-bucket identity."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = tuple(
        (tuple(getattr(x, "shape", ()) or ()),
         str(getattr(x, "dtype", type(x).__name__)))
        for x in leaves)
    return treedef, sig


def _shape_label(sig_key) -> str:
    """Compact human label for a shape bucket: the distinct non-scalar
    leaf shapes, largest first (enough to tell row buckets apart)."""
    shapes = sorted({s for s, _ in sig_key[1] if s},
                    key=lambda s: -int(__import__("math").prod(s)))
    return ",".join("x".join(map(str, s)) for s in shapes[:4]) or "scalar"


class XlaIntrospector:
    """Global registry of compiled-program facts (see module docstring).

    ``records()`` returns one dict per compiled executable:
    ``{tag, phase, shapes, compile_s, flops?, bytes_accessed?,
    argument_bytes?, output_bytes?, temp_bytes?}``. ``summary()``
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
        """Record one real compile of `tag` (the lowlat AOT path calls
        this directly — it already owns its lower/compile).

        `compile_s` is the BACKEND compile wall time (the
        ``lowered.compile()`` step); `trace_s` is the trace/lower time
        that precedes it (pure Python+jaxpr work no cache can skip).
        `cache_hit` marks a "compile" the persistent compilation cache
        actually served from disk — its wall time is attributed to
        ``cache_load_s_total`` instead of ``compile_s_total``, because a
        warm process LOADS, it does not compile. The split is what
        makes warm start measurable: a cache-warm rerun shows
        compile_s_total ~ 0 while trace/load totals stay honest."""
        rec: Dict[str, Any] = {"tag": tag, "phase": phase or tag,
                               "shapes": sig_label,
                               "compile_s": float(compile_s),
                               "trace_s": float(trace_s)}
        if cache_hit:
            rec["cache_hit"] = True
        rec.update(executable_cost(compiled))
        with self._lock:
            self._records.append(rec)
        # always-current through obs meta, so bench.py and the
        # OpenMetrics exporter read one place (compiles are rare —
        # re-summarizing per compile is noise-free); only the global
        # introspector publishes — test-local registries must not
        # overwrite the run's meta
        if self is globals().get("global_xla"):
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
            hit = bool(r.get("cache_hit"))
            if hit:
                load_total += r["compile_s"]
                n_hits += 1
            else:
                total += r["compile_s"]
            trace_total += r.get("trace_s", 0.0)
            by_phase[r["phase"]] = by_phase.get(r["phase"], 0) + 1
            t = by_tag.setdefault(r["tag"], {
                "programs": 0, "compile_s": 0.0})
            t["programs"] += 1
            if hit:
                t["cache_load_s"] = round(t.get("cache_load_s", 0.0)
                                          + r["compile_s"], 4)
            else:
                t["compile_s"] = round(t["compile_s"] + r["compile_s"], 4)
            if r.get("trace_s"):
                t["trace_s"] = round(t.get("trace_s", 0.0)
                                     + r["trace_s"], 4)
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
    """``jax.jit(wrap_traced(tag, fn))`` plus, when the introspector is
    enabled, per-shape-bucket AOT routing that captures compile time and
    cost analysis. Drop-in for the existing program-boundary jits
    (grower, fused iteration, predict traversal)."""
    import jax
    from ..compile_cache import donation_allowed
    from .health import global_health
    from .profile import global_profile
    reg = registry if registry is not None else global_xla
    # device-time attribution (obs/profile.py): the jitted function name
    # is what the profiler trace shows, so map it back to the obs tag
    global_profile.register_tag(tag, phase, getattr(fn, "__name__", tag))
    if not donation_allowed():
        # one policy (compile_cache.donation_allowed): donation is a
        # memory optimisation only, LGBM_TPU_NO_DONATE drops it
        jit_kwargs.pop("donate_argnums", None)
    jitted = jax.jit(global_metrics.wrap_traced(tag, fn), **jit_kwargs)
    compiled_cache: Dict[Any, Any] = {}
    broken: List[str] = []  # non-empty => this tag fell back for good

    def _dispatch(*args, **kwargs):
        if not reg.enabled or broken:
            return jitted(*args, **kwargs)
        try:
            key = _sig_key(args, kwargs)
        except Exception as exc:  # unhashable pytree — don't retry
            broken.append(repr(exc))
            reg.note_fallback(tag, repr(exc))
            return jitted(*args, **kwargs)
        entry = compiled_cache.get(key)
        if entry is None:
            try:
                t0 = time.perf_counter()
                lowered = jitted.lower(*args, **kwargs)
                t1 = time.perf_counter()
                hits0 = _cache_hit_count[0]
                entry = lowered.compile()
                dt_compile = time.perf_counter() - t1
            except Exception as exc:
                broken.append(repr(exc))
                reg.note_fallback(tag, repr(exc))
                return jitted(*args, **kwargs)
            compiled_cache[key] = entry
            reg.note_compile(tag, phase, _shape_label(key), dt_compile,
                             entry, trace_s=t1 - t0,
                             cache_hit=_cache_hit_count[0] > hits0)
        if global_profile.capturing:
            # retain (executable, latest args) for the window-close
            # block_until_ready micro-reruns; dropped at stop_window
            global_profile.register_entry(tag, phase, entry, args, kwargs)
        try:
            return entry(*args, **kwargs)
        except Exception as exc:
            broken.append(repr(exc))
            reg.note_fallback(tag, repr(exc))
            return jitted(*args, **kwargs)

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
