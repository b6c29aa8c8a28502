"""Training-health observability: collective timing & straggler
attribution, cross-shard drift sentinels, and model-quality diagnostics.

The third always-on-capable obs pillar, alongside ``obs/memory.py``
(capacity) and ``obs/xla.py`` (compiled-program facts). Those two
explain where bytes and compile time go; this module answers whether
training is *healthy* — the detection layer ROADMAP item 5's elastic
fault tolerance needs before it can react to anything. Three parts:

1. **Collective accounting & timing** — the psum/all_gather call sites
   in ``learner.py`` and ``parallel/{voting,feature_parallel}.py`` go
   through the :func:`psum` / :func:`all_gather` wrappers here. Each
   wrapper keeps the PR-1 trace-time counters alive AND registers the
   site (tag, op, payload bytes, loop trip count) into the *manifest*
   of the program being traced; every runtime invocation of an
   instrumented program (``obs/xla.instrumented_jit``) then multiplies
   its manifest into per-tag runtime counters — so steady-state
   iterations report the collectives actually issued, not the zero the
   trace-time-only counters showed after the first compile.
   :meth:`HealthRegistry.probe_collectives` adds device-synchronized
   wall time: a timed psum + all_gather microprobe over the real mesh,
   giving a measured seconds-per-byte rate per op (the first
   driver-visible view of ICI behavior in the multichip dryrun).

2. **Cross-shard drift sentinels** — :meth:`HealthRegistry.check_drift`
   digests replicated device state per shard inside ``shard_map``
   (sum / sum-of-squares / abs-sum / nonfinite-count per array, NaNs
   zeroed so identical-NaN state still matches) and ``all_gather``\\ s
   the digests across the mesh; any shard whose digest differs from
   the majority is a silently-diverged replica. Under the
   ``tpu_health`` knob: ``warn`` records + logs the mismatch,
   ``error`` raises a structured :class:`DriftError` — converting
   ROADMAP item 4's silent parity failures into an alarm.

3. **Model-quality diagnostics** — per-iteration NaN/Inf sentinel
   counts (``isfinite`` reductions folded into the fused training
   programs by ``boosting.py`` — the fused path stays fused),
   host-side straggler skew over per-phase timings (allgathered
   across processes every check period: max/median per phase plus the
   worst-shard ordinal), and an eval-loss anomaly detector
   (spike / NaN / plateau flags fed from ``engine.train``).

Everything flows through :meth:`HealthRegistry.summary` → bench.py's
JSON line / the multichip dryrun's ``MULTICHIP-HEALTH`` line →
``obs/export.render_openmetrics`` (``lgbmtpu_health_*`` families,
validated by ``tools/check_health.py``) → Chrome trace spans
(``health/drift_check`` etc. when the tracer runs).

Disabled cost: with the registry off and ``tpu_health=off`` every
hot-path entry (``note_program_call``, the boosting hooks) is a single
attribute check; manifests are captured at trace time only (compile
cost, never per iteration) and the trained model is bit-identical with
health on or off (asserted by tests/test_health.py).

Enabled via ``LGBM_TPU_HEALTH=1``, ``global_health.enable()``, or
implicitly with the metrics registry (``LGBM_TPU_TELEMETRY`` / the
telemetry callbacks); the ``tpu_health=off/warn/error`` knob arms the
per-booster drift/NaN alarms independently of full telemetry.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .metrics import global_metrics


class HealthError(RuntimeError):
    """Base class of the structured training-health alarms."""


class DriftError(HealthError):
    """Replicated state diverged across mesh shards (tpu_health=error)."""


class NonFiniteError(HealthError):
    """NaN/Inf gradients, hessians or scores detected (tpu_health=error)."""


# eval-anomaly detector tuning: a point is a "spike" when it is worse
# than the rolling median by this fraction of the median's magnitude;
# a "plateau" when the best improvement over the window is below the
# absolute epsilon for a full window
_EVAL_WINDOW = 8
_EVAL_SPIKE_FRAC = 0.5
_EVAL_PLATEAU_EPS = 1e-9


def _tree_bytes(tree) -> int:
    """Total payload bytes of a pytree of (traced or concrete) arrays."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            continue
        total += int(size) * int(np.dtype(dtype).itemsize)
    return total


def tree_depths(split_leaf: np.ndarray) -> np.ndarray:
    """Depth of every leaf of a grown tree from its ``split_leaf``
    record (creation order: split s splits leaf ``split_leaf[s]``, the
    right child becomes leaf s+1 — learner.TreeArrays numbering).
    Returns the per-leaf depth array (root-only tree -> [0])."""
    split_leaf = np.asarray(split_leaf).reshape(-1)
    n_leaves = int(np.sum(split_leaf >= 0)) + 1
    depth = np.zeros(max(n_leaves, 1), np.int32)
    nxt = 1
    for s in range(split_leaf.shape[0]):
        leaf = int(split_leaf[s])
        if leaf < 0:
            continue
        d = depth[leaf] + 1
        depth[leaf] = d
        depth[nxt] = d
        nxt += 1
    return depth[:max(n_leaves, 1)]


class HealthRegistry:
    """Global training-health state (see module docstring)."""

    def __init__(self) -> None:
        self.enabled = os.environ.get(
            "LGBM_TPU_HEALTH", "") not in ("", "0")
        self._lock = threading.Lock()
        # --- collective accounting
        # program tag -> tuple of (site_tag, op, nbytes, loop_factor)
        self._manifests: Dict[str, Tuple[Tuple[str, str, int, int], ...]] = {}
        self._trace_stack: List[List[Tuple[str, str, int, int]]] = []
        # site tag -> {"op", "calls", "bytes"} — RUNTIME-attributed
        self.runtime: Dict[str, Dict[str, Any]] = {}
        self.program_calls: Dict[str, int] = {}
        # op -> {"seconds", "bytes", "count"} from the timed microprobe
        self.probe: Dict[str, Dict[str, float]] = {}
        # --- straggler
        self.straggler: Optional[Dict[str, Any]] = None
        self._straggler_base: Dict[str, float] = {}
        # --- drift
        self.drift_checks = 0
        self.drift_mismatches = 0
        self.last_drift: Optional[Dict[str, Any]] = None
        self._digest_cache: Dict[Any, Any] = {}
        # --- NaN/Inf sentinel
        self.nonfinite: Dict[str, int] = {}
        self.nonfinite_iterations = 0
        self.last_nonfinite: Optional[Dict[str, Any]] = None
        # --- eval anomaly detector
        self._eval_hist: Dict[str, List[float]] = {}
        self.eval_anomalies: Dict[str, int] = {}
        self.last_eval_anomaly: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._manifests.clear()
            self._trace_stack.clear()
            self.runtime.clear()
            self.program_calls.clear()
            self.probe.clear()
        self.straggler = None
        self._straggler_base = {}
        self.drift_checks = 0
        self.drift_mismatches = 0
        self.last_drift = None
        self.nonfinite = {}
        self.nonfinite_iterations = 0
        self.last_nonfinite = None
        self._eval_hist.clear()
        self.eval_anomalies = {}
        self.last_eval_anomaly = None

    # ------------------------------------------------------------------
    # collective manifests (trace time) + runtime attribution (per call)
    def begin_program_trace(self, tag: str) -> None:
        """Open a manifest-capture frame: collective wrappers traced
        under this program body register into it. Trace-time only."""
        with self._lock:
            self._trace_stack.append([])

    def end_program_trace(self, tag: str) -> None:
        with self._lock:
            if not self._trace_stack:
                return
            sites = self._trace_stack.pop()
            # nested program traces (rare) attribute to the inner tag;
            # re-traces for new shapes replace the manifest wholesale
            self._manifests[tag] = tuple(sites)

    def register_site(self, site_tag: str, op: str, nbytes: int,
                      loop_factor: int = 1) -> None:
        """Record one traced collective call site into the open
        manifest (no-op outside a program trace). ``loop_factor`` is
        the static trip count when the site sits inside a ``lax.scan``
        body — traced once, issued `loop_factor` times per run."""
        with self._lock:
            if self._trace_stack:
                self._trace_stack[-1].append(
                    (site_tag, op, int(nbytes), max(int(loop_factor), 1)))

    def note_program_call(self, tag: str) -> None:
        """One runtime invocation of an instrumented program: multiply
        its manifest into the per-tag runtime counters. Callers guard
        on ``enabled`` — this is the per-call hot path."""
        manifest = self._manifests.get(tag)
        with self._lock:
            self.program_calls[tag] = self.program_calls.get(tag, 0) + 1
            if not manifest:
                return
            for site_tag, op, nbytes, factor in manifest:
                ent = self.runtime.get(site_tag)
                if ent is None:
                    ent = self.runtime[site_tag] = {
                        "op": op, "calls": 0, "bytes": 0}
                ent["calls"] += factor
                ent["bytes"] += nbytes * factor

    # ------------------------------------------------------------------
    # timed collective microprobe (device-synchronized wall time)
    def probe_collectives(self, mesh, payload_rows: int = 4096,
                          reps: int = 2) -> Optional[Dict[str, Any]]:
        """Run a timed psum + all_gather microprobe over `mesh` and
        record a measured seconds/bytes rate per op. The probe is its
        own tiny shard_map program (raw lax collectives, so it never
        pollutes the runtime site counters); the first rep warms the
        compile, later reps are timed behind ``block_until_ready`` —
        honest device-synchronized wall time, where the in-program
        collectives can never be separately host-timed."""
        if getattr(mesh, "size", 1) <= 1:
            return None
        import time

        import jax
        from .trace import global_tracer

        programs = self._probe_programs(mesh, payload_rows)
        n = payload_rows * mesh.size
        # byte accounting matches the runtime wrappers' convention
        # (_tree_bytes of the per-shard RESULT): psum's per-shard
        # reduced output is the local slice, all_gather's is W x it —
        # so the derived seconds-per-byte rate prices runtime bytes
        # consistently in _estimate_collective_share
        op_bytes = {"psum": payload_rows * 4, "all_gather": n * 4,
                    "psum_scatter": payload_rows * 4 // mesh.size}
        out: Dict[str, Any] = {}
        with global_tracer.span("health/collective_probe"):
            for op, (fn, x) in programs.items():
                try:
                    jax.block_until_ready(fn(x))  # compile/cache + warm
                    t0 = time.perf_counter()
                    for _ in range(max(reps, 1)):
                        r = fn(x)
                    jax.block_until_ready(r)
                    dt = (time.perf_counter() - t0) / max(reps, 1)
                except Exception:  # probes must never take training down
                    continue
                nbytes = op_bytes[op]
                with self._lock:
                    ent = self.probe.setdefault(
                        op, {"seconds": 0.0, "bytes": 0, "count": 0})
                    ent["seconds"] += dt
                    ent["bytes"] += nbytes
                    ent["count"] += 1
                out[op] = {"seconds": round(dt, 6), "bytes": nbytes}
        return out or None

    def _probe_programs(self, mesh, payload_rows: int):
        """Jitted probe programs cached per (mesh, payload) — repeated
        probes (every learner setup + the dryrun emit) must reuse the
        first pair of compiles, like _digest_program below."""
        key = ("probe", mesh.axis_names, tuple(mesh.devices.flat),
               int(payload_rows))
        cached = self._digest_cache.get(key)
        if cached is not None:
            return cached
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import shard_map as _shard_map

        axis = mesh.axis_names[0]
        x = jnp.ones((payload_rows * mesh.size,), jnp.float32)

        def _psum(v):
            return lax.psum(v, axis)

        def _gather(v):
            return lax.all_gather(v, axis)

        def _scatter(v):
            return lax.psum_scatter(v, axis, scatter_dimension=0,
                                    tiled=True)

        cached = {
            "psum": (jax.jit(_shard_map(
                _psum, mesh=mesh, in_specs=P(axis), out_specs=P())), x),
            "all_gather": (jax.jit(_shard_map(
                _gather, mesh=mesh, in_specs=P(axis),
                out_specs=P(axis))), x),
            "psum_scatter": (jax.jit(_shard_map(
                _scatter, mesh=mesh, in_specs=P(axis),
                out_specs=P(axis))), x),
        }
        self._digest_cache[key] = cached
        return cached

    # ------------------------------------------------------------------
    # straggler attribution
    @staticmethod
    def straggler_from_matrix(phase_names: Sequence[str],
                              matrix) -> Dict[str, Any]:
        """Skew stats from a [n_hosts, n_phases] per-phase seconds
        matrix: per phase the max and median across hosts, their ratio
        (the straggler skew), and the worst-shard ordinal. Pure math —
        the allgather plumbing lives in :meth:`straggler_probe`."""
        m = np.asarray(matrix, np.float64)
        if m.ndim == 1:
            m = m[None, :]
        phases: Dict[str, Any] = {}
        max_skew, worst_phase = 0.0, None
        for j, name in enumerate(phase_names):
            col = m[:, j]
            med = float(np.median(col))
            mx = float(np.max(col))
            # epsilon floor keeps the ratio finite (JSON-safe) when a
            # phase ran on a minority of hosts only
            skew = mx / max(med, 1e-9) if mx > 0 else 1.0
            worst = int(np.argmax(col))
            phases[name] = {"max_s": round(mx, 6),
                            "median_s": round(med, 6),
                            "skew": round(skew, 4),
                            "worst": worst}
            if skew > max_skew:
                max_skew, worst_phase = skew, name
        return {"n_hosts": int(m.shape[0]), "phases": phases,
                "max_skew": round(max_skew, 4), "worst_phase": worst_phase}

    def straggler_probe(self, phase_seconds: Optional[Dict[str, float]]
                        = None) -> Optional[Dict[str, Any]]:
        """Gather each host's per-phase self-times accumulated since the
        last probe (from the tracer aggregation) across processes and
        publish the skew summary. Single-process meshes share one host,
        so the matrix degenerates to one row (skew 1.0) — the plumbing
        still runs, which is what the multichip dryrun proves."""
        if phase_seconds is None:
            from .trace import global_tracer
            agg = global_tracer.summary()
            cur = {n: a["self_seconds"] for n, a in agg.items()}
            phase_seconds = {n: cur[n] - self._straggler_base.get(n, 0.0)
                             for n in cur}
            self._straggler_base = cur
        names = sorted(n for n, v in phase_seconds.items() if v > 0)
        if not names:
            return self.straggler
        vec = np.asarray([phase_seconds[n] for n in names], np.float64)
        matrix = vec[None, :]
        try:
            import jax
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils as mh
                # phase sets can differ across hosts (host-0-only driver
                # work, a phase still pending on a straggler); column j
                # must mean the same phase everywhere, so a name-list
                # signature rides along and any disagreement falls back
                # to local-only stats instead of misattributing skew
                import zlib
                sig = float(zlib.crc32("\n".join(names).encode()))
                gathered = np.asarray(mh.process_allgather(
                    np.concatenate([vec, [sig]])))
                if np.all(gathered[:, -1] == sig):
                    matrix = gathered[:, :-1]
        except Exception:
            pass  # a failed gather degrades to local-only stats
        fresh = self.straggler_from_matrix(names, matrix)
        # merge across probes: keep every phase's WORST observed skew —
        # a straggler that showed up once must stay visible in the
        # run-final summary, not be overwritten by a later quiet probe
        prev = self.straggler
        if prev:
            merged = dict(prev["phases"])
            for name, ph in fresh["phases"].items():
                old = merged.get(name)
                if old is None or ph.get("skew", 0) >= old.get("skew", 0):
                    merged[name] = ph
            worst = max(merged, key=lambda n: merged[n].get("skew", 0.0))
            fresh = {"n_hosts": fresh["n_hosts"], "phases": merged,
                     "max_skew": merged[worst].get("skew", 1.0),
                     "worst_phase": worst}
        self.straggler = fresh
        return fresh

    # ------------------------------------------------------------------
    # cross-shard drift sentinels
    def _digest_program(self, mesh, leaves, treedef):
        """Cached jitted shard_map digest: each shard computes a [L, 4]
        digest of its LOCAL copy of every (replicated) leaf — sum,
        sum-of-squares, abs-sum, nonfinite count, with nonfinite values
        zeroed from the sums so identical-NaN state still matches —
        then all_gathers to [W, L, 4] for the host comparison."""
        avals = tuple((tuple(x.shape), str(x.dtype)) for x in leaves)
        key = (mesh.axis_names, tuple(mesh.devices.flat), treedef, avals)
        fn = self._digest_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import shard_map as _shard_map

        axis = mesh.axis_names[0]

        def body(*xs):
            digs = []
            for x in xs:
                xf = jnp.asarray(x).astype(jnp.float32).ravel()
                finite = jnp.isfinite(xf)
                xz = jnp.where(finite, xf, 0.0)
                digs.append(jnp.stack([
                    jnp.sum(xz), jnp.sum(xz * xz), jnp.sum(jnp.abs(xz)),
                    jnp.sum((~finite).astype(jnp.float32))]))
            return lax.all_gather(jnp.stack(digs), axis)  # [W, L, 4]

        fn = jax.jit(_shard_map(
            body, mesh=mesh, in_specs=tuple(P() for _ in leaves),
            out_specs=P()))
        self._digest_cache[key] = fn
        return fn

    def drift_digests(self, mesh, tree) -> np.ndarray:
        """[W, n_leaves, 4] per-shard digests of a replicated pytree."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        fn = self._digest_program(mesh, leaves, treedef)
        return np.asarray(fn(*leaves))

    def check_drift(self, mesh, arrays: Dict[str, Any], *,
                    mode: str = "warn",
                    where: str = "") -> List[Dict[str, Any]]:
        """Digest every named replicated pytree across the mesh and
        compare shards. Returns the mismatch records; ``mode="warn"``
        logs and counts them, ``mode="error"`` raises
        :class:`DriftError` naming the diverged shard ordinals."""
        from .trace import global_tracer
        mismatches: List[Dict[str, Any]] = []
        with global_tracer.span("health/drift_check"):
            for name, tree in arrays.items():
                digs = self.drift_digests(mesh, tree)
                self.drift_checks += 1
                # majority vote: the modal digest row is "truth", every
                # other shard is divergent — a single bad replica is
                # named even when shard 0 is the bad one (W >= 3). With
                # no strict majority (e.g. a diverged 2-shard mesh) the
                # replicas are indistinguishable: every shard is
                # reported rather than arbitrarily blaming one.
                keys = [digs[w].tobytes() for w in range(digs.shape[0])]
                counts: Dict[bytes, int] = {}
                for k in keys:
                    counts[k] = counts.get(k, 0) + 1
                majority = max(counts, key=lambda k: counts[k])
                if len(counts) > 1 and counts[majority] * 2 <= len(keys):
                    bad = list(range(len(keys)))
                else:
                    bad = [w for w, k in enumerate(keys) if k != majority]
                if bad:
                    mismatches.append({
                        "name": name, "shards": bad,
                        "where": where,
                        "digests": digs.reshape(digs.shape[0], -1)
                        .tolist()})
        if mismatches:
            self.drift_mismatches += len(mismatches)
            self.last_drift = {"where": where,
                               "mismatches": [
                                   {k: m[k] for k in ("name", "shards")}
                                   for m in mismatches]}
            detail = "; ".join(
                f"{m['name']}: shard(s) {m['shards']} diverged"
                for m in mismatches)
            msg = (f"cross-shard drift detected"
                   f"{' at ' + where if where else ''}: {detail} "
                   f"(replicated state is no longer replicated — "
                   f"see obs/health.py)")
            if str(mode).lower() == "error":
                raise DriftError(msg)
            from .. import log
            log.warning(msg)
        return mismatches

    # ------------------------------------------------------------------
    # NaN/Inf sentinel
    def note_sentinel(self, iteration: int, counts: Dict[str, int], *,
                      mode: str = "warn", where: str = "") -> None:
        """Record one iteration's nonfinite counts (grad/hess/scores).
        Zero counts are free book-keeping; any nonzero count flags the
        iteration and, under ``mode="error"``, raises
        :class:`NonFiniteError` — within the iteration that produced
        it, not many evals later."""
        total = 0
        for kind, v in counts.items():
            v = int(v)
            if v:
                self.nonfinite[kind] = self.nonfinite.get(kind, 0) + v
            total += v
        if not total:
            return
        self.nonfinite_iterations += 1
        self.last_nonfinite = {"iteration": int(iteration), **{
            k: int(v) for k, v in counts.items()}}
        detail = ", ".join(f"{k}={int(v)}" for k, v in counts.items() if v)
        msg = (f"non-finite training state at iteration {iteration}"
               f"{' (' + where + ')' if where else ''}: {detail} "
               f"entries are NaN/Inf")
        if str(mode).lower() == "error":
            raise NonFiniteError(msg)
        from .. import log
        log.warning(msg)

    # ------------------------------------------------------------------
    # eval-loss anomaly detector
    def note_eval(self, iteration: int, data_name: str, metric_name: str,
                  value: float, higher_better: bool = False) -> List[str]:
        """Feed one eval result; returns the anomaly flags it raised
        (subset of {"nan", "spike", "plateau"})."""
        key = f"{data_name}/{metric_name}"
        hist = self._eval_hist.setdefault(key, [])
        flags: List[str] = []
        v = float(value) if value is not None else float("nan")
        if not math.isfinite(v):
            flags.append("nan")
        else:
            window = hist[-_EVAL_WINDOW:]
            if len(window) >= 4:
                med = float(np.median(window))
                worse = (med - v) if higher_better else (v - med)
                if math.isfinite(med) and worse > max(
                        abs(med), 1e-12) * _EVAL_SPIKE_FRAC:
                    flags.append("spike")
            if len(window) >= _EVAL_WINDOW:
                vals = window + [v]
                # flat over a full window in either direction
                if (max(vals) - min(vals)) < _EVAL_PLATEAU_EPS:
                    flags.append("plateau")
            hist.append(v)
            if len(hist) > 4 * _EVAL_WINDOW:
                del hist[:-2 * _EVAL_WINDOW]
        for f in flags:
            self.eval_anomalies[f] = self.eval_anomalies.get(f, 0) + 1
        if flags:
            self.last_eval_anomaly = {
                "iteration": int(iteration), "metric": key,
                "value": v if math.isfinite(v) else None, "flags": flags}
        return flags

    def note_evals(self, iteration: int, results) -> None:
        """Feed an engine evaluation_result_list
        ([(data_name, metric, value, higher_better), ...])."""
        for item in results or ():
            try:
                name, metric, value, hib = item[0], item[1], item[2], \
                    bool(item[3])
            except (IndexError, TypeError):
                continue
            self.note_eval(iteration, name, metric, value, hib)

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """The bench/MULTICHIP-JSON shaped health summary; sections with
        nothing recorded are omitted (a disabled run returns {})."""
        out: Dict[str, Any] = {}
        with self._lock:
            runtime = {t: dict(v) for t, v in self.runtime.items()}
            probe = {op: dict(v) for op, v in self.probe.items()}
        if runtime:
            out["collectives"] = runtime
        if probe:
            for op, ent in probe.items():
                secs = ent.get("seconds", 0.0)
                ent["bytes_per_s"] = (round(ent["bytes"] / secs, 1)
                                      if secs > 0 else 0.0)
            out["collective_probe"] = probe
        est = self._estimate_collective_share(runtime, probe)
        if est:
            out["collectives_est"] = est
        if self.straggler:
            out["straggler"] = self.straggler
        if self.drift_checks or self.drift_mismatches:
            out["drift"] = {"checks": self.drift_checks,
                            "mismatches": self.drift_mismatches}
            if self.last_drift:
                out["drift"]["last"] = self.last_drift
        if self.nonfinite or self.nonfinite_iterations:
            out["nonfinite"] = {**self.nonfinite,
                                "flagged_iterations":
                                self.nonfinite_iterations}
            if self.last_nonfinite:
                out["nonfinite"]["last"] = self.last_nonfinite
        if self.eval_anomalies:
            out["eval"] = dict(self.eval_anomalies)
            if self.last_eval_anomaly:
                out["eval"]["last"] = self.last_eval_anomaly
        return out

    @staticmethod
    def _estimate_collective_share(runtime, probe) -> Optional[Dict]:
        """Estimated collective seconds (runtime bytes x the probe's
        measured per-byte rate) as a share of total measured training
        time — the quantity tools/check_perf_gate.py's health check
        holds to a ceiling. None when either side is missing."""
        if not runtime or not probe:
            return None
        est = 0.0
        for ent in runtime.values():
            p = probe.get(ent.get("op"))
            if not p or p.get("bytes", 0) <= 0:
                continue
            rate = p["seconds"] / p["bytes"]  # measured seconds per byte
            est += ent.get("bytes", 0) * rate
        if est <= 0:
            return None
        train_s = sum(r.get("iteration_seconds", 0.0)
                      for r in global_metrics.history)
        out = {"est_seconds": round(est, 6)}
        if train_s > 0:
            out["train_seconds"] = round(train_s, 4)
            out["time_share"] = round(min(est / train_s, 1.0), 4)
        return out


global_health = HealthRegistry()

# env-enabled telemetry (LGBM_TPU_TELEMETRY) arms health too, matching
# obs/memory.py's watermarks and obs/xla.py's introspector
if global_metrics.enabled:
    global_health.enable()


# ---------------------------------------------------------------------------
# collective call-site wrappers (used by learner.py / parallel/*)
def psum(x, axis_name: str, *, tag: str, loop_factor: int = 1):
    """``lax.psum`` with health accounting: keeps the PR-1 trace-time
    counters and registers the site (tag, bytes, scan trip count) into
    the enclosing program's manifest for runtime attribution."""
    import jax
    from jax import lax
    with jax.named_scope("lgbm/collective"):
        out = lax.psum(x, axis_name)
    nbytes = _tree_bytes(out)
    global_metrics.note_collective("psum", nbytes)
    global_health.register_site(tag, "psum", nbytes, loop_factor)
    return out


def all_gather(x, axis_name: str, *, tag: str, loop_factor: int = 1):
    """``lax.all_gather`` (pytree-mapped) with health accounting; byte
    counts are of the GATHERED result (W x the local payload)."""
    import jax
    from jax import lax
    with jax.named_scope("lgbm/collective"):
        out = jax.tree_util.tree_map(
            lambda a: lax.all_gather(a, axis_name), x)
    nbytes = _tree_bytes(out)
    global_metrics.note_collective("all_gather", nbytes)
    global_health.register_site(tag, "all_gather", nbytes, loop_factor)
    return out


def psum_scatter(x, axis_name: str, *, tag: str, loop_factor: int = 1,
                 scatter_dimension: int = 0):
    """``lax.psum_scatter`` (tiled) with health accounting: each shard
    receives only its owned 1/W slice of the reduced tensor — the
    ReduceScatter of data_parallel_tree_learner.cpp:287. Byte counts
    are of the per-shard RESULT slice (the wrapper convention), which
    is what makes the psum->psum_scatter reduction visible as a W-fold
    drop in the runtime counters."""
    import jax
    from jax import lax
    with jax.named_scope("lgbm/collective"):
        out = lax.psum_scatter(x, axis_name,
                               scatter_dimension=scatter_dimension,
                               tiled=True)
    nbytes = _tree_bytes(out)
    global_metrics.note_collective("psum_scatter", nbytes)
    global_health.register_site(tag, "psum_scatter", nbytes, loop_factor)
    return out


def note_gspmd_collective(op: str, nbytes: int, *, tag: str,
                          loop_factor: int = 1) -> None:
    """Account a collective the XLA GSPMD partitioner inserts on its own
    (no lax call site to wrap — e.g. the reduce-scatter materializing a
    feature-sharded histogram constraint). Called at trace time from
    inside the instrumented program so the modeled bytes land in the
    same manifest/runtime counters as the explicit wrappers."""
    global_metrics.note_collective(op, int(nbytes))
    global_health.register_site(tag, op, int(nbytes), loop_factor)
