"""OpenMetrics / Prometheus text-format export of the obs registries.

PR 1–5 accumulated rich internal telemetry (counters, latency
reservoirs, recompile counts, HBM watermarks, the analytic memory
model) that nothing could scrape. This module is the egress:

- ``render_openmetrics()`` — one Prometheus text-format (0.0.4)
  document over ``obs.metrics.global_metrics`` (event counters, latency
  reservoirs as summary metrics with quantile labels, predict
  throughput, trace-time jit counters, collective traffic), the
  per-device HBM stats + ``obs.memory`` watermark/model gauges where
  available, the ``obs.xla`` compile facts, and host identity labels.
- ``MetricsHTTPEndpoint`` — a daemon-thread HTTP listener serving
  ``/metrics`` (the rendered document), ``/healthz`` (process
  liveness — 200 whenever the listener is up) and ``/readyz`` (503
  until the owner's ``ready_fn`` turns true; ``ModelServer`` wires its
  warm()-in-progress state here). stdlib ``http.server`` on a thread,
  so it keeps answering while the main thread blocks in ``warm()`` or
  a training step.
- ``MetricsTextfileFlusher`` — the training-side egress for hosts with
  a node-exporter textfile collector instead of a scrape target:
  ``LGBM_TPU_METRICS_FILE=/path.prom`` makes the boosting loop flush
  the rendered document atomically (tmp + rename) at most every
  ``LGBM_TPU_METRICS_FLUSH_SECS`` (default 15), plus once at exit.

Disabled cost: with the env var unset, ``global_flusher.maybe_flush()``
is a single attribute check; nothing renders, nothing is written.
"""

from __future__ import annotations

import atexit
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .metrics import global_metrics

# Prometheus text exposition format 0.0.4 (the content type Prometheus'
# scraper negotiates for the text format)
CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"
# OpenMetrics 1.0: same rendered body (the document ends with the
# required `# EOF` terminator and stays within the common subset), so
# negotiation only changes the advertised content type
OPENMETRICS_CONTENT_TYPE = ("application/openmetrics-text; "
                            "version=1.0.0; charset=utf-8")


def negotiate_content_type(accept: Optional[str]) -> str:
    """Content type for a scrape given its Accept header: OpenMetrics
    when the scraper asks for ``application/openmetrics-text``
    (Prometheus does once per target to probe support), the classic
    0.0.4 text type otherwise."""
    return (OPENMETRICS_CONTENT_TYPE
            if "application/openmetrics-text" in (accept or "")
            else CONTENT_TYPE)

_NAME_OK = re.compile(r"[^a-zA-Z0-9_:]")


def _metric_name(name: str, suffix: str = "") -> str:
    """`serve/registry_hit` -> `lgbmtpu_serve_registry_hit<suffix>`."""
    return "lgbmtpu_" + _NAME_OK.sub("_", name).strip("_") + suffix


def _label_value(v: Any) -> str:
    s = str(v)
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt(v: Any) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


class _Doc:
    """Accumulates families in render order, one TYPE header each."""

    def __init__(self) -> None:
        self.lines: List[str] = []
        self._typed: set = set()

    def sample(self, family: str, mtype: str, value: Any,
               labels: Optional[Dict[str, Any]] = None,
               help_text: str = "", name: Optional[str] = None) -> None:
        if family not in self._typed:
            self._typed.add(family)
            if help_text:
                self.lines.append(f"# HELP {family} {help_text}")
            self.lines.append(f"# TYPE {family} {mtype}")
        n = name or family
        if labels:
            lab = ",".join(f'{k}="{_label_value(v)}"'
                           for k, v in sorted(labels.items()))
            n += "{" + lab + "}"
        self.lines.append(f"{n} {_fmt(value)}")

    def text(self) -> str:
        # `# EOF` is the OpenMetrics 1.0 terminator; Prometheus 0.0.4
        # parsers treat it as a comment, so one body serves both
        return "\n".join(self.lines + ["# EOF"]) + "\n"


def render_openmetrics(registry=None,
                       extra_gauges: Optional[Dict[str, Any]] = None
                       ) -> str:
    """The full obs state as one Prometheus text-format document.

    `extra_gauges` maps already-sanitized family names to values
    (the ModelServer adds its pack/registry gauges this way)."""
    reg = registry if registry is not None else global_metrics
    doc = _Doc()

    # snapshot the concurrently-mutated dicts under the registry mutex:
    # the serve loop/executor insert new counter and reservoir names
    # while the HTTP daemon thread renders (a live iteration would
    # raise "dictionary changed size during iteration" mid-scrape)
    with reg._mutex:
        counters = dict(reg.counters)
        reservoirs = dict(reg.latency_reservoirs)
    trace_counts = dict(reg.trace_counts)
    meta = dict(reg.meta)

    # host identity: an info-style gauge (constant 1) carrying labels,
    # so multihost scrapes are mergeable by labels instead of by target
    from ..hostenv import host_labels
    doc.sample("lgbmtpu_host_info", "gauge", 1, labels=host_labels(),
               help_text="host/process identity labels (value is 1)")

    # flat event counters (serve/* registry + batcher + server events)
    for name in sorted(counters):
        doc.sample(_metric_name(name, "_total"), "counter",
                   counters[name])

    # latency reservoirs -> summary metrics with quantile labels
    fam = "lgbmtpu_latency_seconds"
    for name in sorted(reservoirs):
        res = reservoirs[name]
        p50, p95, p99 = res.quantiles((0.50, 0.95, 0.99))
        for q, v in (("0.5", p50), ("0.95", p95), ("0.99", p99)):
            doc.sample(fam, "summary", v,
                       labels={"name": name, "quantile": q},
                       help_text="latency quantiles from the bounded "
                                 "obs reservoirs")
        doc.sample(fam, "summary", res.total_seconds,
                   labels={"name": name}, name=fam + "_sum")
        doc.sample(fam, "summary", res.count,
                   labels={"name": name}, name=fam + "_count")

    # served-explanation latency: the generic block above already
    # carries name="explain/request"; this dedicated family gives the
    # explain SLO its own stable name, mirroring how the serve
    # dashboards key on lgbmtpu_latency_seconds{name="serve/request"}
    # (family linted by tools/check_shap.py)
    res = reservoirs.get("explain/request")
    if res is not None and res.count:
        fam = "lgbmtpu_explain_latency_seconds"
        p50, p95, p99 = res.quantiles((0.50, 0.95, 0.99))
        for q, v in (("0.5", p50), ("0.95", p95), ("0.99", p99)):
            doc.sample(fam, "summary", v, labels={"quantile": q},
                       help_text="served SHAP-explanation request "
                                 "latency (ModelServer.explain)")
        doc.sample(fam, "summary", res.total_seconds, name=fam + "_sum")
        doc.sample(fam, "summary", res.count, name=fam + "_count")

    # predict throughput accumulators (always-on)
    doc.sample("lgbmtpu_predict_rows_total", "counter",
               reg.predict_rows_total)
    doc.sample("lgbmtpu_predict_seconds_total", "counter",
               reg.predict_seconds_total)
    doc.sample("lgbmtpu_predict_rows_per_sec", "gauge",
               reg.predict_rows_per_sec())

    # trace-time jit counters + collective traffic
    for tag in sorted(trace_counts):
        doc.sample("lgbmtpu_jit_traces_total", "counter",
                   trace_counts[tag], labels={"tag": tag},
                   help_text="python traces per jit tag (one per "
                             "program (re)compile at top level)")
    doc.sample("lgbmtpu_collective_calls_total", "counter",
               reg.collective_calls)
    doc.sample("lgbmtpu_collective_bytes_total", "counter",
               reg.collective_bytes)

    # device memory gauges (accelerator backends only)
    stats = reg.per_device_memory_stats()
    for s in stats or ():
        lab = {"device": s.get("device", 0)}
        for key, fam_name in (("bytes_in_use", "lgbmtpu_device_bytes_in_use"),
                              ("peak_bytes_in_use",
                               "lgbmtpu_device_peak_bytes_in_use"),
                              ("bytes_limit", "lgbmtpu_device_bytes_limit")):
            if isinstance(s.get(key), (int, float)):
                doc.sample(fam_name, "gauge", s[key], labels=lab)

    # per-phase HBM watermarks (obs/memory.py; armed on accelerators)
    from .memory import global_watermarks
    for phase, ph in sorted(global_watermarks.summary().items()):
        doc.sample("lgbmtpu_phase_peak_bytes", "gauge", ph["peak_bytes"],
                   labels={"phase": phase},
                   help_text="span-boundary HBM peak per phase")

    # analytic-model gauges published through obs meta
    mm = meta.get("mem_model")
    if isinstance(mm, dict) and "peak_bytes" in mm:
        doc.sample("lgbmtpu_mem_peak_model_bytes", "gauge",
                   mm["peak_bytes"],
                   help_text="analytic peak-HBM model (obs/memory.py)")
    ht = meta.get("hist_traffic")
    if isinstance(ht, dict) and "hist_bytes_per_iter" in ht:
        doc.sample("lgbmtpu_hist_bytes_per_iter", "gauge",
                   ht["hist_bytes_per_iter"],
                   help_text="analytic per-iteration histogram HBM "
                             "traffic (learner.hist_traffic_model)")

    # checkpoint accounting (resilience/checkpoint.py; the snapshot
    # COUNT rides the generic resilience/* counters above)
    rc = meta.get("resilience_checkpoint")
    if isinstance(rc, dict) and "seconds_total" in rc:
        doc.sample("lgbmtpu_resilience_checkpoint_seconds_total",
                   "counter", rc["seconds_total"],
                   help_text="wall time spent writing training "
                             "checkpoints (atomic snapshot + fsync "
                             "path, resilience/checkpoint.py)")
        doc.sample("lgbmtpu_resilience_checkpoint_last_iteration",
                   "gauge", rc.get("last_iteration", -1))

    # continual-training accounting (resilience/continual.py; the
    # generation/rollback/swap COUNTS ride the generic continual/*
    # counters above — these are the summary-shaped extras)
    ct = meta.get("continual")
    if isinstance(ct, dict) and "generations" in ct:
        doc.sample("lgbmtpu_continual_swap_seconds_total", "counter",
                   ct.get("swap_seconds_total", 0.0),
                   help_text="wall time spent in validated hot-swaps "
                             "(reload-parity check + transactional "
                             "registry registration)")
        doc.sample("lgbmtpu_continual_last_swap_seconds", "gauge",
                   ct.get("last_swap_seconds", 0.0))
        doc.sample("lgbmtpu_continual_model_iterations", "gauge",
                   ct.get("model_iterations", 0),
                   help_text="boosting iterations in the last-good "
                             "continual model")
        doc.sample("lgbmtpu_continual_retained_snapshots", "gauge",
                   ct.get("retained_snapshots", 0))
        doc.sample("lgbmtpu_continual_resumes_total", "counter",
                   ct.get("resumes", 0),
                   help_text="checkpoint resumes observed by the "
                             "continual loop (incl. elastic mesh "
                             "resizes, counted separately)")
        doc.sample("lgbmtpu_continual_mesh_resizes_total", "counter",
                   ct.get("mesh_resizes", 0))

    # serving-fleet health (serve/fleet.py FleetRouter; the
    # failover/hedge/quarantine COUNTS ride the generic fleet/*
    # counters above — these are the per-replica state gauges the
    # chaos validator scrapes to see the kill and the recovery)
    fl = meta.get("fleet")
    if isinstance(fl, dict) and "replicas" in fl:
        doc.sample("lgbmtpu_fleet_replicas", "gauge", fl["replicas"],
                   help_text="configured replicas behind the "
                             "FleetRouter")
        for name in sorted(fl.get("replica_up", {})):
            doc.sample("lgbmtpu_fleet_replica_up", "gauge",
                       fl["replica_up"][name],
                       labels={"replica": name},
                       help_text="1 while the replica answers its "
                                 "liveness probe")
        for name in sorted(fl.get("replica_quarantined", {})):
            doc.sample("lgbmtpu_fleet_replica_quarantined", "gauge",
                       fl["replica_quarantined"][name],
                       labels={"replica": name},
                       help_text="1 while the router holds the replica "
                                 "out of rotation")

    # out-of-core streaming accounting (io/streaming.py StreamStats,
    # published per iteration by the streamed boosting paths): the
    # driver-visible proof that slab uploads overlap the histogram
    # kernels without silicon counters
    sm = meta.get("stream")
    if isinstance(sm, dict) and sm.get("slabs_total"):
        doc.sample("lgbmtpu_stream_slabs_total", "counter",
                   sm.get("slabs_total", 0),
                   help_text="host-resident bin slabs fed to the device "
                             "(tpu_stream out-of-core training)")
        doc.sample("lgbmtpu_stream_uploads_total", "counter",
                   sm.get("uploads_total", 0))
        doc.sample("lgbmtpu_stream_bytes_uploaded_total", "counter",
                   sm.get("bytes_uploaded_total", 0))
        doc.sample("lgbmtpu_stream_upload_seconds_total", "counter",
                   sm.get("upload_seconds_total", 0.0))
        doc.sample("lgbmtpu_stream_overlapped_uploads_total", "counter",
                   sm.get("overlapped_uploads_total", 0))
        doc.sample("lgbmtpu_stream_kernel_seconds_total", "counter",
                   sm.get("kernel_seconds_total", 0.0),
                   help_text="host wall time blocked on streamed-"
                             "pipeline device compute")
        doc.sample("lgbmtpu_stream_iterations_total", "counter",
                   sm.get("iterations_total", 0))
        doc.sample("lgbmtpu_stream_overlap_ratio", "gauge",
                   sm.get("overlap_ratio", 0.0),
                   help_text="fraction of upload wall-time issued while "
                             "device compute was in flight (the "
                             "double-buffer's measured overlap)")
        doc.sample("lgbmtpu_stream_slab_rows", "gauge",
                   sm.get("slab_rows", 0))
        doc.sample("lgbmtpu_stream_n_slabs", "gauge",
                   sm.get("n_slabs", 0))

    # XLA introspection (obs/xla.py; populated while enabled)
    from .xla import global_xla
    xs = global_xla.summary()
    doc.sample("lgbmtpu_xla_compile_seconds_total", "counter",
               xs["compile_s_total"],
               help_text="wall time spent compiling XLA programs")
    doc.sample("lgbmtpu_xla_trace_seconds_total", "counter",
               xs.get("trace_s_total", 0.0),
               help_text="wall time spent tracing/lowering before "
                         "compile (no cache can skip it)")
    doc.sample("lgbmtpu_xla_cache_load_seconds_total", "counter",
               xs.get("cache_load_s_total", 0.0),
               help_text="wall time loading programs from the "
                         "persistent compilation cache")
    doc.sample("lgbmtpu_xla_cache_hits_total", "counter",
               xs.get("n_cache_hits", 0))
    doc.sample("lgbmtpu_xla_programs_total", "counter", xs["n_programs"])
    for phase in sorted(xs["n_recompiles_by_phase"]):
        doc.sample("lgbmtpu_xla_compiles_total", "counter",
                   xs["n_recompiles_by_phase"][phase],
                   labels={"phase": phase})
    for tag in sorted(xs["by_tag"]):
        t = xs["by_tag"][tag]
        if "flops" in t:
            doc.sample("lgbmtpu_xla_flops", "gauge", t["flops"],
                       labels={"tag": tag},
                       help_text="XLA cost-analysis flops per compiled "
                                 "program set")
        if "bytes_accessed" in t:
            doc.sample("lgbmtpu_xla_bytes_accessed", "gauge",
                       t["bytes_accessed"], labels={"tag": tag})

    # device-time attribution + roofline (obs/profile.py; emits nothing
    # until a tpu_profile window captured something)
    from .profile import global_profile
    ps = global_profile.summary()
    if ps.get("device_seconds_by_tag"):
        doc.sample("lgbmtpu_profile_window_seconds", "gauge",
                   ps.get("window_wall_s", 0.0),
                   help_text="cumulative wall time of tpu_profile "
                             "capture windows")
        if "coverage" in ps:
            doc.sample("lgbmtpu_profile_coverage", "gauge",
                       ps["coverage"],
                       help_text="attributed device seconds / window "
                                 "wall time (perf-gate check 11 band)")
        src = ps.get("source", "fallback")
        for tag in sorted(ps["device_seconds_by_tag"]):
            doc.sample("lgbmtpu_profile_device_seconds_total", "counter",
                       ps["device_seconds_by_tag"][tag],
                       labels={"tag": tag, "source": src},
                       help_text="measured device-busy seconds per "
                                 "program tag (jax.profiler trace or "
                                 "the block_until_ready fallback)")
        for tag in sorted(ps.get("calls_by_tag", {})):
            doc.sample("lgbmtpu_profile_calls_total", "counter",
                       ps["calls_by_tag"][tag], labels={"tag": tag})
        for layer, secs in ps.get("device_seconds_by_layer", {}).items():
            doc.sample("lgbmtpu_profile_layer_device_seconds_total",
                       "counter", secs, labels={"layer": layer},
                       help_text="device self seconds per lgbm/<layer> "
                                 "scope (profiler windows; the layer "
                                 "table of obs/profile.py)")
        rl = global_profile.last_roofline
        if rl is None:
            try:
                rl = global_profile.roofline()
            except Exception:
                rl = None
        if isinstance(rl, dict):
            for tag in sorted(rl.get("by_tag", {})):
                row = rl["by_tag"][tag]
                if "achieved_bytes_per_s" in row:
                    doc.sample("lgbmtpu_profile_achieved_bytes_per_second",
                               "gauge", row["achieved_bytes_per_s"],
                               labels={"tag": tag},
                               help_text="achieved HBM bytes/s per tag "
                                         "vs hostenv.device_peaks")
                for res, key in (("bytes", "bytes_utilization"),
                                 ("flops", "flops_utilization")):
                    if key in row:
                        doc.sample("lgbmtpu_profile_utilization", "gauge",
                                   row[key],
                                   labels={"tag": tag, "resource": res},
                                   help_text="achieved/peak throughput "
                                             "fraction (roofline)")

    # training-health families (obs/health.py; empty summary — health
    # never armed — emits nothing, asserted by tools/check_health.py)
    from .health import global_health
    hs = global_health.summary()
    for tag in sorted(hs.get("collectives", {})):
        ent = hs["collectives"][tag]
        lab = {"tag": tag, "op": ent.get("op", "")}
        doc.sample("lgbmtpu_health_collective_calls_total", "counter",
                   ent.get("calls", 0), labels=lab,
                   help_text="collectives actually issued at runtime, "
                             "attributed per program call (obs/health.py)")
        doc.sample("lgbmtpu_health_collective_bytes_total", "counter",
                   ent.get("bytes", 0), labels=lab)
    for op in sorted(hs.get("collective_probe", {})):
        p = hs["collective_probe"][op]
        doc.sample("lgbmtpu_health_collective_seconds_total", "counter",
                   p.get("seconds", 0.0), labels={"op": op},
                   help_text="device-synchronized wall time of the "
                             "collective microprobe")
        doc.sample("lgbmtpu_health_collective_probe_bytes_total",
                   "counter", p.get("bytes", 0), labels={"op": op})
    strag = hs.get("straggler") or {}
    for phase in sorted(strag.get("phases", {})):
        ph = strag["phases"][phase]
        skew = ph.get("skew", 1.0)
        if isinstance(skew, (int, float)) and skew == skew \
                and skew not in (float("inf"),):
            doc.sample("lgbmtpu_health_straggler_skew", "gauge", skew,
                       labels={"phase": phase},
                       help_text="per-phase max/median host-time skew "
                                 "across shards (worst-shard ordinal in "
                                 "the health summary)")
    drift = hs.get("drift") or {}
    if drift:
        doc.sample("lgbmtpu_health_drift_checks_total", "counter",
                   drift.get("checks", 0),
                   help_text="cross-shard replicated-state digest "
                             "comparisons run")
        doc.sample("lgbmtpu_health_drift_mismatch_total", "counter",
                   drift.get("mismatches", 0))
    nf = hs.get("nonfinite") or {}
    for kind in ("grad", "hess", "scores"):
        if kind in nf:
            doc.sample("lgbmtpu_health_nonfinite_total", "counter",
                       nf[kind], labels={"kind": kind},
                       help_text="NaN/Inf entries caught by the "
                                 "per-iteration sentinel")
    if nf:
        doc.sample("lgbmtpu_health_nonfinite_iterations_total", "counter",
                   nf.get("flagged_iterations", 0))
    for kind in sorted(k for k in (hs.get("eval") or {})
                       if k != "last"):
        doc.sample("lgbmtpu_health_eval_anomalies_total", "counter",
                   hs["eval"][kind], labels={"kind": kind},
                   help_text="eval-loss anomaly flags "
                             "(nan/spike/plateau)")

    for fam_name in sorted(extra_gauges or {}):
        doc.sample(fam_name, "gauge", extra_gauges[fam_name])
    return doc.text()


# ---------------------------------------------------------------------------
class MetricsHTTPEndpoint:
    """Daemon-thread HTTP listener for /metrics, /healthz, /readyz.

    `render_fn` produces the /metrics body; `ready_fn` (optional)
    gates /readyz (False -> 503). Binds `port` (0 = ephemeral; read the
    chosen one back from ``.port``)."""

    def __init__(self, render_fn: Callable[[], str],
                 ready_fn: Optional[Callable[[], bool]] = None,
                 port: int = 0, host: str = "127.0.0.1") -> None:
        import http.server

        class Handler(http.server.BaseHTTPRequestHandler):
            def _send(self, code: int, body: bytes,
                      ctype: str = "text/plain; charset=utf-8") -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self) -> None:
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    try:
                        body = render_fn().encode()
                    except Exception as exc:
                        self._send(500, f"render failed: {exc}\n".encode())
                        return
                    self._send(200, body, negotiate_content_type(
                        self.headers.get("Accept")))
                elif path == "/healthz":
                    self._send(200, b"ok\n")
                elif path == "/readyz":
                    ready = True if ready_fn is None else bool(ready_fn())
                    self._send(200 if ready else 503,
                               b"ready\n" if ready else b"warming\n")
                else:
                    self._send(404, b"not found\n")

            def log_message(self, *args) -> None:
                pass  # scrapes must not spam the training log

        self._httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="lgbm-metrics-http",
            daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


# ---------------------------------------------------------------------------
class MetricsTextfileFlusher:
    """Periodic atomic flush of the rendered document to a textfile
    (node-exporter textfile-collector shape). Armed by the
    ``LGBM_TPU_METRICS_FILE`` env var; ``maybe_flush()`` is the
    per-iteration hook — one attribute check when unarmed."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # None = never flushed: the first maybe_flush() always writes
        # (time.monotonic() starts near 0 on a freshly booted machine,
        # so a numeric sentinel would throttle it)
        self._last: Optional[float] = None
        self.rearm()

    def rearm(self) -> None:
        """Re-read the env knobs (tests toggle them at runtime)."""
        self.path = os.environ.get("LGBM_TPU_METRICS_FILE", "")
        self.armed = bool(self.path)
        try:
            self.interval_s = float(os.environ.get(
                "LGBM_TPU_METRICS_FLUSH_SECS", "") or 15.0)
        except ValueError:
            self.interval_s = 15.0

    def maybe_flush(self, force: bool = False) -> bool:
        if not self.armed:
            return False
        now = time.monotonic()
        with self._lock:
            if (not force and self._last is not None
                    and now - self._last < self.interval_s):
                return False
            self._last = now
        return self.flush()

    def flush(self) -> bool:
        if not self.armed:
            return False
        try:
            text = render_openmetrics()
            tmp = self.path + ".tmp"
            with open(tmp, "w") as fh:
                fh.write(text)
            os.replace(tmp, self.path)  # scrapers never see a torn file
            return True
        except Exception:
            return False  # egress must never take training down


global_flusher = MetricsTextfileFlusher()


def _flush_at_exit() -> None:
    if global_flusher.armed:
        global_flusher.flush()


atexit.register(_flush_at_exit)
