"""Training telemetry subsystem.

The TPU-native expansion of the reference's ``USE_TIMETAG`` phase
timers (ref: Common::Timer / FunctionTimer, include/LightGBM/utils/
common.h:980,1044; global_timer dump at src/boosting/gbdt.cpp:29):

- ``obs.trace``   — nested named spans with parent/child self-time
  attribution, exportable as Chrome trace-event JSON
  (``LGBM_TPU_TRACE=/path.json`` or the ``trace_output`` param) and as
  an aggregated summary dict.
- ``obs.metrics`` — per-iteration metrics registry: phase times,
  grad/hess norms, leaves grown, split-gain stats, JIT recompilation
  counts, device memory, collective traffic.
- ``obs.memory`` — HBM memory observability: the analytic peak-memory
  model (``train_memory_model`` / ``predict_memory_model``), live
  per-phase watermarks sampled at span boundaries
  (``global_watermarks``), and the ``preflight`` capacity planner that
  fails fast (with knob recommendations) instead of OOMing mid-run.
- ``obs.xla``    — XLA program introspection: always-on
  first-dispatch counters of every program a boundary acquires
  (trace+lower seconds, compile-or-load seconds, cache hit), and with
  telemetry on per-executable ``cost_analysis()`` /
  ``memory_analysis()`` capture and per-phase/shape-bucket recompile
  attribution (``instrumented_jit`` at the program boundaries).
- ``obs.health`` — training-health: runtime-attributed collective
  byte/call counters with a timed mesh microprobe, host straggler-skew
  attribution, cross-shard drift sentinels over replicated state
  (``tpu_health=off/warn/error`` — warn records, error raises
  ``DriftError``/``NonFiniteError``), per-iteration NaN/Inf sentinels
  folded into the fused programs, and an eval-loss anomaly detector.
- ``obs.profile`` — device-time attribution: ``jax.profiler``-backed
  capture windows (``tpu_profile=off/window/bench`` +
  ``LGBM_TPU_PROFILE_DIR``) parsed into per-program device-busy
  seconds keyed to the obs tags, a profiler-free
  ``block_until_ready`` fallback for CPU CI, and the roofline layer
  (achieved bytes/s + utilization vs ``hostenv.device_peaks`` + a
  memory/compute-bound verdict per tag).
- ``obs.flightrec`` — crash flight recorder: a bounded ring of recent
  structured events (iterations, serve outcomes, health anomalies,
  fault injections, checkpoint/resume transitions) atomically dumped
  on DriftError/NonFiniteError/SIGTERM/exit-75/exit and on demand
  (``LGBM_TPU_FLIGHTREC=/path.json``).
- ``obs.export`` — OpenMetrics egress: the Prometheus text-format
  renderer over all of the above, the ``/metrics``+``/healthz``+
  ``/readyz`` HTTP endpoint (Accept-negotiated OpenMetrics vs
  Prometheus content type, ``# EOF``-terminated), and the
  ``LGBM_TPU_METRICS_FILE`` textfile flusher.

All are disabled by default and their hot-path guards are single
attribute checks — training with telemetry off records nothing beyond
``obs.xla``'s one record per acquired program, and allocates nothing
per span/observation.
"""

from .trace import Tracer, global_tracer  # noqa: F401
from .metrics import (LatencyReservoir, MetricsRegistry,  # noqa: F401
                      global_metrics)
from .memory import (PhaseWatermarks, PreflightError,  # noqa: F401
                     PreflightReport, device_capacity_bytes,
                     global_watermarks, predict_memory_model, preflight,
                     preflight_predict, train_memory_model)
from .xla import (XlaIntrospector, aot_cost_summary,  # noqa: F401
                  global_xla, instrumented_jit)
from .health import (DriftError, HealthError,  # noqa: F401
                     HealthRegistry, NonFiniteError, global_health)
from .profile import (ProfileRegistry, global_profile,  # noqa: F401
                      layer_table)
from .flightrec import (FlightRecorder, global_flightrec,  # noqa: F401
                        validate_dump)
from .export import (MetricsHTTPEndpoint,  # noqa: F401
                     MetricsTextfileFlusher, global_flusher,
                     render_openmetrics)

__all__ = ["Tracer", "global_tracer", "LatencyReservoir",
           "MetricsRegistry", "global_metrics",
           "PhaseWatermarks", "PreflightError", "PreflightReport",
           "device_capacity_bytes", "global_watermarks",
           "train_memory_model", "predict_memory_model",
           "preflight", "preflight_predict",
           "XlaIntrospector", "global_xla", "instrumented_jit",
           "aot_cost_summary", "HealthError", "DriftError",
           "NonFiniteError", "HealthRegistry", "global_health",
           "ProfileRegistry", "global_profile", "layer_table",
           "FlightRecorder", "global_flightrec", "validate_dump",
           "MetricsHTTPEndpoint",
           "MetricsTextfileFlusher", "global_flusher",
           "render_openmetrics"]
