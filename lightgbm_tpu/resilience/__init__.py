"""Fault tolerance for training and serving.

- ``checkpoint`` — atomic full-state snapshots at iteration boundaries
  + bit-identical resume (``tpu_checkpoint_every`` /
  ``tpu_checkpoint_path``; SIGTERM-driven preemption snapshots exiting
  with ``EXIT_PREEMPTED``).
- ``faults`` — the deterministic fault-injection plan the tests and
  ``tools/check_resilience.py`` drive the recovery paths with.
- ``degrade`` — serving-side graceful degradation (per-model circuit
  breaker, backoff schedules) used by ``serve/server.py`` together
  with per-request deadlines and bounded admission.
- ``watchdog`` — the distributed-training heartbeat/deadline watchdog
  (``tpu_watchdog_deadline_s``): a hung peer becomes a structured
  ``PeerLostError`` + checkpoint + ``EXIT_PREEMPTED`` instead of an
  infinite collective stall.
- ``errors`` — the structured exception hierarchy
  (``CorruptModelError`` and friends).
"""

from .errors import (EXIT_PREEMPTED, CircuitOpenError,
                     CorruptCheckpointError, CorruptModelError,
                     DeadlineExceeded, DistributedInitError,
                     ElasticResumeError, PeerLostError,
                     ResumeMismatchError, ServerOverloaded,
                     TransientServeError)
from .faults import FaultPlan, global_faults, install as install_faults
from .checkpoint import (load_checkpoint, restore_booster,
                         save_checkpoint)
from .continual import ContinualTrainer, GenerationResult
from .watchdog import Watchdog

__all__ = [
    "EXIT_PREEMPTED", "CircuitOpenError", "ContinualTrainer",
    "CorruptCheckpointError", "CorruptModelError", "DeadlineExceeded",
    "DistributedInitError", "ElasticResumeError", "GenerationResult",
    "PeerLostError", "ResumeMismatchError", "ServerOverloaded",
    "TransientServeError", "FaultPlan", "Watchdog",
    "global_faults", "install_faults", "load_checkpoint",
    "restore_booster", "save_checkpoint",
]
