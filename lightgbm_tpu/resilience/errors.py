"""Structured error hierarchy of the resilience layer.

Dependency-free on purpose: ``model_io`` (corrupt-model detection),
``serve/`` (degradation paths) and ``resilience/checkpoint.py`` all
raise these, and none of them can afford an import cycle through the
other. Every class carries machine-readable fields (byte offsets,
retry-after hints) so callers can react programmatically instead of
string-matching messages.
"""

from __future__ import annotations

from typing import Optional

# Exit code of a preemption-triggered shutdown: engine.train finished
# the in-flight iteration, wrote a checkpoint, and exited on purpose.
# 75 = BSD EX_TEMPFAIL ("temporary failure; retry") — a supervisor that
# sees it should re-run the same command, which resumes from the
# checkpoint. Distinct from 1 (crash) and 0 (done).
EXIT_PREEMPTED = 75


class CorruptModelError(ValueError):
    """A model file / string / checkpoint failed structural validation
    (truncation, garbage, digest mismatch). ``offset`` is the byte
    offset at which the content stopped making sense — for a truncated
    file that is where the missing bytes should have started.
    A ``ValueError`` so the CLI's fatal handler (and callers catching
    bad-input errors generically) see it without importing this
    module."""

    def __init__(self, message: str, offset: Optional[int] = None,
                 path: Optional[str] = None):
        self.offset = offset
        self.path = path
        where = ""
        if path:
            where += f" [{path}]"
        if offset is not None:
            where += f" (byte offset {offset})"
        super().__init__(message + where)


class CorruptCheckpointError(CorruptModelError):
    """A training checkpoint's content digest (or container structure)
    did not verify — resuming from it would silently train on torn
    state, so the loader refuses."""


class ResumeMismatchError(ValueError):
    """A checkpoint exists but was written by an incompatible run
    (different objective / tree counts / dataset shape). Mesh-shape
    drift alone is tolerated when ``tpu_elastic_resume`` is on
    (resilience/elastic.py); everything else always refuses."""


class ElasticResumeError(RuntimeError):
    """An elastic (mesh-resized) resume failed its rejoin validation:
    the drift digests of the restored state did not agree across the
    rebuilt mesh, so letting the rejoined replicas vote would fork the
    model. ``shards`` names the diverged shard ordinals."""

    def __init__(self, message: str, shards: Optional[list] = None):
        self.shards = list(shards or [])
        super().__init__(message)


class DeadlineExceeded(RuntimeError):
    """A serve request's deadline expired before (or while) it could be
    dispatched; the request failed fast instead of occupying the
    batcher. ``elapsed_s`` is how long it had been queued."""

    def __init__(self, message: str, elapsed_s: float = 0.0):
        self.elapsed_s = float(elapsed_s)
        super().__init__(message)


class ServerOverloaded(RuntimeError):
    """Admission control shed this request: the pending queue already
    holds more than ``serve_max_queue_rows`` rows. ``retry_after_s`` is
    the server's estimate of when capacity frees up (retry-after
    semantics for an HTTP front to surface as a 429/503 header)."""

    def __init__(self, message: str, retry_after_s: float = 0.05):
        self.retry_after_s = float(retry_after_s)
        super().__init__(message)


class CircuitOpenError(RuntimeError):
    """The per-model circuit breaker is open after repeated predict
    faults; requests fail fast until the half-open probe succeeds.
    ``retry_after_s`` is the time until the breaker half-opens."""

    def __init__(self, message: str, retry_after_s: float = 0.0):
        self.retry_after_s = float(retry_after_s)
        super().__init__(message)


class TransientServeError(RuntimeError):
    """A retryable serving fault (registry pack / compile hiccup, an
    injected fault-plan failure). The server's dispatch retries these
    with exponential backoff; anything else counts against the circuit
    breaker immediately."""


class DistributedInitError(RuntimeError):
    """Joining the jax.distributed runtime failed after the configured
    retry schedule (coordinator down, wrong address, handshake
    timeout). ``attempts`` is how many connection attempts were made;
    ``last_error`` carries the final underlying failure so a supervisor
    can distinguish a dead coordinator from a misconfigured rank."""

    def __init__(self, message: str, attempts: int = 1,
                 last_error: Optional[BaseException] = None):
        self.attempts = int(attempts)
        self.last_error = last_error
        super().__init__(message)


class PeerLostError(RuntimeError):
    """The training watchdog's heartbeat collective did not complete
    within ``tpu_watchdog_deadline_s`` — a peer process is hung or dead
    and every further collective would stall with it. ``deadline_s`` is
    the deadline that expired, ``iteration`` the boundary at which the
    heartbeat was attempted, and ``phase`` names the watched step.
    engine.train escalates this to checkpoint + ``EXIT_PREEMPTED`` so a
    supervisor restarts the survivors on a shrunk mesh (elastic
    resume)."""

    def __init__(self, message: str, deadline_s: float = 0.0,
                 iteration: Optional[int] = None, phase: str = "heartbeat"):
        self.deadline_s = float(deadline_s)
        self.iteration = iteration
        self.phase = str(phase)
        super().__init__(message)
