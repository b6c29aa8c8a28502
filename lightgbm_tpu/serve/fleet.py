"""Serving fleet: health-gated routing over N ModelServer replicas.

One ``ModelServer`` in one process is one failure domain: a wedged
replica under traffic is an outage. ``FleetRouter`` fronts N replicas —
in-process (tests, ``bench.py --fleet``) or subprocesses speaking the
replica HTTP protocol (``tools/check_fleet.py``) — and survives the
faults a single server cannot:

- **health-gated routing**: a daemon probe loop hits every replica's
  ``/readyz`` + ``/healthz`` on a ``serve_probe_interval_ms`` cadence
  and drives a quarantine/reinstate state machine — consecutive probe
  failures pull a replica out of rotation, consecutive successes put
  it back (a SIGSTOPped process times out its probes, gets
  quarantined, and is reinstated after SIGCONT without operator
  action);
- **failover retry**: predicts are idempotent and replicas are
  bit-identical by the PR-3 pack contract, so a dispatch that dies
  (connection refused, timeout, transient fault) retries on the next
  healthy replica — the caller sees one answer, not the dead replica;
- **hedged dispatch** (``serve_hedge_ms`` > 0): a request still
  unanswered after the hedge delay fires a duplicate on another
  healthy replica and the first answer wins; when both complete, the
  answers are ASSERTED bit-identical (the pack contract, checked in
  production, not just in tests);
- **graceful drain**: ``begin_drain()`` stops admitting, in-flight
  requests finish, replicas deregister (``ready`` flips false) — the
  fleet half of the SIGTERM/exit-75 contract (each subprocess replica
  independently honors the single-replica half in ``serve_file`` /
  ``_replica_main``).

Fleet events land in the ``fleet/*`` obs counters
(``lgbmtpu_fleet_*_total``: failovers, hedges, quarantines,
reinstates, drains), per-replica up/quarantined gauges render from
``global_metrics.meta["fleet"]`` (obs/export.py), every
quarantine/reinstate/failover is flight-recorded, and
``aggregate_counter_totals`` merges the replicas' own ``/metrics``
scrapes into fleet-wide totals.

The replica subprocess entry (``python -m lightgbm_tpu.serve.fleet
--replica ...``) reuses ``serve_file``'s construction recipe
(``registry_from_config`` + ``server_from_config``) and adds a
``POST /predict`` endpoint next to the stock /metrics, /healthz,
/readyz — raw float64 bytes in, raw float64 bytes out, shape in
headers, errors mapped back to the structured resilience errors.
"""

from __future__ import annotations

import asyncio
import itertools
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..obs.flightrec import global_flightrec
from ..obs.metrics import global_metrics
from ..resilience.degrade import CircuitBreaker
from ..resilience.errors import (CircuitOpenError, DeadlineExceeded,
                                 ServerOverloaded, TransientServeError)
from .server import ModelServer

# replica-side error -> HTTP status + X-Error header; router-side the
# same table maps the header back to the structured exception, so the
# hierarchy survives the process boundary
_ERROR_STATUS = {"ServerOverloaded": 503, "CircuitOpenError": 503,
                 "DeadlineExceeded": 504, "TransientServeError": 500}
_ERROR_CLASS = {"ServerOverloaded": ServerOverloaded,
                "CircuitOpenError": CircuitOpenError,
                "DeadlineExceeded": DeadlineExceeded,
                "TransientServeError": TransientServeError}


class InProcessReplica:
    """A ModelServer in this process wearing the replica interface
    (tests and ``bench.py --fleet``; fault injection kills these by
    flipping ``fail_dispatch``)."""

    def __init__(self, name: str, server: ModelServer):
        self.name = str(name)
        self.server = server
        self.fail_dispatch = False  # test hook: simulate a dead replica

    def probe(self, timeout_s: float):
        """(alive, ready) — in-process liveness is the process itself."""
        if self.fail_dispatch:
            return False, False
        return True, bool(self.server.ready)

    async def predict(self, name: str, x: np.ndarray,
                      raw_score: bool = False) -> np.ndarray:
        if self.fail_dispatch:
            raise ConnectionError(f"replica {self.name} is down "
                                  "(injected)")
        return await self.server.predict(name, x, raw_score=raw_score)

    def metrics_text(self) -> str:
        from ..obs.export import render_openmetrics
        return render_openmetrics()

    def close(self) -> None:
        pass  # owner closes the server


class HTTPReplica:
    """A subprocess replica behind the fleet HTTP protocol. Blocking
    urllib I/O — the router runs these calls on its I/O executor."""

    def __init__(self, name: str, base_url: str,
                 request_timeout_s: float = 10.0):
        self.name = str(name)
        self.base_url = str(base_url).rstrip("/")
        self.request_timeout_s = float(request_timeout_s)

    def _get(self, path: str, timeout_s: float):
        import urllib.error
        import urllib.request
        try:
            with urllib.request.urlopen(self.base_url + path,
                                        timeout=timeout_s) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as exc:
            return exc.code, exc.read()

    def probe(self, timeout_s: float):
        """(alive, ready): /healthz answering at all is liveness;
        /readyz 200 is readiness. A dead process refuses the connect,
        a stopped (SIGSTOP) one times out the read — both unalive."""
        try:
            alive = self._get("/healthz", timeout_s)[0] == 200
        except Exception:
            return False, False
        try:
            ready = self._get("/readyz", timeout_s)[0] == 200
        except Exception:
            ready = False
        return alive, ready

    def predict_blocking(self, name: str, x: np.ndarray,
                         raw_score: bool = False) -> np.ndarray:
        import urllib.error
        import urllib.request
        x = np.ascontiguousarray(x, np.float64)
        req = urllib.request.Request(
            self.base_url + "/predict", data=x.tobytes(), method="POST",
            headers={"X-Model": name,
                     "X-Shape": ",".join(str(d) for d in x.shape),
                     "X-Raw-Score": "1" if raw_score else "0",
                     "Content-Type": "application/octet-stream"})
        try:
            with urllib.request.urlopen(
                    req, timeout=self.request_timeout_s) as resp:
                body = resp.read()
                shape = tuple(int(d) for d in
                              resp.headers["X-Shape"].split(","))
        except urllib.error.HTTPError as exc:
            err = exc.headers.get("X-Error", "")
            detail = exc.read().decode(errors="replace").strip()
            cls = _ERROR_CLASS.get(err)
            if cls is not None:
                raise cls(f"replica {self.name}: {detail}")
            raise ConnectionError(
                f"replica {self.name} answered {exc.code}: {detail}")
        return np.frombuffer(body, np.float64).reshape(shape)

    def metrics_text(self) -> str:
        status, body = self._get("/metrics", self.request_timeout_s)
        if status != 200:
            raise ConnectionError(
                f"replica {self.name} /metrics answered {status}")
        return body.decode()

    def close(self) -> None:
        pass  # the subprocess has its own lifecycle (SIGTERM contract)


class _ReplicaState:
    __slots__ = ("up", "quarantined", "fail_streak", "ok_streak",
                 "breaker")

    def __init__(self, breaker: CircuitBreaker):
        self.up = True
        self.quarantined = False
        self.fail_streak = 0
        self.ok_streak = 0
        self.breaker = breaker


class FleetRouter:
    """Health-gated request router over replica objects.

    ``predict`` is the fleet's serving API — same signature and same
    bits as ``ModelServer.predict`` on any single replica. ``start()``
    launches the probe loop; ``stop()`` (or ``drain()`` first for
    graceful shutdown) tears it down."""

    def __init__(self, replicas: Sequence, probe_interval_ms: float = 50.0,
                 hedge_ms: float = 0.0, fail_threshold: int = 2,
                 ok_threshold: int = 2, probe_timeout_s: float = 0.25,
                 breaker_threshold: int = 5, breaker_reset_s: float = 1.0,
                 max_attempts: int = 0):
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names: {names}")
        self.replicas = list(replicas)
        self.probe_interval_s = max(float(probe_interval_ms), 1.0) / 1e3
        self.hedge_s = max(float(hedge_ms), 0.0) / 1e3
        self.fail_threshold = max(int(fail_threshold), 1)
        self.ok_threshold = max(int(ok_threshold), 1)
        self.probe_timeout_s = float(probe_timeout_s)
        # one failover pass over every replica plus one second chance:
        # enough to ride out the kill->quarantine window without
        # retrying forever into a fully-dead fleet
        self.max_attempts = int(max_attempts) or (2 * len(self.replicas))
        self._state: Dict[str, _ReplicaState] = {
            r.name: _ReplicaState(CircuitBreaker(
                f"fleet/{r.name}", threshold=int(breaker_threshold),
                reset_s=float(breaker_reset_s)))
            for r in self.replicas}
        self._rr = itertools.count()  # round-robin cursor
        self._lock = threading.Lock()
        self._inflight = 0
        self._draining = False
        self._stop = threading.Event()
        self._probe_thread: Optional[threading.Thread] = None
        # blocking replica I/O (HTTP predicts, scrapes) rides here so
        # the event loop keeps routing while a replica is slow
        self._io_executor = ThreadPoolExecutor(
            max_workers=max(8, 2 * len(self.replicas)),
            thread_name_prefix="lgbm-fleet-io")
        self._metrics_endpoint = None
        self._publish_meta()

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "FleetRouter":
        """Start the health-probe loop (idempotent)."""
        if self._probe_thread is None or not self._probe_thread.is_alive():
            self._stop.clear()
            self._probe_thread = threading.Thread(
                target=self._probe_loop, name="lgbm-fleet-probe",
                daemon=True)
            self._probe_thread.start()
        return self

    def stop(self) -> None:
        """Stop probing and release the I/O executor (no drain — use
        ``drain()`` first for the graceful path)."""
        self._stop.set()
        if self._probe_thread is not None:
            self._probe_thread.join(timeout=5)
            self._probe_thread = None
        self._io_executor.shutdown(wait=False)
        if self._metrics_endpoint is not None:
            self._metrics_endpoint.close()
            self._metrics_endpoint = None

    def begin_drain(self) -> None:
        """Stop admitting fleet requests (idempotent): the fleet
        ``/readyz`` deregisters immediately, while requests already
        admitted keep routing — replica servers only begin their own
        drain inside :meth:`drain`, AFTER the fleet's in-flight count
        hits zero, so an admitted request is never shed by its own
        shutdown. Subprocess replicas drain on their own SIGTERM."""
        if self._draining:
            return
        self._draining = True
        global_metrics.inc_counter("fleet/drains")
        if global_flightrec.armed:
            global_flightrec.record("fleet_drain", inflight=self._inflight)

    async def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful fleet drain: stop admitting, wait (bounded) for
        in-flight requests, drain in-process replicas, stop probing.
        Returns True when everything flushed within the timeout."""
        self.begin_drain()
        deadline = time.perf_counter() + max(float(timeout_s), 0.0)
        while self._inflight > 0 and time.perf_counter() < deadline:
            await asyncio.sleep(0.002)
        ok = self._inflight == 0
        for rep in self.replicas:
            if isinstance(rep, InProcessReplica):
                rep.server.begin_drain()
                ok = await rep.server.drain(
                    timeout_s=max(deadline - time.perf_counter(), 0.0)) \
                    and ok
        self.stop()
        if global_flightrec.armed:
            global_flightrec.record("fleet_drained", ok=ok)
        return ok

    # -- health state machine -------------------------------------------
    def _probe_loop(self) -> None:
        while not self._stop.is_set():
            self.probe_once()
            self._stop.wait(self.probe_interval_s)

    def probe_once(self) -> None:
        """One probe sweep (the loop body; callable directly in tests)."""
        for rep in self.replicas:
            st = self._state[rep.name]
            try:
                alive, ready = rep.probe(self.probe_timeout_s)
            except Exception:
                alive, ready = False, False
            st.up = bool(alive)
            if alive and ready:
                st.ok_streak += 1
                st.fail_streak = 0
            else:
                st.fail_streak += 1
                st.ok_streak = 0
            if not st.quarantined and st.fail_streak >= self.fail_threshold:
                self._quarantine(rep.name, st)
            elif st.quarantined and st.ok_streak >= self.ok_threshold:
                self._reinstate(rep.name, st)
        self._publish_meta()

    def _quarantine(self, name: str, st: _ReplicaState) -> None:
        st.quarantined = True
        global_metrics.inc_counter("fleet/quarantines")
        if global_flightrec.armed:
            global_flightrec.record("fleet_quarantine", replica=name,
                                    up=st.up, fail_streak=st.fail_streak)

    def _reinstate(self, name: str, st: _ReplicaState) -> None:
        st.quarantined = False
        global_metrics.inc_counter("fleet/reinstates")
        if global_flightrec.armed:
            global_flightrec.record("fleet_reinstate", replica=name,
                                    ok_streak=st.ok_streak)

    def _publish_meta(self) -> None:
        global_metrics.set_meta("fleet", {
            "replicas": len(self.replicas),
            "replica_up": {r.name: int(self._state[r.name].up)
                           for r in self.replicas},
            "replica_quarantined": {
                r.name: int(self._state[r.name].quarantined)
                for r in self.replicas},
        })

    def healthy_replicas(self) -> List:
        return [r for r in self.replicas
                if not self._state[r.name].quarantined]

    # -- routing ---------------------------------------------------------
    def _pick(self, exclude: Optional[set] = None):
        """Next in-rotation replica, round-robin; quarantined and
        excluded (already tried this request) replicas are skipped.
        Falls back to ANY in-rotation replica when every one was tried
        (a second chance beats failing the request), then None."""
        pool = self.healthy_replicas()
        if not pool:
            return None
        fresh = [r for r in pool if not exclude or r.name not in exclude]
        pick_from = fresh or pool
        return pick_from[next(self._rr) % len(pick_from)]

    async def predict(self, name: str, data, raw_score: bool = False
                      ) -> np.ndarray:
        """Serve one request through the fleet. Bit-identical to any
        single replica's answer (pack contract); survives replica death
        mid-request via failover; sheds only when the fleet is draining
        or every attempt on every replica failed."""
        if self._draining:
            global_metrics.inc_counter("resilience/drain_rejected")
            raise ServerOverloaded(
                "fleet is draining (shutdown requested): not admitting "
                "new requests", retry_after_s=0.0)
        x = np.asarray(data, np.float64)
        global_metrics.inc_counter("fleet/requests")
        with self._lock:
            self._inflight += 1
        try:
            return await self._route(name, x, raw_score)
        finally:
            with self._lock:
                self._inflight -= 1

    async def _route(self, name: str, x: np.ndarray,
                     raw_score: bool) -> np.ndarray:
        tried: set = set()
        last_exc: Optional[BaseException] = None
        for attempt in range(self.max_attempts):
            rep = self._pick(exclude=tried)
            if rep is None:
                break  # whole fleet quarantined
            st = self._state[rep.name]
            try:
                probe_held = st.breaker.admit()
            except CircuitOpenError as exc:
                tried.add(rep.name)
                last_exc = exc
                continue
            try:
                out = await self._dispatch_hedged(rep, name, x, raw_score)
            except (DeadlineExceeded, asyncio.CancelledError):
                # load condition, not a replica fault: no failover (a
                # request past its deadline is dead on every replica)
                if probe_held:
                    st.breaker.release_probe()
                raise
            except ServerOverloaded as exc:
                # the replica shed (bounded admission / its own drain):
                # not a fault verdict, but another replica may have room
                if probe_held:
                    st.breaker.release_probe()
                self._note_failover(rep.name, attempt, exc)
                tried.add(rep.name)
                last_exc = exc
                continue
            except Exception as exc:
                # replica death / transient exhausted: breaker failure
                # + failover to the next healthy replica
                st.breaker.record_failure()
                st.fail_streak += 1  # dispatch faults feed quarantine too
                self._note_failover(rep.name, attempt, exc)
                tried.add(rep.name)
                last_exc = exc
                continue
            st.breaker.record_success()
            return out
        if last_exc is not None:
            raise last_exc
        raise ServerOverloaded(
            f"no replica in rotation ({len(self.replicas)} configured, "
            "all quarantined)", retry_after_s=self.probe_interval_s)

    def _note_failover(self, name: str, attempt: int,
                       exc: BaseException) -> None:
        global_metrics.inc_counter("fleet/failovers")
        if global_flightrec.armed:
            global_flightrec.record("fleet_failover", replica=name,
                                    attempt=attempt,
                                    error=type(exc).__name__)

    async def _dispatch(self, rep, name: str, x: np.ndarray,
                        raw_score: bool) -> np.ndarray:
        if isinstance(rep, InProcessReplica):
            return await rep.predict(name, x, raw_score=raw_score)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._io_executor, rep.predict_blocking, name, x, raw_score)

    async def _dispatch_hedged(self, rep, name: str, x: np.ndarray,
                               raw_score: bool) -> np.ndarray:
        """Primary dispatch with an optional hedge: if the primary has
        not answered within ``hedge_s``, fire a duplicate on another
        healthy replica and return whichever answers first. When both
        complete, the answers must be bit-identical — the failover
        safety argument, asserted in the hot path."""
        primary = asyncio.ensure_future(
            self._dispatch(rep, name, x, raw_score))
        if self.hedge_s <= 0:
            return await primary
        try:
            return await asyncio.wait_for(asyncio.shield(primary),
                                          self.hedge_s)
        except asyncio.TimeoutError:
            pass
        except Exception:
            raise  # primary failed fast: the failover loop handles it
        alt = self._pick(exclude={rep.name})
        if alt is None:
            return await primary  # nobody to hedge on
        global_metrics.inc_counter("fleet/hedges")
        if global_flightrec.armed:
            global_flightrec.record("fleet_hedge", primary=rep.name,
                                    hedge=alt.name)
        secondary = asyncio.ensure_future(
            self._dispatch(alt, name, x, raw_score))
        done, pending = await asyncio.wait(
            {primary, secondary}, return_when=asyncio.FIRST_COMPLETED)
        winner_out, winner_exc = None, None
        for fut in done:
            if fut.exception() is None:
                winner_out = fut.result()
                break
            winner_exc = fut.exception()
        if winner_out is None:
            # every completed future failed; the still-pending one is
            # the last hope
            if pending:
                return await next(iter(pending))
            raise winner_exc
        if pending:
            # let the loser finish in the background and hold it to the
            # bit-parity contract when it does
            loser = next(iter(pending))
            loser.add_done_callback(
                lambda fut, ref=winner_out: self._check_hedge_parity(
                    fut, ref))
        else:
            for fut in done:
                if fut.exception() is None and fut.result() is not \
                        winner_out:
                    self._assert_parity(winner_out, fut.result())
        global_metrics.inc_counter("fleet/hedge_wins")
        return winner_out

    def _check_hedge_parity(self, fut: "asyncio.Future", ref) -> None:
        if fut.cancelled() or fut.exception() is not None:
            return  # the loser died; the winner already answered
        self._assert_parity(ref, fut.result())

    def _assert_parity(self, a, b) -> None:
        same = (np.asarray(a).shape == np.asarray(b).shape
                and np.array_equal(np.asarray(a), np.asarray(b)))
        if not same:
            global_metrics.inc_counter("fleet/parity_violations")
            if global_flightrec.armed:
                global_flightrec.record("fleet_parity_violation")
            raise AssertionError(
                "hedged replicas returned different bits for the same "
                "request — the pack contract (PR-3) is broken")

    # -- observability ----------------------------------------------------
    def scrape_replicas(self) -> Dict[str, str]:
        """Each in-rotation replica's own /metrics document (the
        aggregator input). Quarantined/dead replicas are skipped — a
        scrape must not block on a corpse."""
        out: Dict[str, str] = {}
        for rep in self.healthy_replicas():
            try:
                out[rep.name] = rep.metrics_text()
            except Exception:
                pass
        return out

    def start_metrics_endpoint(self, port: int = 0,
                               host: Optional[str] = None):
        """Fleet-level /metrics (+ /healthz, /readyz): the process-wide
        obs document — which includes the fleet counters and the
        per-replica gauges from meta["fleet"]. Ready while at least one
        replica is in rotation and the fleet is not draining."""
        from ..obs.export import MetricsHTTPEndpoint, render_openmetrics
        if host is None:
            host = os.environ.get("LGBM_TPU_METRICS_HOST", "") \
                or "127.0.0.1"
        self._metrics_endpoint = MetricsHTTPEndpoint(
            render_openmetrics,
            ready_fn=lambda: (not self._draining
                              and bool(self.healthy_replicas())),
            port=port, host=host)
        return self._metrics_endpoint

    def stats(self) -> Dict[str, Any]:
        return {
            "replicas": {
                r.name: {"up": self._state[r.name].up,
                         "quarantined": self._state[r.name].quarantined,
                         "breaker": self._state[r.name].breaker.state}
                for r in self.replicas},
            "inflight": self._inflight,
            "draining": self._draining,
            "counters": {k: v for k, v in
                         sorted(global_metrics.counters.items())
                         if k.startswith("fleet/")},
        }


def aggregate_counter_totals(texts: Dict[str, str]) -> Dict[str, float]:
    """Merge replica ``/metrics`` scrapes into fleet-wide counter
    totals: every ``*_total`` family summed across replicas (labels
    ignored — the per-replica breakdown is what the individual scrape
    is for). Pure text processing, usable on any OpenMetrics input."""
    totals: Dict[str, float] = {}
    for text in texts.values():
        for line in text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            name_part, _, value = line.rpartition(" ")
            family = name_part.split("{", 1)[0].strip()
            if not family.endswith("_total"):
                continue
            try:
                totals[family] = totals.get(family, 0.0) + float(value)
            except ValueError:
                continue
    return totals


# ----------------------------------------------------------------------
# fleet construction + the subprocess replica protocol


def build_inprocess_fleet(model_str: str, cfg,
                          n_replicas: Optional[int] = None
                          ) -> FleetRouter:
    """N in-process replicas, each its own registry + ModelServer (the
    shared model tier is the model STRING — each replica packs it
    independently, and the pack contract makes the packs bit-identical).
    For tests and ``bench.py --fleet``; the chaos validator uses real
    subprocesses instead."""
    from .server import registry_from_config, server_from_config
    n = int(n_replicas if n_replicas is not None
            else getattr(cfg, "serve_fleet_replicas", 3))
    replicas = []
    for i in range(n):
        registry = registry_from_config(cfg)
        registry.load("default", model_str=model_str)
        replicas.append(InProcessReplica(
            f"r{i}", server_from_config(registry, cfg)))
    return FleetRouter(
        replicas,
        probe_interval_ms=getattr(cfg, "serve_probe_interval_ms", 50.0),
        hedge_ms=getattr(cfg, "serve_hedge_ms", 0.0),
        breaker_threshold=getattr(cfg, "serve_breaker_threshold", 5),
        breaker_reset_s=getattr(cfg, "serve_breaker_reset_s", 30.0))


class ReplicaHTTPEndpoint:
    """The subprocess replica's HTTP front: ``POST /predict`` next to
    the stock GET /metrics, /healthz, /readyz. Handler threads submit
    coroutines onto the replica's event loop and block on the result —
    the asyncio server keeps coalescing while many requests wait."""

    def __init__(self, server: ModelServer, loop: asyncio.AbstractEventLoop,
                 port: int = 0, host: str = "127.0.0.1",
                 request_timeout_s: float = 60.0):
        import http.server

        from ..obs.export import negotiate_content_type, render_openmetrics

        def render() -> str:
            return render_openmetrics(extra_gauges={
                "lgbmtpu_serve_pack_bytes": server.registry.pack_bytes(),
                "lgbmtpu_serve_models": len(server.registry),
            })

        timeout_s = float(request_timeout_s)

        class Handler(http.server.BaseHTTPRequestHandler):
            def _send(self, code: int, body: bytes,
                      headers: Optional[Dict[str, str]] = None,
                      ctype: str = "application/octet-stream") -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self) -> None:
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    body = render().encode()
                    self._send(200, body, ctype=negotiate_content_type(
                        self.headers.get("Accept")))
                elif path == "/healthz":
                    self._send(200, b"ok\n", ctype="text/plain")
                elif path == "/readyz":
                    ok = bool(server.ready)
                    self._send(200 if ok else 503,
                               b"ready\n" if ok else b"not ready\n",
                               ctype="text/plain")
                else:
                    self._send(404, b"not found\n", ctype="text/plain")

            def do_POST(self) -> None:
                if self.path.split("?", 1)[0] != "/predict":
                    self._send(404, b"not found\n", ctype="text/plain")
                    return
                try:
                    shape = tuple(int(d) for d in
                                  self.headers["X-Shape"].split(","))
                    n = int(self.headers.get("Content-Length", "0"))
                    x = np.frombuffer(self.rfile.read(n),
                                      np.float64).reshape(shape)
                    name = self.headers.get("X-Model", "default")
                    raw = self.headers.get("X-Raw-Score", "0") == "1"
                except Exception as exc:
                    self._send(400, f"bad request: {exc}\n".encode(),
                               ctype="text/plain")
                    return
                fut = asyncio.run_coroutine_threadsafe(
                    server.predict(name, x, raw_score=raw), loop)
                try:
                    out = np.ascontiguousarray(fut.result(timeout_s),
                                               np.float64)
                except Exception as exc:
                    fut.cancel()
                    kind = type(exc).__name__
                    code = _ERROR_STATUS.get(kind, 500)
                    self._send(code, f"{exc}\n".encode(),
                               headers={"X-Error": kind},
                               ctype="text/plain")
                    return
                self._send(200, out.tobytes(), headers={
                    "X-Shape": ",".join(str(d) for d in out.shape)})

            def log_message(self, *args) -> None:
                pass  # request logging rides the obs counters instead

        self._httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="lgbm-replica-http",
            daemon=True)
        self._thread.start()

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def _replica_main(argv: Optional[List[str]] = None) -> int:
    """Entry of one subprocess replica: ``python -m
    lightgbm_tpu.serve.fleet --replica model=<file> port=<p>
    [key=value ...]``.

    Builds the same registry/server serve_file does, serves the replica
    HTTP protocol, prints one ``READY <port>`` line (the spawner's
    rendezvous), and on SIGTERM drains and exits ``EXIT_PREEMPTED``."""
    import signal
    import sys

    from ..config import Config
    from ..resilience.errors import EXIT_PREEMPTED
    from .server import registry_from_config, server_from_config

    args = list(argv if argv is not None else sys.argv[1:])
    if args and args[0] == "--replica":
        args = args[1:]
    params: Dict[str, Any] = {}
    for tok in args:
        if "=" not in tok:
            raise SystemExit(f"replica args are key=value, got {tok!r}")
        k, v = tok.split("=", 1)
        params[k.strip()] = v.strip()
    model_file = params.pop("model", "")
    port = int(params.pop("port", "0"))
    if not model_file:
        raise SystemExit("replica needs model=<file>")

    cfg = Config.from_params(params)
    registry = registry_from_config(cfg)
    registry.load("default", model_file=model_file, validate=True)
    server = server_from_config(registry, cfg)

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    endpoint = ReplicaHTTPEndpoint(server, loop, port=port)
    exit_code = {"code": 0}

    def _on_sigterm() -> None:
        async def _drain_and_stop() -> None:
            server.begin_drain()  # /readyz deregisters immediately
            await server.drain()
            await server.close()
            exit_code["code"] = EXIT_PREEMPTED
            loop.stop()
        asyncio.ensure_future(_drain_and_stop())

    loop.add_signal_handler(signal.SIGTERM, _on_sigterm)
    print(f"READY {endpoint.port}", flush=True)
    try:
        loop.run_forever()
    finally:
        endpoint.close()
        loop.close()
    return exit_code["code"]


if __name__ == "__main__":  # pragma: no cover - exercised by check_fleet
    import sys
    sys.exit(_replica_main())
