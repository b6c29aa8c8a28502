#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It fails at once unless JAX's default backend is a TPU with
the chips the cell asks for. Set-up makes the data from the seed, bins it
through ``lgb.Dataset`` and runs the first boosting iteration of one
``lgb.train`` call (trace, compile or cache load, run). The window is the
whole iterations of that same call that follow, each stopped by
``jax.block_until_ready`` on the training scores; it closes at the first
iteration boundary at or after ``--seconds``. With ``--trace 1`` one more
iteration runs under the JAX profiler and the per-layer metrics are read
from its trace. Then the plain reference (``reference.py``) follows what
the timed path produced, ``judge`` holds each number it compared to the
cell's limit, and the last line of standard output is the result as one
JSON object.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own (``manifest.py``); this file
knows none of them by name.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # process start, as near as Python can see it

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (os.path.join(BENCH_DIR, "metrics"), BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import data as bench_data          # noqa: E402
import manifest                    # noqa: E402
import reference                   # noqa: E402
import roofline                    # noqa: E402
import xtrace                      # noqa: E402

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def log(msg: str = "") -> None:
    print(msg, flush=True)


def host_memory() -> str:
    """This process's resident memory now and at its peak, for the log: the
    machine with one chip ends a run at 40 GiB."""
    import resource
    with open("/proc/self/statm", encoding="ascii") as fh:
        now = int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return f"host memory {now / 2**30:.1f} GiB now, {peak / 2**30:.1f} peak"


class Programs:
    """Counts every program this process acquires (compiled, or loaded
    from the persistent cache) and every persistent-cache hit, by JAX's
    own monitoring events."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.acquired = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.seconds = []         # of every acquisition that took over 1 s
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE:
            self.acquired += 1
            if duration >= 1.0:
                self.seconds.append(round(duration, 1))

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1
        elif event == _CACHE_MISS:
            self.cache_misses += 1


def find_devices(chips: int, require_chip: bool) -> dict:
    import jax
    backend = jax.default_backend()
    if require_chip and backend != "tpu":
        sys.exit(f"benchmarks/run.py: needs a TPU, JAX's default backend is "
                 f"{backend!r} (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    devs = jax.devices()
    if require_chip and len(devs) < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chip(s), JAX "
                 f"reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Window:
    """The callbacks of the one ``lgb.train`` call: ``__call__`` runs after
    every iteration, stops the clock on the scores, closes the window, and
    in a traced run puts one more iteration under the profiler."""

    def __init__(self, seconds: float, trace: bool, programs: Programs,
                 trace_dir: str):
        self.seconds = float(seconds)
        self.trace = trace
        self.programs = programs
        self.trace_dir = trace_dir
        self.train_ready = None   # clock when the first iteration starts
        self.stamps = []          # clock at the end of every iteration
        self.acquired = []        # programs acquired by then
        self.closed_at = None     # index into stamps where the window closed
        self.traced_from = None   # clock when the profiler was on
        self.traced_s = None      # length of the traced iteration
        self._span = None

    def before_first(self, env) -> None:
        if self.train_ready is None:
            self.train_ready = time.perf_counter()

    before_first.before_iteration = True

    def __call__(self, env) -> None:
        import jax
        from lightgbm_tpu.callback import EarlyStopException
        jax.block_until_ready(env.model._gbdt.scores)
        now = time.perf_counter()
        self.stamps.append(now)
        self.acquired.append(self.programs.acquired)
        if self._span is not None:        # the traced iteration just ended
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()
            self.traced_s = now - self.traced_from
            raise EarlyStopException(env.iteration, [])
        if len(self.stamps) < 2 or now - self.stamps[0] < self.seconds:
            return
        self.closed_at = len(self.stamps) - 1
        if not self.trace:
            raise EarlyStopException(env.iteration, [])
        jax.profiler.start_trace(self.trace_dir)
        self._span = jax.profiler.TraceAnnotation(
            xtrace.HOST_SPAN_PREFIX + "iteration")
        self._span.__enter__()
        self.traced_from = time.perf_counter()

    @property
    def iterations(self) -> int:
        return self.closed_at

    @property
    def window_s(self) -> float:
        return self.stamps[self.closed_at] - self.stamps[0]

    @property
    def iteration_seconds(self) -> list:
        s = self.stamps[:self.closed_at + 1]
        return [b - a for a, b in zip(s, s[1:])]

    @property
    def compiled_after_warmup(self) -> int:
        return self.acquired[-1] - self.acquired[0]


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise manifest.ManifestError(f"per-layer metric {name!r} has no "
                                     f"reader {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layers(cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read; a reader that returns None is left out of the line."""
    out = {}
    for name in cell.per_layer:
        value = load_reader(name)(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": cell.units[name]}
    return out


def breakdown(trace: "xtrace.Trace") -> dict:
    events = [e for evs in trace.devices.values() for e in evs]
    by_kind = {}
    for name, ns in xtrace.self_times(events).items():
        kind = xtrace.short_name(name)
        by_kind[kind] = by_kind.get(kind, 0.0) + ns
    own = sorted(by_kind.items(), key=lambda kv: -kv[1])
    t0 = min(e[1] for e in events)
    t1 = max(e[1] + e[2] for e in events)
    for name, start, dur in trace.host_spans:
        t0, t1 = min(t0, start), max(t1, start + dur)
    gaps = xtrace.idle_gaps(events, t0, t1)[:10]
    return {"device_ops": [[name, ns / 1e9] for name, ns in own[:10]],
            "idle_gaps": [[xtrace.span_at(trace.host_spans, start + dur / 2),
                           dur / 1e9] for start, dur in gaps]}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit, and a number that is not finite is over it."""
    compared = {}
    correct = True
    for name in reference.NUMBERS:
        value, limit = float(numbers[name]), float(limits[name])
        compared[name] = {"value": value, "limit": limit}
        if not value <= limit:
            correct = False
    return correct, compared


def traced_metrics(cell, window, trace_dir: str, shapes: dict,
                   peaks: dict) -> tuple:
    """(the cell's per-layer metrics, device busy seconds, breakdown) from
    the profiler's trace of the one traced iteration."""
    xplane = xtrace.find_xplane(trace_dir)
    tr = xtrace.read_xplane(xplane)
    events = [e for evs in tr.devices.values() for e in evs]
    log(f"trace {os.path.getsize(xplane) / 2**20:.1f} MiB, {len(events)} "
        f"device events; {host_memory()}")
    if not events:
        raise RuntimeError("the trace holds no device operation")
    busy_s = sum(xtrace.busy_ns(evs) for evs in tr.devices.values()
                 ) / 1e9 / len(tr.devices)
    ctx = {"trace": tr, "events": events, "busy_s": busy_s,
           "window_s": window.traced_s, "shapes": shapes, "peaks": peaks,
           "iteration_seconds": window.iteration_seconds}
    return read_layers(cell, ctx), busy_s, breakdown(tr)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, rows: int = None,
             stand_ins: tuple = ()) -> dict:
    """One run. ``main`` passes none of the keywords: ``rows`` and
    ``require_chip=False`` are for the tests (a tiny size on the CPU);
    ``stand_ins`` names answers of the reference put in the program's
    place (``reference.STAND_INS``: the control and the planted faults),
    each judged after the program's own as that was, under the key
    ``stand_ins`` of the result (``tests/test_faults.py``)."""
    import numpy as np
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if require_chip and platforms and "tpu" not in platforms.split(","):
        sys.exit(f"benchmarks/run.py: needs a TPU, JAX_PLATFORMS is "
                 f"{platforms!r}")
    import jax
    from lightgbm_tpu import compile_cache, native
    compile_cache.configure("auto")
    programs = Programs()
    import lightgbm_tpu as lgb

    cfg, traffic = cell.config, cell.traffic
    n = int(rows or traffic["rows"])
    f = int(cfg["num_features"])
    params = dict(cfg["params"], verbosity=-1)

    # Host work first, the chip after it. The TPU runtime takes 13 GiB of
    # this process's memory when it starts (my chip runs, PR 25), the raw
    # rows are 18 GiB at 1.2M x 2000 and binning them 9 more, and the machine
    # with one chip ends a run at 40 GiB. Binning touches no device, so the
    # rows are made, binned and let go of before JAX is asked for one.
    # ``lgb.Dataset`` keeps the raw rows whatever ``free_raw_data`` says, so
    # they are let go of by hand; the reference makes them again from the
    # seed, chunk by chunk.
    t = time.perf_counter()
    x, y = bench_data.make_data(n, f, seed, cfg["data"])
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(x, label=y, params=params,
                     feature_name=[f"Column_{i}" for i in range(f)])
    ds.construct()
    t_bin = time.perf_counter() - t
    ds.data = ds._binned.raw_data = None
    del x
    log("binner: " + ("native library loaded" if native.available()
                      else "NumPy path (no native library)")
        + "; " + host_memory())
    t = time.perf_counter()
    device = find_devices(cell.chips, require_chip)
    t_dev = time.perf_counter() - t
    log(f"device: {device}; jax {jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir} (JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', 'unset')}); "
        + host_memory())

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = Window(seconds, trace, programs, trace_dir)
    t_train = time.perf_counter()
    try:
        bst = lgb.train(params, ds, num_boost_round=1_000_000,
                        callbacks=[window.before_first, window])
        setup_s = window.stamps[0] - _T0
        t_ready = window.train_ready
        log(f"set-up {setup_s:.3f} s: imports "
            f"{t_train - _T0 - t_gen - t_bin - t_dev:.3f}, generate "
            f"{t_gen:.3f}, bin {t_bin:.3f}, device {t_dev:.3f}, booster and "
            f"upload {t_ready - t_train:.3f}, "
            f"first iteration {window.stamps[0] - t_ready:.3f} "
            f"({window.acquired[0]} programs: {programs.cache_hits} "
            f"persistent-cache hits, {programs.cache_misses} misses; "
            f"acquisitions over 1 s: {programs.seconds})")
        log("window iterations, s: " + " ".join(
            f"{s:.4f}" for s in window.iteration_seconds)
            + "; " + host_memory())
        peak = memory_peak_bytes(cell.chips)
        scores = np.asarray(bst._gbdt.scores)[0][:n]
        model = bst.model_to_string()
        del bst, ds
        if window.compiled_after_warmup:
            raise RuntimeError(
                f"{window.compiled_after_warmup} program(s) were compiled or "
                "loaded after the warm-up iteration")
        device_out = dict(device, memory_peak_bytes=peak)
        extra = {}
        if trace:
            shapes = {"rows": n, "features": f,
                      "max_bin": int(params["max_bin"]),
                      "num_leaves": int(params["num_leaves"]),
                      "int8": bool(params.get("use_quantized_grad"))}
            metrics, busy_s, extra["breakdown"] = traced_metrics(
                cell, window, trace_dir, shapes,
                roofline.device_peaks(device["kind"]))
            device_out.update(busy_s=busy_s, window_s=window.traced_s)
        else:
            metrics = {"train_iters_per_s": window.iterations / window.window_s,
                       "setup_s": setup_s}
            metrics = {name: {"value": value, "unit": cell.units[name]}
                       for name, value in metrics.items()
                       if name in cell.end_to_end}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference, once the window has closed and the peak has been read
    t = time.perf_counter()
    chunks = bench_data.Chunks(n, f, seed, cfg["data"])
    threads = bench_data.host_threads()
    trees = reference.parse_model(model)
    numbers = reference.compare(chunks, cfg["params"], trees, scores,
                                len(window.stamps), cell.check, seed, threads,
                                log=log)
    correct, compared = judge(numbers, cell.limits)
    log(f"reference took {time.perf_counter() - t:.3f} s; {host_memory()}")
    if stand_ins:
        del scores
        extra["stand_ins"] = {}
        made = reference.stand_ins(chunks, cfg["params"],
                                   trees[:int(cell.check["trees"])],
                                   stand_ins, threads)
        for kind in stand_ins:
            log(f"the reference in the program's place, {kind}:")
            its_trees, its_scores = made.pop(kind)
            ok, its = judge(reference.compare(
                chunks, cfg["params"], its_trees, its_scores, len(its_trees),
                cell.check, seed, threads, log=log), cell.limits)
            extra["stand_ins"][kind] = {"correct": ok, "compared": its}
    return {"correct": correct, "attempted": window.iterations,
            "failed": int(numbers["trees_missing"]), "metrics": metrics,
            "device": device_out, **extra, "compared": compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = manifest.load_cell(args.workload)
    except manifest.ManifestError as exc:
        sys.exit(f"benchmarks/run.py: {exc}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, pair in result["compared"].items():
        print(f"compared {name}: {pair['value']!r} limit {pair['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    log(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
