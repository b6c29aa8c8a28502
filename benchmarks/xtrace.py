"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: which operations ran on the device and when.

``read_xplane`` turns the file into plain lists; everything after it works
on those lists, so the arithmetic is checked on a hand-built event list
(``benchmarks/tests/test_xtrace.py``).

An event is ``(name, start_ns, duration_ns, text)``: ``text`` is the
event's string statistics joined, which is where the trace names a custom
call's target.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"          # the per-operation line of a TPU device plane
DEVICE_PLANE = "/device:TPU:"
HOST_SPAN_PREFIX = "bench/"   # the harness's own spans (TraceAnnotation)


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane name -> [event]
    host_spans: list = field(default_factory=list)  # [(name, start, dur)]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            if plane.name.startswith(DEVICE_PLANE) and line.name == OPS_LINE:
                out = trace.devices.setdefault(plane.name, [])
                for ev in events:
                    text = " ".join(str(v) for _, v in ev.stats
                                    if isinstance(v, str))
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.duration_ns), text))
            elif plane.name.startswith("/host:"):
                for ev in events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        trace.host_spans.append(
                            (ev.name, float(ev.start_ns),
                             float(ev.duration_ns)))
    return trace


def merged_intervals(events) -> list:
    """The union of the events' intervals as sorted, disjoint
    ``[start, end]`` pairs."""
    out = []
    for start, end in sorted((e[1], e[1] + e[2]) for e in events):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def busy_ns(events) -> float:
    """Nanoseconds in which at least one operation ran."""
    return sum(end - start for start, end in merged_intervals(events))


def self_times(events) -> dict:
    """name -> nanoseconds of that operation's own time: its duration less
    the part its children cover (a ``while`` spans the operations of its
    body on the same line). Events nest properly on one line."""
    out = {}
    stack = []   # [end, name, duration, covered by children]

    def close():
        end, name, dur, covered = stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, dur - covered)

    for name, start, dur, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            close()
        if stack:
            stack[-1][3] += min(dur, stack[-1][0] - start)
        stack.append([start + dur, name, dur, 0.0])
    while stack:
        close()
    return out


def short_name(name: str) -> str:
    """An operation's kind and result shape from the whole HLO instruction
    the trace gives as its name: ``%fusion.74 = u8[63000000]{0:T(1024)}
    fusion(...)`` becomes ``fusion u8[63000000]``, so that the thirteen
    passes of one kernel add up under one name."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    kind = head.lstrip("%").rstrip("0123456789").rstrip(".")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{kind} {shape}"[:80]


def idle_gaps(events, t0: float, t1: float) -> list:
    """``(start_ns, duration_ns)`` of every stretch of ``[t0, t1]`` in
    which no operation ran, longest first."""
    gaps = []
    at = t0
    for start, end in merged_intervals(events):
        start, end = max(start, t0), min(end, t1)
        if end <= start:
            continue
        if start > at:
            gaps.append((at, start - at))
        at = max(at, end)
    if t1 > at:
        gaps.append((at, t1 - at))
    return sorted(gaps, key=lambda g: -g[1])


def span_at(host_spans, t: float) -> str:
    """The innermost of the harness's host spans that covers ``t``."""
    best = None
    for name, start, dur in host_spans:
        if start <= t <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "outside the harness's spans"
