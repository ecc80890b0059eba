"""Reduction of a profiler trace to the benchmark's device numbers.

A run with ``--trace 1`` records its whole measured window with
``jax.profiler.trace`` and wraps it, and each unit of work, in host spans
named ``bench.*`` (``jax.profiler.TraceAnnotation``). This module reads the
``.xplane.pb`` that the profiler writes and computes, over the window:

- busy: the union of the intervals in which an operation ran on a device,
  averaged over the devices used; idle is the window less busy;
- the time of named device operations (the top ones for ``breakdown``);
- collective time (collective operations, and asynchronous collectives
  from start to done), and the part of it in which no other operation ran
  on that device (exposed);
- the idle gaps, each named by the innermost ``bench.*`` host span that
  covers its midpoint.

Only the process that holds the chip can trace it, so this runs in the
benchmark's own process after the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

#: The profiler lines of a TPU plane: one event per operation the core
#: executes, and the spans of asynchronous operations (copies, collectives)
#: from start to done.
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv)", re.IGNORECASE)
TOP = 10


@dataclasses.dataclass
class Trace:
    """Events in nanoseconds on one clock: ``devices`` maps a device plane
    to its operations ``(name, start, end)``, ``async_ops`` to its
    asynchronous operations; ``spans`` are the host's ``bench.*`` spans
    ``(name, start, end)``."""

    devices: dict
    spans: list
    async_ops: dict = dataclasses.field(default_factory=dict)


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def op_name(event_name: str) -> str:
    """A TPU op event is named by its HLO text, ``%fusion.3 = bf16[...]
    fusion(...)``; its name is the instruction's."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: the operations of every TPU device plane,
    and every ``bench.*`` event on the host planes."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    devices: dict = {}
    async_ops: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                into = {OPS_LINE: devices, ASYNC_LINE: async_ops}.get(
                    line.name)
                if into is not None:
                    into[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans, async_ops)


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _minus(a: list, b: list) -> list:
    """Parts of the sorted disjoint intervals ``a`` that ``b`` (sorted,
    disjoint) does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _leaves(events: list) -> list:
    """The operations that hold no other: a ``while`` or ``call`` op spans
    the operations of its body, which are listed after it."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for i, ev in enumerate(events)
            if i + 1 == len(events) or events[i + 1][2] > ev[2]
            or events[i + 1][1] >= ev[2]]


def window_of(trace: Trace) -> tuple:
    spans = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return spans[0]


def _span_at(spans: list, t: float) -> str:
    """The innermost ``bench.*`` span (not the window) covering ``t``."""
    best = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e and (
                best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside any bench span"


def reduce(trace: Trace, window: tuple | None = None) -> dict:
    """The window's device numbers, in seconds, averaged over devices.

    Returns ``busy_s``, ``window_s``, ``idle_s``, ``collective_s``,
    ``exposed_collective_s``, ``op_s`` (seconds per operation name, of the
    operations that hold no others),
    and ``breakdown`` with the top device operations and the longest idle
    gaps named by host span."""
    if not trace.devices:
        raise ValueError("trace holds no TPU device operations")
    lo, hi = window if window is not None else window_of(trace)
    n = len(trace.devices)
    busy = idle = coll = exposed = 0.0
    op_s: dict = defaultdict(float)
    gaps: list = []
    for plane, events in trace.devices.items():
        inside = [(name, max(s, lo), min(e, hi)) for name, s, e in events
                  if e > lo and s < hi]
        in_flight = [(max(s, lo), min(e, hi))
                     for name, s, e in trace.async_ops.get(plane, [])
                     if e > lo and s < hi and COLLECTIVE.search(name)]
        for name, s, e in _leaves(inside):
            op_s[name] += (e - s) / n
        busy_iv = _union([(s, e) for _, s, e in inside])
        busy += _length(busy_iv)
        idle_iv = _minus([(lo, hi)], busy_iv)
        idle += _length(idle_iv)
        gaps.extend(idle_iv)
        coll_iv = _union([(s, e) for name, s, e in inside
                          if COLLECTIVE.search(name)] + in_flight)
        other_iv = _union([(s, e) for name, s, e in inside
                           if not COLLECTIVE.search(name)])
        coll += _length(coll_iv)
        exposed += _length(_minus(coll_iv, other_iv))
    ns = 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy / n * ns,
        "window_s": (hi - lo) * ns,
        "idle_s": idle / n * ns,
        "collective_s": coll / n * ns,
        "exposed_collective_s": exposed / n * ns,
        "devices": n,
        "op_s": {k: v * ns for k, v in op_s.items()},
        "breakdown": {
            "device_ops": [[k, v * ns] for k, v in top_ops],
            "idle_gaps": [[_span_at(trace.spans, (s + e) / 2), (e - s) * ns]
                          for s, e in gaps[:TOP]],
        },
    }
