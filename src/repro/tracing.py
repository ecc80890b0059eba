"""Spans and counters of the program, on the profiler's clock.

:func:`span` always enters a ``jax.profiler.TraceAnnotation``, so a span
lands in any profile on the same clock as the device's operations. While
a profiler session records (``TraceAnnotation.is_enabled()``), it is also
appended to a bounded in-memory buffer, and :func:`count` adds to named
counters; otherwise both keep nothing. There is no option to turn the
recorder on: an operator profiles the process, for instance with
``jax.profiler.trace(dir)`` around their own serving code, and then reads
:func:`snapshot`. :func:`reset` clears the buffer between sessions.

One recorder serves the process, as the profiler does; a test may make its
own :class:`Recorder`.
"""
from __future__ import annotations

import time
from array import array
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: Spans kept before further ones are dropped (and counted).
CAPACITY = 1 << 16


class Span(NamedTuple):
    """One recorded span: ``start_ns`` and ``end_ns`` from
    ``time.perf_counter_ns``; ``parent`` is the index of the enclosing
    recorded span (or None); ``call`` the call id given to the outermost
    span, shared by the spans inside it."""

    name: str
    start_ns: int
    end_ns: int | None
    parent: int | None
    call: object


class _Recorded:
    """A span entered while the profiler records."""

    __slots__ = ("rec", "name", "call", "ann", "index")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.rec, self.name = rec, name
        self.call = attrs.get("call")
        self.ann = TraceAnnotation(name, **attrs)

    def __enter__(self):
        self.ann.__enter__()
        self.index = self.rec._open(self.name, self.call)
        return self

    def __exit__(self, *exc):
        self.rec._close(self.index)
        return self.ann.__exit__(*exc)


class Recorder:
    """A bounded buffer of spans and a table of counters, filled only
    while a profiler session records. For a single-threaded caller: spans
    nest on one plain stack.

    The buffer is columnar (strings, ints and ``array`` columns), so that
    recording creates no object the cyclic garbage collector tracks and
    starts no collection in the recorded program."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.reset()

    def reset(self) -> None:
        """Forget every span, counter and drop."""
        self._names: list = []
        self._calls: list = []
        self._parent = array("q")
        self._start = array("q")
        self._end = array("q")
        self._counters: dict = {}
        self._dropped = 0
        self._stack: list = []       # indices of the open recorded spans

    def span(self, name: str, **attrs):
        """A context manager around a block named ``name``; ``attrs`` go to
        the profiler's event, and ``call=`` also to the recorded span and
        the spans inside it."""
        if not TraceAnnotation.is_enabled():
            return TraceAnnotation(name, **attrs)
        return _Recorded(self, name, attrs)

    def _open(self, name: str, call) -> int:
        """Record the start of a span: its index, -1 for a dropped span."""
        parent = self._stack[-1] if self._stack else -1
        if call is None and parent >= 0:
            call = self._calls[parent]
        index = len(self._names)
        if index >= self.capacity:
            self._dropped += 1
            return -1
        self._names.append(name)
        self._calls.append(call)
        self._parent.append(parent)
        self._start.append(time.perf_counter_ns())
        self._end.append(-1)
        self._stack.append(index)
        return index

    def _close(self, index: int) -> None:
        end = time.perf_counter_ns()
        # a dropped span, or one opened before a reset, is not on the stack
        if self._stack and self._stack[-1] == index:
            self._stack.pop()
            self._end[index] = end

    def count(self, name: str, n: float = 1) -> None:
        """Add ``n`` to counter ``name``, while the profiler records."""
        if TraceAnnotation.is_enabled():
            self._counters[name] = self._counters.get(name, 0) + n

    def snapshot(self) -> dict:
        """What was recorded: ``spans`` (:class:`Span` records in the order
        they were entered; one still open has ``end_ns`` None),
        ``counters``, ``dropped``, and per span name of the closed ones
        ``count``, ``total_s``, ``self_s`` (total less the time of the
        spans inside) and ``longest_s``."""
        spans = [Span(n, st, None if e < 0 else e, None if p < 0 else p, c)
                 for n, st, e, p, c in zip(self._names, self._start,
                                           self._end, self._parent,
                                           self._calls)]
        counters, dropped = dict(self._counters), self._dropped
        closed = [(i, s) for i, s in enumerate(spans) if s.end_ns is not None]
        inner_ns = [0] * len(spans)
        for _, s in closed:
            if s.parent is not None:
                inner_ns[s.parent] += s.end_ns - s.start_ns
        names: dict = {}
        for i, s in closed:
            d = s.end_ns - s.start_ns
            st = names.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                           "self_s": 0.0, "longest_s": 0.0})
            st["count"] += 1
            st["total_s"] += d * 1e-9
            st["self_s"] += (d - inner_ns[i]) * 1e-9
            st["longest_s"] = max(st["longest_s"], d * 1e-9)
        return {"spans": spans, "counters": counters, "dropped": dropped,
                "names": names}


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
snapshot = RECORDER.snapshot
reset = RECORDER.reset
