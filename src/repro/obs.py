"""In-program spans: where the host's time goes inside the summarizer.

A :class:`SpanRecorder` keeps the spans the program opens in a bounded
in-memory ring.  Each span records its name, its start and end on
``time.perf_counter_ns()``, its own id, its parent's id (the span open on
the same thread when it began, 0 for a root) and a request id: the chunk's
journal sequence number for write spans, ``(epoch, batch)`` of the query
view for read spans.  A child without a request id of its own takes its
parent's.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
while a profiler trace is being taken, so the trace holds the program's
spans on the profiler's clock next to the device's events.  Recording
fetches nothing from the device.

``enabled`` is on by default; with it off, ``span()`` makes one attribute
check and returns a shared no-op context.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import math
import threading
import time
from typing import List, NamedTuple

import jax

_Annotation = jax.profiler.TraceAnnotation

RING = 1 << 16          # spans kept; older ones are dropped and counted

_NULL = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    start_ns: int       # time.perf_counter_ns()
    end_ns: int
    span_id: int
    parent_id: int      # 0: a root span
    request_id: object

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class _Open:
    """One open span: a context manager that records itself on exit."""

    __slots__ = ("rec", "name", "rid", "sid", "parent", "start", "ann",
                 "stack")

    def __init__(self, rec: "SpanRecorder", name: str, rid) -> None:
        self.rec, self.name, self.rid = rec, name, rid

    def __enter__(self) -> "_Open":
        rec = self.rec
        stack = self.stack = rec._stack()
        if stack:
            top = stack[-1]
            self.parent = top.sid
            if self.rid is None:
                self.rid = top.rid
        else:
            self.parent = 0
        self.sid = next(rec._ids)
        stack.append(self)
        # the profiler's own annotation only while a trace is being taken
        if _Annotation.is_enabled():
            self.ann = _Annotation(self.name)
            self.ann.__enter__()
        else:
            self.ann = None
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.stack.pop()
        rec = self.rec
        ring = rec._ring
        if len(ring) == ring.maxlen:
            rec.dropped += 1
        # a plain tuple here; spans() makes the Span
        ring.append((self.name, self.start, end, self.sid, self.parent,
                     self.rid))


class SpanRecorder:
    """Bounded ring of host spans (see the module docstring)."""

    def __init__(self, maxlen: int = RING) -> None:
        self.enabled = True
        self.dropped = 0            # spans pushed out of the full ring
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, request_id=None):
        """Context manager recording one span named ``name``."""
        if not self.enabled:
            return _NULL
        return _Open(self, name, request_id)

    def spans(self, prefix: str = "", t_from: float = -math.inf,
              t_to: float = math.inf) -> List[Span]:
        """Recorded spans whose name starts with ``prefix`` and which start
        and end inside ``[t_from, t_to]``, in seconds on the clock of
        ``time.perf_counter()``; in the order they ended."""
        lo, hi = t_from * 1e9, t_to * 1e9
        return [Span(*s) for s in list(self._ring)
                if s[0].startswith(prefix) and s[1] >= lo and s[2] <= hi]
