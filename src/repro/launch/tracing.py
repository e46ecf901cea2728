"""Host spans of the serving scheduler, kept while a JAX profiler
session collects.

An operator gets them the way they already capture a device trace:
``jax.profiler.start_trace(dir)`` ... ``stop_trace()``. With no session
collecting, ``Tracer.span`` returns one shared no-op context and
``begin`` returns at once: the cost is one
``TraceAnnotation.is_enabled()`` call per span. While a session
collects, each span

* enters ``jax.profiler.TraceAnnotation("serve.<name>")``, so that it
  lies in the same ``.xplane.pb`` as the device's operations, on their
  clock;
* is kept in memory as a ``Span``, stamped with ``time.time_ns()``, in
  a list bounded at ``CAP`` spans; spans past it are counted in
  ``dropped``. ``ProfileData`` gives the trace's events from another
  origin, so a reader lays these stamps on the trace by an offset it
  measures (the same span is in both).

Two kinds: ``span(name)`` wraps a boundary of the calling thread's work
and nests (its ``parent`` is the index of the span it ran inside);
``begin(name, rid)`` / ``end(name, rid)`` follow one request across
calls (``rid`` set, no parent). A per-request span that opened while
the session collected is closed by ``end`` even after the session has
stopped; one never closed keeps ``end_ns`` None.
"""

from __future__ import annotations

import contextlib
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

PREFIX = "serve."
CAP = 1 << 16

_NOOP = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int | None       # None while open
    parent: int | None       # index in ``Tracer.spans`` of the enclosing span
    rid: int | None          # the request, for per-request spans


class _Nested:
    """One nested span while it is open."""

    __slots__ = ("tracer", "name", "index", "ann")

    def __init__(self, tracer: Tracer, name: str) -> None:
        self.tracer, self.name = tracer, name

    def __enter__(self) -> _Nested:
        self.ann = TraceAnnotation(PREFIX + self.name)
        self.ann.__enter__()
        stack = self.tracer._stack
        self.index = self.tracer._add(self.name,
                                      stack[-1] if stack else None, None)
        stack.append(self.index)
        return self

    def __exit__(self, *exc) -> None:
        self.tracer._stack.pop()
        if self.index is not None:
            self.tracer._close(self.index)
        self.ann.__exit__(*exc)


class Tracer:
    """The scheduler's spans, in the order they opened (a parent before
    its children)."""

    def __init__(self) -> None:
        self.cap = CAP
        self.spans: list[Span] = []
        self.dropped = 0
        self._stack: list[int | None] = []
        # (name, rid) -> (index in spans, its annotation)
        self._open: dict[tuple[str, int], tuple] = {}

    def span(self, name: str):
        """A context that records ``name`` around the work it wraps."""
        if not TraceAnnotation.is_enabled():
            return _NOOP
        return _Nested(self, name)

    def begin(self, name: str, rid: int) -> None:
        """Open the span ``name`` of request ``rid``; nothing if one is
        already open (the first opening counts) or no session collects."""
        if (name, rid) in self._open or not TraceAnnotation.is_enabled():
            return
        ann = TraceAnnotation(PREFIX + name, rid=rid)
        ann.__enter__()
        self._open[(name, rid)] = (self._add(name, None, rid), ann)

    def end(self, name: str, rid: int) -> None:
        """Close the span ``name`` of request ``rid``, if one is open."""
        got = self._open.pop((name, rid), None)
        if got is None:
            return
        index, ann = got
        if index is not None:
            self._close(index)
        ann.__exit__(None, None, None)

    def _add(self, name: str, parent: int | None, rid: int | None
             ) -> int | None:
        if len(self.spans) >= self.cap:
            self.dropped += 1
            return None
        self.spans.append(Span(name, time.time_ns(), None, parent, rid))
        return len(self.spans) - 1

    def _close(self, index: int) -> None:
        self.spans[index] = self.spans[index]._replace(end_ns=time.time_ns())
