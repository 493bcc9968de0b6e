"""Host spans: the one recorder the program and its benchmark write to.

A span is ``(name, start_s, end_s)`` on the ``time.perf_counter`` clock,
kept in memory by a :class:`Spans` recorder.  Each span also knows its
parent, the innermost span open on the same thread when it opened, and
its request: one id shared by every span of one call into the engine
(the id of the span that opened the request, inherited by every span
nested under it).  While a profile is being taken (``annotate``), each
span is also written into the profiler's trace as a
``jax.profiler.TraceAnnotation``, so that a trace reduction can name
device idle time by what the host was doing.

The program opens its spans through the module-level :func:`span`,
which writes to the recorder installed by :func:`install` and, with
none installed, returns one shared null context: the cost of an
untraced span is one global read and an empty ``with``.
"""

from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Iterator, NamedTuple, Optional


class SpanRecord(NamedTuple):
    """One closed span, with the links the plain triple leaves out."""

    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]    # id of the innermost span open on its thread
    request: Optional[int]   # id of the span that opened its request


class Spans:
    """In-memory span recorder, safe to write from several threads."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._open = threading.local()   # per thread: stack of open spans
        self._records: list[SpanRecord] = []

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    @contextmanager
    def span(self, name: str, request: bool = False) -> Iterator[None]:
        """Record ``name`` around the ``with`` body.  ``request=True``
        starts a new request whose id is this span's own; otherwise the
        span joins the request of its parent, if any."""
        stack = self._stack()
        sid = next(self._ids)
        parent, req = stack[-1] if stack else (None, None)
        if request:
            req = sid
        stack.append((sid, req))
        t0 = time.perf_counter()
        try:
            if self.annotate:
                import jax
                with jax.profiler.TraceAnnotation(name):
                    yield
            else:
                yield
        finally:
            t1 = time.perf_counter()
            stack.pop()
            with self._lock:
                self._records.append(SpanRecord(name, t0, t1, sid, parent,
                                                req))

    @property
    def records(self) -> list[tuple[str, float, float]]:
        """Every closed span as ``(name, start_s, end_s)``."""
        with self._lock:
            return [(r.name, r.start, r.end) for r in self._records]

    def between(self, t0: float, t1: float, name: str | None = None
                ) -> list[tuple[str, float, float]]:
        """Spans that started in ``[t0, t1)``, optionally of one name,
        as ``(name, start_s, end_s)``."""
        return [(r.name, r.start, r.end)
                for r in self.nodes(t0, t1, name)]

    def nodes(self, t0: float, t1: float, name: str | None = None
              ) -> list[SpanRecord]:
        """As :meth:`between`, with each span's id, parent and request."""
        with self._lock:
            return [r for r in self._records
                    if t0 <= r.start < t1 and (name is None or r.name == name)]


_NULL = nullcontext()
_recorder: Optional[Spans] = None


def span(name: str, request: bool = False):
    """A span in the installed recorder, or the shared null context when
    none is installed."""
    rec = _recorder
    if rec is None:
        return _NULL
    return rec.span(name, request)


def install(recorder: Optional[Spans]) -> Optional[Spans]:
    """Install ``recorder`` process-wide (``None`` turns recording off)
    and return the one installed before, for the caller to restore."""
    global _recorder
    before, _recorder = _recorder, recorder
    return before
