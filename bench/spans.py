"""Host spans the benchmark records around its calls into each layer.

Each span is kept in memory as ``(name, start_s, end_s)`` on the
``time.perf_counter`` clock, and, while a profile is being taken, also
written into the profiler's trace as a ``jax.profiler.TraceAnnotation``,
so that the trace reduction can name device idle gaps by what the host
was doing.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self._lock = threading.Lock()
        self.records: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        t1 = time.perf_counter()
        with self._lock:
            self.records.append((name, t0, t1))

    def between(self, t0: float, t1: float, name: str | None = None
                ) -> list[tuple[str, float, float]]:
        """Spans that started in ``[t0, t1)``, optionally of one name."""
        with self._lock:
            return [s for s in self.records
                    if t0 <= s[1] < t1 and (name is None or s[0] == name)]
