"""Persistent compiled-trigger cache (ROADMAP: stop re-jitting per
(bucket, mesh) on every new engine instance).

A compiled trigger is a pure function of (program fingerprint, trigger
kind, input, bucket rank, plan partition, mesh, backend options) — none
of it engine-local — so the jitted callable can outlive the
``IncrementalEngine`` that first built it.  The cache stores callables
under exactly that key: a second engine constructed over a structurally
identical program at the same sizes, executing the same plan on the
same mesh, gets the *same* function object back, and jax's jit cache
(keyed on function identity) serves the compiled executable with no
re-trace and no re-compile.

Process-level by design: XLA executables are not picklable, so true
on-disk persistence is delegated to jax's own compilation cache (which
the entry points turn on, :mod:`repro.launch.compile_cache`), and which
composes with this cache — the key here removes the *re-trace*, the jax
cache removes the *re-compile* across processes.

Engines use the process-global instance whenever they execute a plan;
pass ``trigger_cache=TriggerCache()`` for an isolated one (tests).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple


class TriggerCache:
    """Thread-safe (key → compiled trigger callable) map with hit/miss
    counters.  Keys must be hashable tuples; values are the callables
    produced by the codegen builders.

    Fleet workers read AND populate this concurrently (N tenants share
    one cache), so every access — including ``len``/``in``/``stats`` —
    holds the lock; ``get_or_build`` builds outside it (jit tracing is
    slow) and lets the first writer win.  ``capacity`` bounds the entry
    count with LRU eviction (``None`` = unbounded, the default): a
    multi-tenant service over many distinct programs must not grow
    compiled-trigger state without bound.
    """

    def __init__(self, capacity: Optional[int] = None):
        if capacity is not None and capacity < 1:
            raise ValueError(f"capacity must be ≥ 1, got {capacity}")
        self.capacity = capacity
        self._fns: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key: Tuple, builder: Callable[[], Callable]
                     ) -> Callable:
        """Return the cached callable for ``key``, building (and
        retaining) it on first use."""
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                self._fns.move_to_end(key)
                return fn
        fn = builder()  # build outside the lock: jit tracing can be slow
        with self._lock:
            won = self._fns.setdefault(key, fn)
            self._fns.move_to_end(key)
            if won is fn:
                self.misses += 1
                self._evict_over_capacity()
            else:
                self.hits += 1
        return won

    def _evict_over_capacity(self) -> None:
        # caller holds the lock
        while self.capacity is not None and len(self._fns) > self.capacity:
            self._fns.popitem(last=False)
            self.evictions += 1

    def evict(self, key: Tuple) -> bool:
        """Drop one entry (e.g. a retired tenant's program); True if it
        was present.  The callable itself stays valid for holders — only
        future lookups rebuild."""
        with self._lock:
            return self._fns.pop(key, None) is not None

    def __len__(self) -> int:
        with self._lock:
            return len(self._fns)

    def __contains__(self, key: Tuple) -> bool:
        with self._lock:
            return key in self._fns

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._fns), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions}


_GLOBAL = TriggerCache()


def global_trigger_cache() -> TriggerCache:
    """The process-wide cache engines share by default."""
    return _GLOBAL


def mesh_cache_key(mesh, axis: Optional[str] = None) -> Optional[Tuple]:
    """Hashable identity of a mesh for trigger-cache keying.

    Includes the concrete device ids in order: the distributed trigger
    builders close over ``NamedSharding(mesh, …)``, so the compiled
    callable is pinned to that exact device placement — two meshes with
    the same shape over different devices (or a permutation, e.g. after
    an elastic reshape) must NOT share cache entries.  Two meshes over
    the identical device sequence compile identical triggers and do
    share."""
    if mesh is None:
        return None
    devs = mesh.devices.ravel()
    return (tuple(mesh.shape.items()),
            axis or mesh.axis_names[0],
            devs[0].platform if len(devs) else "cpu",
            tuple(int(d.id) for d in devs))
