"""Open loop: rank-1 row updates to a fleet's tenants at a fixed total
rate, sent on schedule whether or not the fleet keeps up.

Arrivals are a Poisson process of ``rate_per_s`` over the window,
conditioned on its expected count: ``round(rate · seconds)`` arrival times
drawn uniform and sorted, so every seed sends the same number of updates
in another order.  Tenants are drawn ``"zipfian"`` (YCSB's request
distribution, p(i) ∝ 1/(i+1)^``theta``) or ``"uniform"``; rows uniform
within a tenant.

Each admitted update's staleness runs from its scheduled arrival (not
from when it was sent) until a read of its tenant's committed views
holds it and the views are ready on the device.  A collector thread
makes those reads.  After the window the fleet gets ``drain_s`` seconds;
an update still not visible then, or a submission not admitted, fails.

Parameters: ``rate_per_s``, ``tenants``, ``theta`` (zipfian only),
``drain_s``, ``delta_scale`` (the norm of each row delta).
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

POLL_S = 0.0005


def _tenant_probs(params: dict, count: int) -> np.ndarray:
    if params["tenants"] == "uniform":
        return np.full(count, 1.0 / count)
    if params["tenants"] == "zipfian":
        w = 1.0 / np.arange(1, count + 1) ** float(params["theta"])
        return w / w.sum()
    raise ValueError(f"unknown tenant distribution {params['tenants']!r}")


def warm(system, params: dict, rng) -> dict:
    system.warm(rng, float(params["delta_scale"]))
    return {}


class _Collector:
    """Marks updates visible as reads through the served path show them."""

    def __init__(self, system):
        self.system = system
        self.lock = threading.Lock()
        self.outstanding = {tid: deque() for tid in system.ids}
        self.staleness: list[float] = []
        self.stop = threading.Event()
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._run,
                                       name="bench-collector", daemon=True)

    def expect(self, tid: str, lsn: int, due: float) -> None:
        with self.lock:
            self.outstanding[tid].append((lsn, due))

    def pending(self) -> int:
        with self.lock:
            return sum(len(q) for q in self.outstanding.values())

    def _run(self) -> None:
        try:
            while not self.stop.is_set():
                found = False
                for tid, q in self.outstanding.items():
                    with self.lock:
                        first = q[0][0] if q else None
                    if first is None or self.system.applied_lsn(tid) < first:
                        continue
                    found = True
                    lsn = self.system.read_visible(tid)
                    now = time.perf_counter()
                    with self.lock:
                        while q and q[0][0] <= lsn:
                            self.staleness.append(now - q.popleft()[1])
                if not found:
                    time.sleep(POLL_S)
        except BaseException as e:  # noqa: BLE001 — reported by finish()
            self.error = e


def drive(system, params: dict, rng, seconds: float, state: dict) -> dict:
    from repro.fleet import ADMITTED
    count = int(round(float(params["rate_per_s"]) * seconds))
    times = np.sort(rng.uniform(0.0, seconds, count))
    tenants = rng.choice(len(system.ids), size=count,
                         p=_tenant_probs(params, len(system.ids)))
    rows = rng.integers(system.n, size=count)
    scale = float(params["delta_scale"])
    collector = _Collector(system)
    refused = 0
    lateness = []
    system.start()
    collector.thread.start()
    start = time.perf_counter()
    for at, t, row in zip(times, tenants, rows):
        due = start + at
        with system.spans.span("generator"):
            delta = system.deltas(rng, 1, scale)[0]
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        lateness.append(time.perf_counter() - due)
        tid = system.ids[t]
        decision, lsn = system.submit(tid, int(row), delta)
        if decision == ADMITTED:
            collector.expect(tid, lsn, due)
        else:
            refused += 1
    end = time.perf_counter()
    return {"window_s": max(end - start, seconds), "attempted": count,
            "refused": refused, "backlog_at_close": collector.pending(),
            "lateness_p50_s": float(np.median(lateness)) if count else 0.0,
            "lateness_max_s": float(max(lateness, default=0.0)),
            "_collector": collector}


def finish(system, params: dict, state: dict) -> dict:
    collector = state.pop("_collector")
    deadline = time.perf_counter() + float(params["drain_s"])
    while collector.pending() and time.perf_counter() < deadline \
            and collector.error is None:
        time.sleep(0.01)
    collector.stop.set()
    collector.thread.join(timeout=60)
    system.stop()
    if collector.thread.is_alive():
        raise RuntimeError("the collector thread did not stop")
    if collector.error is not None:
        raise RuntimeError("the collector failed") from collector.error
    unseen = collector.pending()
    return {"failed": state["refused"] + unseen, "unseen": unseen,
            "updates": len(collector.staleness),
            "staleness_s": collector.staleness}
