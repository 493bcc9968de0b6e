"""Closed loop, one rank-1 row update at a time, each blocked until every
view is ready before the next is drawn.  Rows uniform over the input.

Parameters: ``delta_scale``, the norm of each row delta.  The window closes at the first update that completes
after ``seconds``, so it holds only whole updates.
"""

from __future__ import annotations

import time


def warm(system, params: dict, rng) -> dict:
    for _ in range(2):
        system.apply_one(int(rng.integers(system.n)),
                         system.deltas(rng, 1, float(params["delta_scale"]))[0])
    return {}


def drive(system, params: dict, rng, seconds: float, state: dict) -> dict:
    latencies, ranks = [], []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        with system.spans.span("generator"):
            row = int(rng.integers(system.n))
            delta = system.deltas(rng, 1, float(params["delta_scale"]))[0]
        t0 = time.perf_counter()
        system.apply_one(row, delta)
        t1 = time.perf_counter()
        latencies.append(t1 - t0)
        ranks.append(1)
        if t1 >= deadline:
            break
    return {"window_s": t1 - start, "attempted": len(latencies),
            "updates": len(latencies), "latencies_s": latencies,
            "firing_ranks": ranks}


def finish(system, params: dict, state: dict) -> dict:
    return {"failed": 0}
