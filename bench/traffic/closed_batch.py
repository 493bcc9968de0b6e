"""Closed loop of batches: each step is one ``apply_updates`` call with
``batch`` rank-1 row updates whose rows are drawn Zipf(``zipf``) over the
input (LINVIEW Table 4: batches of 1000, Zipf factor 2.0, row 0 the most
frequent).  A generator thread builds the next batch while the current one
is applied; the time the loop waits for it is reported as its lateness.

Parameters: ``batch``, ``zipf``, ``pool`` (batches whose rows are drawn up
front, so that set-up warms exactly the stacked-rank buckets they reach;
the window cycles through them, with fresh deltas),
``delta_scale`` (the norm of each row delta: row 0 takes about 61% of all
updates, so its deltas add up over the window, and the scale keeps that
sum near one uniform update's).
The window closes at the first batch that completes after ``seconds``.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time

import numpy as np

from bench import record


def _bucket(rank: int) -> int:
    """The engine's static rank bucket: the next power of two."""
    return 1 << (int(rank) - 1).bit_length()


def _pool(params: dict, rng, n: int) -> np.ndarray:
    r = rng.zipf(float(params["zipf"]),
                 size=(int(params["pool"]), int(params["batch"])))
    return np.minimum(r - 1, n - 1)


def _build(system, rows: np.ndarray, rng, scale: float):
    """The batch's updates, and its deltas summed per distinct row."""
    deltas = system.deltas(rng, len(rows), scale)
    updates = [(record.one_hot(system.n, r), d[:, None])
               for r, d in zip(rows, deltas)]
    uniq, inv = np.unique(rows, return_inverse=True)
    order = np.argsort(inv, kind="stable")
    starts = np.searchsorted(inv[order], np.arange(len(uniq)))
    sums = np.add.reduceat(deltas[order].astype(np.float64), starts, axis=0)
    return updates, uniq, sums


def warm(system, params: dict, rng) -> dict:
    """One firing at each bucket the pool's batches reach once
    re-compressed to their distinct rows (capped at the engine's
    ``max_batch_rank``).  The warm batch for bucket b is b updates to rows
    0..b-1: stacked rank b, under the cap, so it fires at bucket b without
    the host re-compression, which compiles nothing."""
    pool = _pool(params, rng, system.n)
    cap = system.cfg.get("engine", {}).get("max_batch_rank") or np.inf
    buckets = sorted({_bucket(min(len(np.unique(r)), cap)) for r in pool})
    for b in buckets:
        rows = np.arange(b)
        system.apply_batch(*_build(system, rows, rng,
                                   float(params["delta_scale"])))
    return {"pool": pool, "buckets": buckets}


def drive(system, params: dict, rng, seconds: float, state: dict) -> dict:
    batches: queue.Queue = queue.Queue(maxsize=1)
    scale = float(params["delta_scale"])
    stop = threading.Event()

    def generate():
        for rows in itertools.cycle(state["pool"]):
            with system.spans.span("generator"):
                item = (_build(system, rows, rng, scale), len(np.unique(rows)))
            while not stop.is_set():
                try:
                    batches.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if stop.is_set():
                return

    gen = threading.Thread(target=generate, name="bench-generator",
                           daemon=True)
    gen.start()
    latencies, ranks, waits = [], [], 0.0
    start = time.perf_counter()
    deadline = start + seconds
    try:
        while True:
            t0 = time.perf_counter()
            item = batches.get()
            t1 = time.perf_counter()
            waits += t1 - t0
            built, distinct = item
            system.apply_batch(*built)
            t2 = time.perf_counter()
            latencies.append(t2 - t1)
            ranks.append(distinct)
            if t2 >= deadline:
                break
    finally:
        stop.set()
        gen.join(timeout=60)
    if gen.is_alive():
        raise RuntimeError("the generator thread did not stop")
    batch = int(params["batch"])
    return {"window_s": t2 - start, "attempted": len(ranks) * batch,
            "updates": len(ranks) * batch, "latencies_s": latencies,
            "firing_ranks": ranks, "generator_wait_s": waits}


def finish(system, params: dict, state: dict) -> dict:
    return {"failed": 0}
