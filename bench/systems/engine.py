"""One ``IncrementalEngine`` over one program, driven on the calling thread.

The engine's input is made on the device from the seed; every update the
traffic sends goes through the engine's own entries (``apply_update`` for
one, ``apply_updates`` for a batch) and into the benchmark's record, from
which the reference rebuilds the final input.
"""

from __future__ import annotations

import dataclasses

import jax

from bench import record
from bench.harness import load_program


class System:
    def __init__(self, cfg: dict, seed: int, spans):
        from repro.core import IncrementalEngine
        self.cfg = cfg
        self.spans = spans
        self.prog = load_program(cfg["program"])
        self.n = int(cfg["n"])
        self._key = record.jax_key(seed, 0)
        A = self.prog.synthesize(cfg, self._key)
        self.engine = IncrementalEngine(
            self.prog.build_program(cfg), {self.prog.INPUT: 1},
            **cfg.get("engine", {}))
        with spans.span("initialize"):
            self.engine.initialize({self.prog.INPUT: A})
            jax.block_until_ready(self.engine.views)
        del A
        self.record = record.RowRecord(self.n)

    def deltas(self, rng, count: int, scale: float):
        return self.prog.deltas(self.cfg, rng, count, scale)

    def apply_one(self, row: int, delta) -> None:
        """One rank-1 row update, blocked until every view is ready."""
        with self.spans.span("apply_update"):
            self.engine.apply_update(self.prog.INPUT,
                                     record.one_hot(self.n, row),
                                     delta[:, None], block=True)
        self.record.add([row], [delta])

    def apply_batch(self, updates, rows, sums) -> None:
        """One batch through ``apply_updates``; ``rows``/``sums`` are its
        deltas summed per distinct row, for the record."""
        with self.spans.span("apply_updates"):
            self.engine.apply_updates(self.prog.INPUT, updates)
        with self.spans.span("wait_views"):
            jax.block_until_ready(self.engine.views)
        self.record.add(rows, sums)

    def counters(self) -> dict:
        return dataclasses.asdict(self.engine.stats)

    def readings(self, control: bool = False
                 ) -> tuple[dict[str, float], dict[str, float] | None]:
        """The program's readings against the reference, and the
        control's where asked.  The engine is dropped first: of its state
        the comparison needs only the views."""
        from bench.reference import apply_row_updates
        views = self.engine.views
        self.engine = None
        rows, deltas = self.record.arrays()
        A = apply_row_updates(self.prog.synthesize(self.cfg, self._key),
                              rows, deltas)
        got = self.prog.readings(self.cfg, A, views)
        del views
        ctl = self.prog.control_readings(self.cfg, A) if control else None
        return got, ctl
