"""One ``IncrementalEngine`` on a row mesh of several chips, driven on the
calling thread.

The mesh is the first ``cfg["mesh"]["chips"]`` devices on one axis named
``cfg["mesh"]["axis"]``.  The engine's input is made on the chips from the
seed, each chip its own row block, and handed to the engine on that mesh
(``IncrementalEngine(mesh=...)``), which keeps every view in row blocks.
Updates go through the engine's own entries as in ``engine.py``; the
reference re-evaluates the final input on the same row blocks
(``bench/reference_rows.py``), so no chip ever holds a whole view.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench import record, reference, reference_rows
from bench.harness import load_module, load_program

_engine = load_module(Path(__file__).with_name("engine.py"),
                      "bench.systems.engine")


class System(_engine.System):
    def __init__(self, cfg: dict, seed: int, spans):
        from repro.core import IncrementalEngine
        self.cfg = cfg
        self.spans = spans
        self.prog = load_program(cfg["program"])
        self.n = int(cfg["n"])
        chips, axis = int(cfg["mesh"]["chips"]), cfg["mesh"]["axis"]
        mesh = Mesh(np.array(jax.devices()[:chips]), (axis,))
        self._rows = NamedSharding(mesh, PartitionSpec(axis, None))
        self._key = record.jax_key(seed, 0)
        A = self._synthesize()
        self.engine = IncrementalEngine(
            self.prog.build_program(cfg), {self.prog.INPUT: 1}, mesh=mesh,
            **cfg.get("engine", {}))
        with spans.span("initialize"):
            self.engine.initialize({self.prog.INPUT: A})
            jax.block_until_ready(self.engine.views)
        del A
        self.record = record.RowRecord(self.n)

    def _synthesize(self) -> jax.Array:
        """A as ``matrix_powers.synthesize`` makes it, in row blocks."""
        return reference_rows.normal(
            self._key, self.n,
            float(self.cfg["spectral_scale"]) / math.sqrt(self.n),
            self._rows)

    def _log_peaks(self) -> None:
        """Each chip's peak so far, before the reference adds its own,
        on standard error (the result line has the largest only)."""
        stats = [d.memory_stats() or {}
                 for d in self._rows.mesh.devices.flat]
        if all("peak_bytes_in_use" in s for s in stats):
            print("info: peak_bytes_in_use per chip: " + ", ".join(
                str(s["peak_bytes_in_use"]) for s in stats),
                file=sys.stderr, flush=True)

    def readings(self, control: bool = False
                 ) -> tuple[dict[str, float], dict[str, float] | None]:
        """Each view's ``max |view − reference| / max |reference|``, and
        the control's where asked, every product in row blocks.  The
        engine is dropped first: of its state the comparison needs only
        the views."""
        self._log_peaks()
        views = self.engine.views
        self.engine = None
        rows, deltas = self.record.arrays()
        A = reference_rows.apply_row_updates(self._synthesize(), rows,
                                             deltas, self._rows)
        names = self.prog.view_names(self.cfg)
        levels = self.prog.levels(self.cfg)
        got = {}
        for name, P in zip(names, reference.powers(
                A, levels, mm=reference_rows.matmul(self._rows))):
            got[name] = float(reference.rel_err(views[name], P))
        del views
        if not control:
            return got, None
        ctl = {}
        pairs = zip(names,
                    reference.powers(A, levels,
                                     mm=reference_rows.matmul(self._rows)),
                    reference.powers(A, levels,
                                     mm=reference_rows.control_matmul(
                                         self._rows)))
        for name, P, C in pairs:
            ctl[name] = float(reference.rel_err(C, P))
        return got, ctl
