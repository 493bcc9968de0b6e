"""Transactional trigger firings (guard layer 2).

A firing either commits completely or leaves the engine untouched.
Because jax arrays are immutable, the pre-firing snapshot is *free*: a
shallow copy of the view dict keeps the old device buffers alive while
the firing builds new ones; rollback is a pointer swap, so a rolled-back
store is bit-identical to the pre-firing store (the literal same
buffers).  The snapshot also captures the engine's host-side firing
bookkeeping (hybrid staleness counters, lazy-stale set, and a copy of
``EngineStats``) so an aborted firing is invisible there too.

The price of the guarantee is that guarded engines cannot donate view
buffers into the firing (`donate=True` would let XLA overwrite the very
arrays the snapshot holds); :class:`repro.core.runtime.IncrementalEngine`
refuses that combination at construction.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Set


class FiringAborted(RuntimeError):
    """A guarded firing failed and was rolled back.

    ``reason`` says why ("chaos: injected trigger fault", "non-finite
    output in view Z", a kernel error repr); ``stage`` is where it was
    caught (``"execute"`` — the trigger raised — or ``"validate"`` — it
    produced non-finite outputs).
    """

    def __init__(self, reason: str, input_name: str, stage: str):
        super().__init__(f"firing on {input_name!r} aborted [{stage}]: "
                         f"{reason}")
        self.reason = reason
        self.input_name = input_name
        self.stage = stage


@dataclass
class FiringSnapshot:
    """Everything a rollback must restore, captured by reference."""

    views: Dict[str, object]
    accum_rank: Dict[str, int]
    stale: Set[str]
    stats: object  # copied EngineStats dataclass
    # deferred-cascade window state (higher-order engines): pending
    # window factors, window-start base snapshots, firing counters
    cascade: Optional[tuple] = None


def take_snapshot(engine) -> FiringSnapshot:
    """Pre-firing snapshot: O(#views) pointer copies, no device work."""
    cascade_fn = getattr(engine, "_cascade_snapshot", None)
    return FiringSnapshot(views=dict(engine.views),
                          accum_rank=dict(engine._accum_rank),
                          stale=set(engine._stale),
                          stats=dataclasses.replace(engine.stats),
                          cascade=cascade_fn() if cascade_fn else None)


def restore_snapshot(engine, snap: FiringSnapshot) -> None:
    """Roll the engine back to ``snap`` — bit-identical: the restored
    views are the very arrays the snapshot kept alive."""
    engine.views = snap.views
    engine._accum_rank = snap.accum_rank
    engine._stale = snap.stale
    for f in dataclasses.fields(type(engine.stats)):
        if f.name != "trigger_builds":  # a build stays in the trigger cache
            setattr(engine.stats, f.name, getattr(snap.stats, f.name))
    if snap.cascade is not None:
        engine._cascade_restore(snap.cascade)


def changed_views(snap: FiringSnapshot,
                  views: Dict[str, object]) -> List[str]:
    """Names whose array identity changed since the snapshot — exactly
    the views this firing wrote (jax arrays are immutable, so a write
    always produces a new buffer)."""
    return [name for name, val in views.items()
            if snap.views.get(name) is not val]


def check_finite(views: Dict[str, object], names) -> Optional[str]:
    """Post-firing output validation: one fused device reduction over
    every written view, a single scalar sync.  Returns a reason naming
    the first offending view, or ``None`` when all outputs are finite.

    The probe itself is a cached jitted program
    (:func:`repro.core.codegen.build_finite_check`) keyed on the sorted
    name tuple, so the clean path never retraces."""
    names = sorted(names)
    if not names:
        return None
    from repro.core.codegen import build_finite_check
    flags = build_finite_check(names)({n: views[n] for n in names})
    if bool(flags.all()):
        return None
    bad = [n for n, ok in zip(names, list(flags)) if not bool(ok)]
    return f"non-finite output in view(s) {', '.join(bad)}"
