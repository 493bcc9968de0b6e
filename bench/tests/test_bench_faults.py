"""The comparison that decides ``correct`` fails what it must.

Each test drives a whole run at tiny sizes on the CPU, past the harness's
look for a chip, with the timed path broken underneath, and sees
``correct`` come out false: a step that leaves the views unchanged, a
batch with half its updates left out, and a view altered where the
firing produces it.  The cells run on one chip, so there is no exchange
between chips to leave out.  Last, the control (the reference one
precision step lower) fails each cell's limits.
"""

from __future__ import annotations

import pytest

from benchutil import RUNNABLE, run_tiny, tiny_cell
from repro.core.runtime import IncrementalEngine

ORIGINAL = {"apply_update": IncrementalEngine.apply_update,
            "apply_updates": IncrementalEngine.apply_updates}


def unchanged(monkeypatch):
    """Every firing returns the views as they were."""
    for name in ORIGINAL:
        monkeypatch.setattr(IncrementalEngine, name,
                            lambda self, *a, **k: self.views)


def half_batch(monkeypatch):
    """A batch fires only the first half of its updates (none of one)."""
    def apply_updates(self, input_name, updates, block=False):
        kept = list(updates)[:len(updates) // 2]
        if not kept:
            return self.views
        return ORIGINAL["apply_updates"](self, input_name, kept, block=block)
    monkeypatch.setattr(IncrementalEngine, "apply_updates", apply_updates)


def altered(monkeypatch):
    """Each firing's P2 comes out with one entry off by a thousandth of
    the view's largest entry."""
    def wrap(fn):
        def fire(self, *a, **k):
            fn(self, *a, **k)
            P2 = self.views["P2"]
            bump = 1e-3 * abs(P2).max()
            self.views = dict(self.views, P2=P2.at[0, 0].add(bump))
            return self.views
        return fire
    for name, fn in ORIGINAL.items():
        monkeypatch.setattr(IncrementalEngine, name, wrap(fn))


FAULTS = [(cell, fault) for cell in RUNNABLE
          for fault in (unchanged, half_batch, altered)
          # one update per firing has no half to leave out
          if not (cell == "powers16k.rank1" and fault is half_batch)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = run_tiny(tiny_cell(cell))
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", RUNNABLE)
def test_control_fails_the_limits(cell):
    """The control, put in the program's place, comes out not correct by
    the comparison that decides ``correct``."""
    result = run_tiny(tiny_cell(cell), control=True)
    assert result["correct"], result["checks"]
    assert result["control_correct"] is False, (
        result["control"], result["checks"])
