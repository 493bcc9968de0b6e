"""The reader of the engine's fused-pass counter, on synthetic records: it
reads passes per firing, reads 0 where firings took no pass, and finds
nothing to read (``None``) where nothing fired or the program has no such
counter."""

from __future__ import annotations

import pytest

from benchutil import harness


def _rec(before: dict, after: dict) -> dict:
    return {"spans": [], "trace": None,
            "counters": {"before": before, "after": after}}


@pytest.mark.parametrize("cell", ["powers16k.rank1", "powers16k.zipf_batch"])
def test_fused_passes_per_firing(cell):
    read = harness.load_cell(cell).reader("fused_passes_per_firing").read
    before = {"fused_view_passes": 6, "triggers_fired": 2}
    after = {"fused_view_passes": 6 + 3 * 5, "triggers_fired": 2 + 5}
    assert read(_rec(before, after)) == pytest.approx(3.0)
    # firings without a pass read 0, not "nothing to read"
    assert read(_rec(before, {"fused_view_passes": 6,
                              "triggers_fired": 9})) == 0
    # no firing in the window
    assert read(_rec(before, before)) is None
    # a program without the counter
    assert read(_rec({"triggers_fired": 1}, {"triggers_fired": 2})) is None
