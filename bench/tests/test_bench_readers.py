"""The reader of the program's rank counters, on synthetic records: it
reads what its docstring names, and finds nothing to read (``None``) in
a record of a program that has no such counter."""

from __future__ import annotations

import pytest

from benchutil import harness

CELL = harness.load_cell("powers16k.zipf_batch")


def _rec(before: dict, after: dict) -> dict:
    return {"spans": [], "trace": None,
            "counters": {"before": before, "after": after}}


def test_bucket_fill_pct():
    read = CELL.reader("bucket_fill_pct").read
    before = {"fired_rank": 10, "padded_rank": 16}
    after = {"fired_rank": 10 + 43 + 40, "padded_rank": 16 + 64 + 64}
    assert read(_rec(before, after)) == pytest.approx(100.0 * 83 / 128)
    # no firing in the window
    assert read(_rec(before, before)) is None
    # a program without the counters (or a fleet's counters)
    assert read(_rec({"triggers_fired": 1}, {"triggers_fired": 2})) is None


def test_stacked_rank_per_firing():
    read = CELL.reader("stacked_rank_per_firing").read
    before = {"stacked_rank": 7, "triggers_fired": 2}
    after = {"stacked_rank": 7 + 1000 + 1000, "triggers_fired": 4}
    assert read(_rec(before, after)) == pytest.approx(1000.0)
    assert read(_rec(before, before)) is None
    assert read(_rec({"triggers_fired": 1}, {"triggers_fired": 2})) is None


def test_trigger_builds_in_window():
    read = harness.load_cell("powers16k.rank1").reader(
        "trigger_builds_in_window").read
    before = {"trigger_builds": 3}
    assert read(_rec(before, {"trigger_builds": 5})) == 2
    # a warm window builds nothing, and reads 0, not "nothing to read"
    assert read(_rec(before, before)) == 0
    assert read(_rec({"triggers_fired": 1}, {"triggers_fired": 2})) is None
