"""The four-chip cell ``powers32k.rank1_4chip``: its files resolve, a tiny
copy of it runs on four virtual CPU devices and comes out correct, and
its two readers read what their docstrings name, on synthetic records."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from benchutil import ROOT, harness
from bench import roofline

NAME = "powers32k.rank1_4chip"
CELL = harness.load_cell(NAME)
PEAKS = roofline.load_peaks("TPU v5 lite")


def test_cell_resolves():
    assert CELL.chips == 4
    assert CELL.cfg["system"] == "engine_mesh"
    assert CELL.cfg["mesh"] == {"axis": "rows", "chips": 4}
    assert CELL.cfg["n"] % (4 * 128) == 0
    names = {m["name"] for m in CELL.per_layer}
    assert {"sharded_firing_roofline_pct",
            "collective_kib_per_firing"} <= names
    assert "firing_roofline_pct" not in names
    for m in CELL.end_to_end + CELL.per_layer:
        assert callable(CELL.reader(m["name"]).read)


def test_one_device_is_no_device():
    """With one device the cell refuses to start (exit 2 in ``run.py``)
    before it builds anything."""
    with pytest.raises(harness.NoDevice):
        harness.run(CELL, 1, 0.1, False, time.perf_counter(),
                    platform="cpu", log=lambda msg: None)


# one child process with four virtual devices runs the tiny cell twice:
# untraced with the control, and traced
_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[0:0] = [{root!r}, {src!r}]
    from bench import harness
    cell = harness.load_cell({name!r})
    cell.cfg = cell.cfg | {{"n": 128}}
    # the roofline reader needs a TPU's peaks
    cell.per_layer = [m for m in cell.per_layer
                      if m["name"] != "sharded_firing_roofline_pct"]
    out = {{}}
    for trace in (False, True):
        out[str(trace)] = harness.run(
            cell, 2 ** 31 + 11, 0.4, trace, time.perf_counter(),
            platform="cpu", control=not trace, log=lambda msg: None)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def tiny_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    script = _SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"), name=NAME)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    runs = json.loads(proc.stdout.strip().splitlines()[-1])
    return runs["False"], runs["True"]


def test_tiny_cell_correct_and_control_not(tiny_runs):
    plain, _ = tiny_runs
    assert plain["correct"], plain["checks"]
    assert not plain["control_correct"], plain["control"]
    assert plain["failed"] == 0 and plain["attempted"] > 0
    assert plain["device"]["count"] == 4
    assert set(plain["metrics"]) == {"update_ms", "update_p95_ms",
                                     "setup_s"}
    assert plain["info"]["compiles_in_window"]["compiles"] == 0


def test_tiny_cell_traced_reads_collectives(tiny_runs):
    _, traced = tiny_runs
    assert traced["correct"], traced["checks"]
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    # n=128: far below a view's shard of 128·128·4/4 bytes per firing
    assert 0 < metrics["collective_kib_per_firing"] < 16.0
    assert metrics["trigger_builds_in_window"] == 0


# -- the readers, on synthetic records ---------------------------------------


def _counters(before: dict, after: dict) -> dict:
    return {"spans": [], "trace": None,
            "counters": {"before": before, "after": after}}


def test_collective_kib_per_firing():
    read = CELL.reader("collective_kib_per_firing").read
    before = {"collective_bytes": 1000, "triggers_fired": 2}
    after = {"collective_bytes": 1000 + 3 * 3801428, "triggers_fired": 5}
    assert read(_counters(before, after)) == pytest.approx(3801428 / 1024)
    # no firing in the window
    assert read(_counters(before, before)) is None
    # a program without the counter (the parent of this cell's engine)
    assert read(_counters({"triggers_fired": 1},
                          {"triggers_fired": 2})) is None


def _sharded_share(ranks, busy, cfg=None):
    rec = {"cfg": cfg or CELL.cfg, "trace": {"busy_in_spans_s": busy},
           "device_kind": "TPU v5 lite", "window": {"firing_ranks": ranks}}
    return CELL.reader("sharded_firing_roofline_pct").read(rec)


def _one_chip_least(ranks, chips=4):
    n, levels = CELL.cfg["n"], int(CELL.cfg["k"]).bit_length() - 1
    total = 0.0
    for r in ranks:
        flops, nbytes = roofline.powers_firing_counts(n, levels, r)
        total += roofline.least_time_s(flops / chips, nbytes / chips,
                                       PEAKS)[0]
    return total


@pytest.mark.parametrize("ranks", [[1] * 5, [1, 2, 64]])
def test_sharded_share_at_one_chips_bound_is_100(ranks):
    least = _one_chip_least(ranks)
    assert _sharded_share(ranks, least) == pytest.approx(100.0)
    for slower in (1.0001, 1.5, 10.0):
        assert _sharded_share(ranks, least * slower) < 100.0
    # a quarter of the whole firing's least time: the same bandwidth bound
    whole = _one_chip_least(ranks, chips=1)
    assert least == pytest.approx(whole / 4)


def test_sharded_share_needs_a_mesh_and_a_trace():
    cfg = {k: v for k, v in CELL.cfg.items() if k != "mesh"}
    assert _sharded_share([1], 1.0, cfg) is None
    assert _sharded_share([1], 0.0) is None
    rec = {"cfg": CELL.cfg, "trace": None, "device_kind": "TPU v5 lite",
           "window": {"firing_ranks": [1]}}
    assert CELL.reader("sharded_firing_roofline_pct").read(rec) is None
