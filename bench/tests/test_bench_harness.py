"""The harness finds every cell's files by name, runs each traffic kind
through the system's real entries at tiny sizes, and refuses to run
without a TPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchutil import CELLS, ROOT, RUNNABLE, harness, run_tiny, tiny_cell

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOAD_FILES = sorted(p.stem for p in (ROOT / "bench" / "workloads").glob(
    "*.json"))


def test_cells_in_order():
    assert [w["name"] for w in BENCHMARK["workloads"]] == [
        c for c in CELLS if c in WORKLOAD_FILES]


@pytest.mark.parametrize("name", WORKLOAD_FILES)
def test_workload_file_resolves(name):
    """Each workload file names a cell of BENCHMARK.json, and its
    configuration, system, traffic kind and metric readers load."""
    cell = harness.load_cell(name)
    assert cell.chips == 1
    assert callable(cell.system.System)
    for fn in ("warm", "drive", "finish"):
        assert callable(getattr(cell.traffic, fn))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    for m in cell.per_layer:
        assert m["moves"] in names
    assert set(cell.workload["limits"]) <= {"views", *harness.load_program(
        cell.cfg["program"]).view_names(cell.cfg)}


def test_split_metric_read_by_base_reader():
    """``device_idle_pct.engine`` and ``.fleet`` have no reader of their
    own: both are read by ``device_idle_pct.py``."""
    cell = harness.load_cell(CELLS[0])
    reader = cell.reader("device_idle_pct.engine")
    assert reader.__name__ == "bench.metrics.device_idle_pct"
    assert cell.reader("device_idle_pct.fleet") is reader
    with pytest.raises(FileNotFoundError):
        cell.reader("no_such_metric.engine")


@pytest.mark.parametrize("name", RUNNABLE)
def test_traffic_drives_real_entry(name):
    """At tiny sizes every traffic kind goes through the system's own
    entries and the result passes the reference comparison."""
    result = run_tiny(tiny_cell(name))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0
    assert result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in tiny_cell(
        name).end_to_end}
    assert result["device"]["platform"] == "cpu"


def test_cell_added_as_files_alone(tmp_path):
    """A new configuration, traffic mix, traffic kind and metric, added
    as files and entries only, are found and run."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({
        "name": "powers_tiny", "source": "https://arxiv.org/abs/1403.6968",
        "file": "bench/configs/powers_tiny.json", "reduced": ["n"],
        "why": "test"})
    cfg = json.loads((ROOT / "bench/configs/powers16k.json").read_text())
    (tmp_path / "bench/configs/powers_tiny.json").write_text(
        json.dumps(cfg | {"n": 64}))
    bench["workloads"].append({"name": "powers_tiny.twice",
                               "config": "powers_tiny", "traffic": "twice",
                               "chips": 1, "why": "test"})
    (tmp_path / "bench/workloads/powers_tiny.twice.json").write_text(
        json.dumps({"config": "powers_tiny", "traffic": "twice",
                    "kind": "closed_pairs", "params": {"delta_scale": 0.3},
                    "limits": {"views": 1e-5}}))
    (tmp_path / "bench/traffic/closed_pairs.py").write_text(
        (ROOT / "bench/traffic/closed_single.py").read_text())
    bench["per_layer"].append({
        "name": "updates_in_window", "unit": "updates", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "update_ms",
        "workloads": ["powers_tiny.twice"]})
    (tmp_path / "bench/metrics/updates_in_window.py").write_text(
        "def read(rec):\n    return rec['window']['updates']\n")
    for m in bench["end_to_end"]:
        if m["name"] == "update_ms":
            m["workloads"].append("powers_tiny.twice")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("powers_tiny.twice", root=tmp_path)
    assert cell.cfg["n"] == 64
    assert [m["name"] for m in cell.per_layer] == ["updates_in_window"]
    result = run_tiny(cell, trace=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"update_ms", "setup_s"}


def _run_command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "powers16k.rank1",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _prints_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_command_without_tpu_fails():
    proc = _run_command(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert proc.returncode != 0
    assert not _prints_result(proc.stdout)
    assert "needs 1 tpu device" in proc.stderr


def test_command_with_benchmark_files_alone_fails(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own
    files has no system to run: no result, a non-zero exit."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not _prints_result(proc.stdout)
