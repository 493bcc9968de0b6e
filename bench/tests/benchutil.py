"""Helpers for the benchmark's CPU tests: cells cut to tiny sizes."""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

CELLS = ["powers16k.rank1", "powers16k.zipf_batch"]

# The fleet's system (``bench/systems/fleet.py``) and its open-loop
# traffic kind wait for a multi-tenant deployment with a public source;
# no committed cell runs them, so the tests drive them through this one,
# built in memory.
FLEET = "fleet_tiny.zipf_open"
RUNNABLE = CELLS + [FLEET]

TINY = {"engine": {"n": 128}, "fleet": {"n": 64, "tenants": 3}}
TINY_PARAMS = {"closed_batch": {"pool": 16},
               "open_poisson": {"rate_per_s": 60, "drain_s": 20}}


def _fleet_cell() -> harness.Cell:
    bench = ROOT / "bench"
    cfg = {"system": "fleet", "program": "matrix_powers", "tenants": 3,
           "n": 64, "k": 8, "spectral_scale": 0.9, "fleet": {"workers": 4},
           "tenant": {"guarded": True, "slo_s": 1.0, "queue_capacity": 256,
                      "max_claim_rank": 64}}
    workload = {"config": "fleet_tiny", "traffic": "zipf_open",
                "kind": "open_poisson",
                "params": {"tenants": "zipfian", "theta": 0.99,
                           "delta_scale": 0.3},
                "limits": {"views": 8e-6}}

    def metrics(unit, *names):
        return [{"name": n, "unit": unit} for n in names]

    return harness.Cell(
        name=FLEET, chips=1, cfg=cfg, workload=workload,
        system=harness.load_module(bench / "systems" / "fleet.py",
                                   "bench.systems.fleet"),
        traffic=harness.load_module(bench / "traffic" / "open_poisson.py",
                                    "bench.traffic.open_poisson"),
        end_to_end=metrics("ms", "staleness_p50_ms", "staleness_p95_ms")
        + metrics("s", "setup_s"),
        per_layer=metrics("updates/commit", "updates_per_commit")
        + metrics("%", "device_idle_pct.fleet"))


def tiny_cell(name: str, root: Path = ROOT) -> harness.Cell:
    """The cell as committed, at a size the CPU runs in a second."""
    cell = _fleet_cell() if name == FLEET else harness.load_cell(name, root)
    cell.cfg = copy.deepcopy(cell.cfg) | TINY[cell.cfg["system"]]
    cell.workload = copy.deepcopy(cell.workload)
    cell.workload["params"].update(TINY_PARAMS.get(cell.workload["kind"],
                                                   {}))
    return cell


def run_tiny(cell: harness.Cell, seed: int = 2 ** 31 + 11,
             seconds: float = 0.4, trace: bool = False,
             control: bool = False) -> dict:
    return harness.run(cell, seed, seconds, trace, time.perf_counter(),
                       platform="cpu", control=control,
                       log=lambda msg: None)
