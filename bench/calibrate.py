#!/usr/bin/env python3
"""Readings behind a cell's limits: the program's and the control's, many
seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

Runs the cell once per seed, as ``run.py`` would, and then puts the
control (the reference one precision step lower) in the program's place.
Prints one JSON line per seed: the program's readings and ``correct``,
the control's readings and ``control_correct``, both judged against the
cell's limits by the same comparison.  The lower reading of a limit is
the largest the program gives, the upper the smallest the control gives.
Needs the chip, like ``run.py``; the benchmark's own runs never run the
control.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

    from bench import harness
    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    t0 = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run(cell, seed, args.seconds, False, t0, log=log,
                        control=True)
        out = {"seed": seed, "correct": r["correct"],
               "control_correct": r["control_correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "metrics": {k: v["value"] for k, v in r["metrics"].items()},
               "readings": r["readings"], "control": r["control"],
               "limits": {k: c["limit"] for k, c in r["checks"].items()},
               "info": r["info"]}
        print(json.dumps(out), flush=True)
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
