#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Builds the cell's system from the seed,
warms up every shape its traffic uses (set-up), measures for
``--seconds``, compares every view the window produced with the plain
reference, and prints one JSON object as the last line of standard
output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window.  The numbers compared, each with its limit, are the
last lines of standard error and the ``checks`` key of the result.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for; exits 1 on any other failure.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the checkout root, not this directory, heads the path: the
    # benchmark's modules are imported as ``bench.*``
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

    from bench import harness
    cell = harness.load_cell(args.workload)

    harness.use_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_PROCESS, log=log)
    except harness.NoDevice as e:
        log(f"error: {e}")
        return 2
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 — any failure exits non-zero, no result
        traceback.print_exc()
        code = 1
    sys.exit(code)
