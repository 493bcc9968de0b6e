"""Least time of one chip's share of the window's firings over that
chip's device time, in %, for matrix powers row-sharded over a mesh.

One chip's share: the firing's FLOPs and bytes (``bench/roofline.py``,
from the program's shapes and each firing's stacked rank) divided by the
chips of the configuration's mesh, its least time the larger of the
bytes over HBM bandwidth and the FLOPs over the bf16 peak.  Device time:
the trace's busy time inside the benchmark's firing spans, averaged over
the mesh's chips.  A chip cannot do less than its share of the work, so
the reading stays at or below 100%.
"""

from bench import roofline


def read(rec: dict):
    trace, cfg = rec["trace"], rec["cfg"]
    if (trace is None or cfg.get("program") != "matrix_powers"
            or "mesh" not in cfg):
        return None
    busy = trace["busy_in_spans_s"]
    if busy <= 0:
        return None
    chips = int(cfg["mesh"]["chips"])
    peaks = roofline.load_peaks(rec["device_kind"])
    levels = int(cfg["k"]).bit_length() - 1
    least = 0.0
    for r in rec["window"]["firing_ranks"]:
        flops, nbytes = roofline.powers_firing_counts(int(cfg["n"]), levels,
                                                      r)
        least += roofline.least_time_s(flops / chips, nbytes / chips,
                                       peaks)[0]
    return 100.0 * least / busy
