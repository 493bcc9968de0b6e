"""Least time of the window's firings over their device time, in %.

Least time per firing: the larger of its bytes over HBM bandwidth and its
FLOPs over the bf16 peak (``bench/roofline.py``, from the program's
shapes and the firing's stacked rank).  Device time: the trace's busy
time inside the benchmark's firing spans.  Matrix powers only.
"""

from bench import roofline


def read(rec: dict):
    trace, cfg = rec["trace"], rec["cfg"]
    if trace is None or cfg.get("program") != "matrix_powers":
        return None
    busy = trace["busy_in_spans_s"]
    if busy <= 0:
        return None
    peaks = roofline.load_peaks(rec["device_kind"])
    levels = int(cfg["k"]).bit_length() - 1
    least = sum(roofline.least_time_s(
        *roofline.powers_firing_counts(int(cfg["n"]), levels, r), peaks)[0]
        for r in rec["window"]["firing_ranks"])
    return 100.0 * least / busy
