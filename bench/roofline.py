"""Operations and bytes a firing needs, from its shapes, and its least time.

The counts are what the algebra needs at the least, not what the program
happens to run, so a share of the roofline computed from them cannot pass
100% unless the measured time leaves out part of the work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def powers_firing_counts(n: int, levels: int, rank: int,
                         itemsize: int = 4) -> tuple[float, float]:
    """FLOPs and HBM bytes of one firing of matrix powers ``P_1 = A``,
    ``P_2 = P_1²`` … ``P_{2^levels}`` under a rank-``rank`` update of A.

    At level j the view's delta has rank ``r_j = rank · 2^j``, because
    ``Δ(P²) = [P U + U (Vᵀ U), U] · [V, Pᵀ V]ᵀ`` doubles it.  Each view is
    read once and written once (n² elements each way); the level's factor
    products ``P U`` and ``Pᵀ V`` can share that read.  FLOPs: the two
    products (2·n²·r_j each) below the top level, and the rank-r_j apply
    ``P += U Vᵀ`` (2·n²·r_j) at every level.  Factor traffic: U, V read
    and the two products written, n·r_j elements each.
    """
    flops = 0.0
    nbytes = 0.0
    for j in range(levels + 1):
        r = rank * 2 ** j
        flops += 2.0 * n * n * r
        nbytes += 2.0 * n * n * itemsize
        if j < levels:
            flops += 2 * 2.0 * n * n * r
            nbytes += 4.0 * n * r * itemsize
    return flops, nbytes


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; have {sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict
                 ) -> tuple[float, str]:
    """The larger of FLOPs over the bf16 peak and bytes over HBM
    bandwidth, and which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bandwidth") if t_bytes >= t_flops else (t_flops,
                                                              "compute")
