"""Rank a firing stacked before re-compression, on average: the change
of ``EngineStats.stacked_rank`` over the change of ``triggers_fired`` in
the window.  The host QR/SVD of a batch grows with it."""


def read(rec: dict):
    b, a = rec["counters"]["before"], rec["counters"]["after"]
    if "stacked_rank" not in a:
        return None
    fired = a["triggers_fired"] - b["triggers_fired"]
    if fired <= 0:
        return None
    return (a["stacked_rank"] - b["stacked_rank"]) / fired
