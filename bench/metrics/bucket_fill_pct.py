"""Share of the fired rank buckets that carried real rank, in %: the
change of ``EngineStats.fired_rank`` (after re-compression) over the
change of ``padded_rank`` (the power-of-two buckets fired) in the
window."""


def read(rec: dict):
    b, a = rec["counters"]["before"], rec["counters"]["after"]
    if "padded_rank" not in a:
        return None
    padded = a["padded_rank"] - b["padded_rank"]
    if padded <= 0:
        return None
    return 100.0 * (a["fired_rank"] - b["fired_rank"]) / padded
