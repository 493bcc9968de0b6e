"""Updates a fleet commit carries: the change of ``fleet_stats()``
``committed_updates`` over the change of ``commits`` in the window."""


def read(rec: dict):
    b, a = rec["counters"]["before"], rec["counters"]["after"]
    if "commits" not in a:
        return None
    commits = a["commits"] - b["commits"]
    if commits <= 0:
        return None
    return (a["committed_updates"] - b["committed_updates"]) / commits
