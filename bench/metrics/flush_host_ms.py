"""Host time of each ``apply_updates`` call until it returns, before the
wait for the views: stacking, re-compression and dispatch.  Mean per
batch over the window, from the benchmark's spans (host clock)."""


def read(rec: dict):
    d = [b - a for name, a, b in rec["spans"] if name == "apply_updates"]
    return 1000.0 * sum(d) / len(d) if d else None
