"""Device idle share of the traced window, in %: 1 minus the union of
device operation intervals over the window."""


def read(rec: dict):
    if rec["trace"] is None:
        return None
    return 100.0 * rec["trace"]["idle_share"]
