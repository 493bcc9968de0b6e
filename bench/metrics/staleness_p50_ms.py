"""Median update-to-visible time over all admitted updates: from the
update's scheduled arrival to a served read that holds it, ready on the
device (host clock)."""

import numpy as np


def read(rec: dict):
    s = rec["window"].get("staleness_s")
    return 1000.0 * float(np.percentile(s, 50)) if s else None
