"""95th percentile over the window's firings, each timed from the call
until its views are ready (host clock)."""

import numpy as np


def read(rec: dict):
    lat = rec["window"].get("latencies_s")
    return 1000.0 * float(np.percentile(lat, 95)) if lat else None
