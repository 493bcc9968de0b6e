"""95th percentile of update-to-visible time over all admitted updates
(host clock)."""

import numpy as np


def read(rec: dict):
    s = rec["window"].get("staleness_s")
    return 1000.0 * float(np.percentile(s, 95)) if s else None
