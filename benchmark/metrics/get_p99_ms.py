"""get_p99_ms: the 99th percentile, over every linearizable get due in the
window, of the wall time from when it was due to when its answer was seen
(one still open at the window's end counts until the end)."""

import numpy as np


def reduce(rec, trace):
    xs = rec.values.get("lat_ms", {}).get("get")
    return float(np.percentile(np.asarray(xs), 99)) if xs else None
