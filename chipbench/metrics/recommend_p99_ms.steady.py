"""99th percentile, in milliseconds, of every recommend due in the window,
from its due time to its return."""

import numpy as np


def read(ctx):
    lat = [r["end"] - r["due"] for r in ctx.win.recs]
    return float(np.quantile(lat, 0.99)) * 1e3 if lat else None
