"""Mean, in ms, of a probe round's solve phase (dispatch, device and
transfer, with the service lock released) over the window's rounds."""

import numpy as np


def read(ctx):
    v = [r["timing"]["solve_s"] for r in ctx.rounds if r["probes"]]
    return float(np.mean(v)) * 1e3 if v else None
