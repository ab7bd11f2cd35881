"""Mean, in ms, of a probe round's host work, prepare plus absorb, over the
rounds that started in the window (``step_sessions`` timing)."""

import numpy as np


def read(ctx):
    v = [r["timing"]["prepare_s"] + r["timing"]["absorb_s"]
         for r in ctx.rounds if r["probes"]]
    return float(np.mean(v)) * 1e3 if v else None
