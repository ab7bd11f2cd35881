"""Median, in ms, of the front desk's queue wait plus batching hold over the
window's completed tickets (the tickets' own attribution fields)."""

import numpy as np


def read(ctx):
    w = [row["ticket"].queue_wait_s + row["ticket"].batch_wait_s
         for row in ctx.win.tickets if row["ticket"].ok]
    return float(np.median(w)) * 1e3 if w else None
