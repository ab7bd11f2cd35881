"""95th percentile of ticket latency, from each ticket's due time, over
every ticket due in the window; a ticket that did not complete counts at
no less than its deadline."""

import numpy as np


def read(ctx):
    from harness.drive import ticket_latencies

    lat = ticket_latencies(ctx.win, ctx.deadline_s)
    return float(np.quantile(lat, 0.95)) if len(lat) else None
