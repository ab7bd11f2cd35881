"""Percent of the window the service spent in tuning rounds, on the
benchmark's own clock around each ``step_sessions`` call: the serving
host's cost of this load.  Every round counts as far as it overlaps the
window, the one in progress at its close too."""


def read(ctx):
    lo, hi = ctx.win.t0, ctx.win.t_close
    busy = sum(max(0.0, min(t1, hi) - max(t0, lo))
               for t0, t1 in ctx.round_intervals)
    return 100.0 * busy / (hi - lo)
