"""Percent of the roofline the descent programs reached in the window: the
least time the chip needs for the algorithm's operations and bytes (see
``harness/flops.py``) over the device time of the descent program's
executions in the trace.  The executor's solve program is the module the
trace names ``jit_traced``; its time includes the small snap-and-score
epilogue, whose work is not counted."""

MODULE = "jit_traced"


def read(ctx):
    from harness.cell import descent_cost
    from harness.flops import roofline_share

    if not ctx.trace:
        return None
    seconds = ctx.trace["modules_s"].get(MODULE, 0.0)
    fl, nb = descent_cost(ctx)
    if seconds <= 0.0 or fl <= 0.0:
        return None
    share, _bound = roofline_share(fl, nb, seconds, ctx.peak["flops_bf16"],
                                   ctx.peak["hbm_bytes_per_s"])
    return 100.0 * share
