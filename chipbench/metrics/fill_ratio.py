"""Percent of the padded (G, R) rows the window's dispatches computed that
were useful cells (executor counters, window delta)."""


def read(ctx):
    d = ctx.exec_delta
    if not d["padded_rows"]:
        return None
    return 100.0 * d["useful_rows"] / d["padded_rows"]
