"""Percent of the traced window in which no operation ran on the device
(recurring traffic)."""


def read(ctx):
    from harness.cell import idle_percent

    if ctx.mix["pattern"] != "recurring":
        return None
    return idle_percent(ctx)
