"""Tickets due in the window that completed within their deadline (counted
from the due time), per second of window."""


def read(ctx):
    n = 0
    for row in ctx.win.tickets:
        t = row["ticket"]
        if t.ok and t.finished_at - row["due"] <= ctx.deadline_s:
            n += 1
    return n / ctx.win.seconds
