"""Arrays moved between host and device per coalesced tenant request: the
window's delta of the executor's ``exec.transfers`` over its delta of
``exec.requests``."""


def read(ctx):
    d = ctx.exec_delta
    if not d.get("requests") or "transfers" not in d:
        return None
    return d["transfers"] / d["requests"]
