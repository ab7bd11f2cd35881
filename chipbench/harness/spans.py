"""The program's own spans, counters and anchors, for the metric readers.

The deployment's stack shares one ``Observability`` bundle
(``dep.service.obs``).  A traced run turns its tracer on as the window opens
and off at its close, with an anchor (``Tracer.anchor``) at each end, so the
tracer holds the spans of the rounds that started in the window, and the
anchors map them onto the profiler's clock.  Where the tracer holds nothing
(an untraced run, or a program without these spans), every reader here
returns ``None``.
"""

from __future__ import annotations

import glob
import os

from . import trace as trace_mod

ROUND = "service.step_round"
EXEC_HOST = ("exec.materialize", "exec.pack", "exec.unpack")
EXEC_WAIT = ("exec.launch", "exec.wait")


def program_spans(ctx) -> list:
    tracer = getattr(getattr(ctx.dep.service, "obs", None), "tracer", None)
    return tracer.spans() if tracer is not None else []


def per_round_s(ctx, names) -> list[float] | None:
    """Seconds in spans named ``names``, summed per round, for every round
    that made a device dispatch; ``None`` without such rounds."""
    spans = program_spans(ctx)
    if not any(s.name in names for s in spans):
        return None  # a program without these spans
    by_id = {s.span_id: s for s in spans}

    def round_of(s):
        while s is not None and s.name != ROUND:
            s = by_id.get(s.parent_id)
        return None if s is None else s.span_id

    rounds = {round_of(s) for s in spans if s.name == "exec.dispatch"}
    rounds.discard(None)
    if not rounds:
        return None
    total = dict.fromkeys(rounds, 0.0)
    for s in spans:
        if s.name in names and s.t1 is not None:
            r = round_of(s)
            if r in total:
                total[r] += s.t1 - s.t0
    return list(total.values())


def lock_waits_s(ctx, op: str) -> list:
    """The ``service.lock_wait_s{op=...}`` histograms of the stack."""
    obs = getattr(ctx.dep.service, "obs", None)
    if obs is None:
        return []
    return [h for h in obs.metrics.instruments("service.lock_wait_s")
            if h.labels.get("op") == op and h.count]


def newest_trace_file() -> str | None:
    """The profile this process wrote last (the window's: the harness
    names each trace directory after its process)."""
    from .cell import RESULTS

    dirs = glob.glob(str(RESULTS / f"trace-{os.getpid()}-*"))
    files = [f for d in dirs if (f := trace_mod.find_xplane(d))]
    return max(files, key=os.path.getmtime) if files else None


def _measure(intervals) -> float:
    return sum(e - s for s, e in trace_mod._union(intervals))


def _intersect(a, b) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_share_in(path: str, spans: list, to_ns, names) -> float | None:
    """Percent of the devices' idle time inside the ``bench.window`` event
    of the profile at ``path`` that falls inside an open span named
    ``names`` (tracer clock, mapped to the profile's by ``to_ns``).

    Host lines are read one by one: several threads' lines share a name,
    so a dict keyed by line name would keep only one of them."""
    from jax.profiler import ProfileData

    win, ops, n_dev = [], [], 0
    for plane in ProfileData.from_file(path).planes:
        n_dev += plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                iv = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                if plane.name.startswith("/host:") and e.name == \
                        trace_mod.WINDOW:
                    win.append(iv)
                elif (plane.name.startswith("/device:")
                      and line.name == "XLA Ops"):
                    ops.append(iv)
    if not win or not n_dev:
        return None
    lo, hi = win[0]
    busy = trace_mod._union(trace_mod._clip(ops, lo, hi))
    idle, edge = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            idle.append((edge, s))
        edge = max(edge, e)
    total = _measure(idle)
    if total <= 0.0:
        return None
    host_spans = trace_mod._union(trace_mod._clip(
        [(to_ns(s.t0), to_ns(s.t1)) for s in spans
         if s.name in names and s.t1 is not None], lo, hi))
    return 100.0 * _measure(_intersect(idle, host_spans)) / total
