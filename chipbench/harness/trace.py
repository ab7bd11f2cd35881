"""From a profiler trace to device busy time, program times and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
A device's plane is named ``/device:<KIND>:<n>``; its ``XLA Ops`` line holds
one event per operation the device ran and its ``XLA Modules`` line one
event per program execution.  Host threads are lines of ``/host:CPU``; the
benchmark's own ``TraceAnnotation`` spans (``bench.window``,
``service.step_round``, ``pf.absorb``, ...) are events there.  All event
times are on one clock, relative to the trace's start.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
SPAN_PREFIXES = ("frontdesk.", "service.", "pf.", "exec.")
INNER_PREFIXES = ("pf.", "exec.")
_MODULE_ID = re.compile(r"\(\d+\)$")


def op_name(text: str) -> str:
    """``%fusion.7 = bf16[...] fusion(...)`` -> ``fusion``: an operation's
    HLO name without its instance number, so instances add up."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def find_xplane(log_dir: str) -> str | None:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load(path: str):
    """Planes of an ``.xplane.pb`` as plain tuples:
    ``{plane: {line: [(name, start_ns, end_ns), ...]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns))
                                for e in line.events]
        planes[plane.name] = lines
    return planes


def reduce(planes: dict, top: int = 10, host_spans=(),
           anchor_ns: float | None = None) -> dict:
    """Busy and idle time of the device(s) inside the ``bench.window`` span,
    per-program and per-operation device time, and the longest idle gaps
    labelled with the benchmark span the host spent most of each gap in.

    ``host_spans`` are ``(name, start_ns, end_ns)`` on the host's
    ``perf_counter_ns``, from threads the profiler does not record;
    ``anchor_ns`` is that clock's reading as the window's span opened,
    which places them on the trace's clock."""
    host = planes.get("/host:CPU", {})
    win = [(s, e) for evs in host.values() for n, s, e in evs if n == WINDOW]
    devices = {p: ls for p, ls in planes.items()
               if p.startswith("/device:") and ls.get("XLA Ops")}
    all_dev = [(s, e) for ls in devices.values() for evs in ls.values()
               for _n, s, e in evs]
    if win:
        lo, hi = win[0]
    elif all_dev:
        lo, hi = min(s for s, _ in all_dev), max(e for _, e in all_dev)
    else:
        return {"devices": 0}
    busy, ops, modules, merged_all = [], {}, {}, []
    for ls in devices.values():
        op_events = ls.get("XLA Ops", [])
        merged = _union(_clip([(s, e) for _n, s, e in op_events], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        merged_all.extend(merged)
        for n, s, e in op_events:
            if e > lo and s < hi:
                key = op_name(n)
                ops[key] = ops.get(key, 0.0) + (min(e, hi) - max(s, lo))
        for n, s, e in ls.get("XLA Modules", []):
            if e > lo and s < hi:
                key = _MODULE_ID.sub("", n)
                modules[key] = modules.get(key, 0.0) + (min(e, hi) - max(s, lo))
    gaps = []
    merged = _union(merged_all)
    edge = lo
    for s, e in merged + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [(n, s, e) for evs in host.values() for n, s, e in evs
             if n.startswith(SPAN_PREFIXES)]
    if anchor_ns is not None:
        shift = lo - anchor_ns
        spans += [(n, s + shift, e + shift) for n, s, e in host_spans]
    labelled = []
    for gs, ge in gaps[:top]:
        # the innermost benchmark span wins: a gap inside pf.absorb under
        # service.step_round is the absorb's
        best, score = "host idle", 0.0
        for n, s, e in spans:
            o = min(e, ge) - max(s, gs)
            w = 2.0 if n.startswith(INNER_PREFIXES) else 1.0
            if o > 0 and o * w > score:
                best, score = n, o * w
        labelled.append([best, (ge - gs) * 1e-9])
    n_dev = max(1, len(devices))
    return {
        "devices": len(devices),
        "lines": {p: sorted(ls) for p, ls in devices.items()},
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "modules_s": {k: v * 1e-9 / n_dev for k, v in modules.items()},
        "device_ops": [[n, v * 1e-9 / n_dev] for n, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": labelled,
    }
