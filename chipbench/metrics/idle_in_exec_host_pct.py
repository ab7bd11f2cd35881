"""Percent of the device's idle time in the traced window that falls inside
an open ``exec.materialize``, ``exec.pack`` or ``exec.unpack`` span of the
program, the spans mapped onto the profile's clock by the tracer's
anchors."""


def read(ctx):
    from harness.spans import (EXEC_HOST, idle_share_in, newest_trace_file,
                               program_spans)

    spans = program_spans(ctx)
    if not any(s.name in EXEC_HOST for s in spans) or not ctx.trace:
        return None
    tracer = ctx.dep.service.obs.tracer
    path = newest_trace_file()
    if path is None:
        return None
    from repro.obs import profile_anchors

    anchors = profile_anchors(path)
    if not anchors or not tracer.anchors():
        return None
    return idle_share_in(path, spans, tracer.profile_clock(anchors),
                         EXEC_HOST)
