"""99th percentile, in ms, of the window's waits for the service lock by
``recommend`` (the program's ``service.lock_wait_s{op=recommend}``
histogram, recorded while its tracer is on)."""


def read(ctx):
    from harness.spans import lock_waits_s

    h = lock_waits_s(ctx, "recommend")
    return h[0].quantile(0.99) * 1e3 if len(h) == 1 else None
