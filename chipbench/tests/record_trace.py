#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction test reads.

    python3 chipbench/tests/record_trace.py OUT.xplane.pb   # on the chip

Inside one ``bench.window`` span: three calls of a jitted matmul program
named ``traced`` under ``service.step_round``, then 80 ms of host sleep
under ``pf.absorb`` with nothing on the device, then one more call.  The
reduction must find the program's device time, a busy share below one, and
the window's longest idle gap, of at least 70 ms, labelled ``pf.absorb``.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record this trace on the chip")

    def traced(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    fn = jax.jit(traced)
    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    jax.block_until_ready(fn(x))
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("service.step_round"):
            for _ in range(3):
                x = jax.block_until_ready(fn(x))
        with jax.profiler.TraceAnnotation("pf.absorb"):
            time.sleep(0.08)
        x = jax.block_until_ready(fn(x))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, out)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
