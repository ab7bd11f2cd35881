#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 chipbench/run.py --workload batch258-mlp.steady --seed 7 \\
        --seconds 30 --trace 0
    python3 chipbench/run.py --workload batch258-mlp.steady --seed 7 \\
        --seconds 10 --sweep 40,60,80      # the knee: one set-up, many rates

A run is one process on the chip it is started on.  It builds the cell's
deployment from the seed (traces, surrogates, sessions), warms every program
the window will run, measures open-loop traffic for ``--seconds``, checks
what the window produced against the plain reference, and prints, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics read from a profiler trace of the window), ``device``
and, last, ``checks``: each number compared with its limit.  The same
numbers close standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def chips_or_exit(chips: int, peaks: dict) -> None:
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {devs[0].platform!r}")
    if kind not in peaks["devices"]:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, found {len(devs)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated ticket rates: print the share of "
                         "tickets done by their deadline at each")
    ap.add_argument("--samples", default=None,
                    help="write every request's latency and every round "
                         "of the window to this JSON file")
    args = ap.parse_args(argv)

    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}")
    chips_or_exit(int(cells[args.workload]["chips"]), peaks)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    from repro.compile_cache import enable_compile_cache

    from harness import cell

    cell.log("compile_cache", dir=enable_compile_cache())
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        cell.sweep(args.workload, args.seed, args.seconds, rates, T_START)
        return 0
    out = cell.run(args.workload, args.seed, args.seconds, bool(args.trace),
                   T_START, peaks, samples=args.samples)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
