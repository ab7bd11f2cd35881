#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference one precision down.

    python3 chipbench/tests/control_check.py --workload batch258-mlp.steady \\
        --seconds 20 --seeds 11 12 13        # on the chip, one process

The configurations state float32 with matmuls at ``highest``, so the control
is the plain reference computed at ``high`` (three bf16 passes), put in the
program's place.  For each seed the cell is set up as a run sets it up and
driven for one window at its own load; then every probe span of the window
is solved by the reference at ``highest`` and at ``high``, and the run's own
comparison (``check.run_checks``, with the configuration's limits) is made
twice: once of the served answers, as a run makes it, and once with the
control's answers in their place.  The control has to come out not correct.
One JSON line per seed, with every descent number of both.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def readings(workload: str, seed: int, seconds: float, found=None,
             rate: float | None = None) -> dict:
    from harness import cell, check, drive

    bench, c, cfg, mix = found if found is not None else \
        cell.find_cell(workload)
    counter = drive.CompileCounter()
    dep, rec, rounds, _ = cell.setup(cfg, mix, seed, annotate=False)
    win, *_ = cell.measure(dep, rec, rounds, mix, seed, seconds, False,
                           counter, rate=rate)
    dep.desk.stop()
    ck = cfg["check"]
    spans = check.window_spans(rec.calls)
    ref = check.solve_reference(dep, spans, "highest", block=ck["block"])
    ctrl_x, _f, ctrl_feas = check.solve_reference(dep, spans, "high",
                                                  block=ck["block"])
    slot = {id(s): i for i, s in enumerate(spans)}
    as_served = [dict(s, x=ctrl_x[slot[id(s)], :len(s["x"])],
                      feas=ctrl_feas[slot[id(s)], :len(s["x"])])
                 if id(s) in slot else s for s in rec.calls]
    out = {"seed": seed}
    for name, calls in (("program", rec.calls), ("control", as_served)):
        checks, info = check.run_checks(dep, calls, win, ck["limits"], seed,
                                        ck["block"], ck["sessions"],
                                        ck["recs"], ref=ref)
        out[name] = {
            "correct": all(v["value"] <= v["limit"] for v in checks.values()),
            **{n: v["value"] for n, v in checks.items()}, **info}
    out["limits"] = ck["limits"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="ticket rate in place of the mix's")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the control is read on the chip")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings(args.workload, seed, args.seconds, rate=args.rate)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
