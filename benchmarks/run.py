"""Benchmark runner: one module per paper table/figure + the roofline and
planner harnesses.

    python -m benchmarks.run            # quick mode (CI-sized)
    python -m benchmarks.run --full     # paper-sized workload counts
    python -m benchmarks.run --only expt1_batch2d,roofline
"""

from __future__ import annotations

import argparse
import importlib
import json
import time
import traceback

SUITES = [
    "expt1_batch2d",     # Fig. 4: batch 2D vs WS/NC/Evo
    "expt2_streaming",   # Fig. 5: streaming 2D/3D + Evo inconsistency
    "expt3_recommend",   # Fig. 6a-d: PF-WUN vs weighted-SO (accurate)
    "expt4_uncertain",   # Fig. 6e-f: learned models + uncertainty
    "speedup",           # §6.1: 2-50x claim
    "solver_compare",    # §4.2: MOGD vs reference solver
    "roofline",          # §Roofline: dry-run artifact table
    "planner_frontier",  # beyond-paper: plan-space Pareto frontier
    "service_throughput",  # cross-rectangle batching + MOO service rates
    "expt5_multistage",  # composed per-stage vs flattened tuning (DAG)
    "expt6_adaptive",    # online model server: drift -> warm re-solve
    "kernelbench",       # kernel vs oracle + VMEM accounting
    "expt7_scaling",     # device-scaling: mesh probe sharding 1->8 devices
    "expt8_serving",     # frontdesk admission plane: open-loop QPS/SLO
    "expt9_restart",     # durable frontier plane: warm restart from vault
    "obsbench",          # observability plane: instrumentation overhead
    "expt10_budget",     # learned probe-budget routing: bandit vs uniform
]


def run_suite(names, quick: bool) -> tuple[dict, list]:
    """Run benchmark modules by name; returns (summaries, failures).

    The single orchestration path shared by this full runner and the CI
    smoke entry point (``scripts/run_benchmarks.py``)."""
    summaries, failures = {}, []
    for name in names:
        print(f"\n########## {name} ({'quick' if quick else 'full'}) "
              f"##########")
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            t = time.perf_counter()
            summary = mod.run(quick=quick)
            if not isinstance(summary, dict) or not summary:
                raise ValueError(
                    f"{name}.run() returned empty/non-dict summary")
            summary["_wall_s"] = time.perf_counter() - t
            summaries[name] = summary
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((name, repr(e)))
    return summaries, failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="results/bench_summary.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = args.only.split(",") if args.only else SUITES
    t0 = time.perf_counter()
    summaries, failures = run_suite(names, quick=not args.full)
    print(f"\n===== benchmark summaries ({time.perf_counter()-t0:.0f}s) =====")
    print(json.dumps(summaries, indent=1, default=str))
    try:
        import pathlib

        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(
            json.dumps(summaries, indent=1, default=str))
    except OSError:
        pass
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
