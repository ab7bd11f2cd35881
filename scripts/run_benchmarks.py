#!/usr/bin/env python
"""Benchmark entry point shared by CI and local runs.

Runs the named benchmark modules (``benchmarks/<name>.py``), requires each
to persist a machine-readable ``results/BENCH_<name>.json``, and fails
loudly on missing, malformed, or empty output — the perf trajectory is
only useful if every run leaves a valid artifact behind.  A cross-suite
roll-up (each suite's summary plus its ``_wall_s`` wall time) lands in
``results/bench_summary.json``.

    PYTHONPATH=src python scripts/run_benchmarks.py --smoke
    PYTHONPATH=src python scripts/run_benchmarks.py --only expt5_multistage
    PYTHONPATH=src python scripts/run_benchmarks.py --validate-only

``--smoke`` runs the CI-sized quick mode (the ``bench-smoke`` CI job);
without it the paper-sized full workloads run.  ``--validate-only`` just
re-checks the artifacts from a previous run.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
RESULTS = REPO / "results"

# benchmarks with a smoke mode cheap enough for per-PR CI
DEFAULT = ["service_throughput", "expt5_multistage", "expt6_adaptive",
           "kernelbench", "expt7_scaling", "expt8_serving",
           "expt9_restart", "obsbench", "expt10_budget"]


def validate_artifact(name: str) -> dict:
    """Load and sanity-check one BENCH json; raises on bad output."""
    path = RESULTS / f"BENCH_{name}.json"
    if not path.exists():
        raise FileNotFoundError(f"{path} was not written")
    text = path.read_text()
    if not text.strip():
        raise ValueError(f"{path} is empty")
    record = json.loads(text)  # malformed JSON raises here
    if not isinstance(record, dict) or not record:
        raise ValueError(f"{path}: expected a non-empty JSON object")
    summary = record.get("summary")
    if not isinstance(summary, dict) or not summary:
        raise ValueError(f"{path}: missing or empty 'summary'")
    if record.get("benchmark") != name:
        raise ValueError(f"{path}: benchmark field "
                         f"{record.get('benchmark')!r} != {name!r}")
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized quick mode (quick=True)")
    ap.add_argument("--only", default=None,
                    help=f"comma-separated benchmark modules "
                         f"(default: {','.join(DEFAULT)})")
    ap.add_argument("--validate-only", action="store_true",
                    help="only re-validate existing BENCH_*.json artifacts")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(DEFAULT)
    failures = []
    if not args.validate_only:
        sys.path.insert(0, str(REPO))  # import benchmarks.* from anywhere
        from benchmarks.run import run_suite  # the one orchestration path
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        summaries, failures = run_suite(names, quick=args.smoke)
        # one cross-suite roll-up with per-suite wall time (_wall_s) so
        # CI runs leave a perf trajectory, not just pass/fail artifacts
        try:
            RESULTS.mkdir(parents=True, exist_ok=True)
            (RESULTS / "bench_summary.json").write_text(
                json.dumps(summaries, indent=1, default=str))
        except OSError as e:
            failures.append(("bench_summary", repr(e)))
    for name in names:
        if any(f[0] == name for f in failures):
            continue
        try:
            validate_artifact(name)
            print(f"[{name}] artifact OK: results/BENCH_{name}.json")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
    if failures:
        for name, err in failures:
            print(f"FAIL {name}: {err}", file=sys.stderr)
        raise SystemExit(1)
    print("\nall benchmark artifacts valid")


if __name__ == "__main__":
    main()
