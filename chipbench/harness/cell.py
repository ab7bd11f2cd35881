"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything a cell is made of is found by name: its configuration in
``configs/<config>.json``, its traffic mix in ``traffic/<traffic>.json``,
and each metric's reader in ``metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import time
from pathlib import Path

import numpy as np

from . import check, deploy, drive, flops, traffic
from . import trace as trace_mod

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
RESULTS = ROOT / "results" / "chipbench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, config, mix)`` for a workload name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT / cfgs[cell["config"]]["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, mix


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Context:
    """What the metric readers read."""

    dep: object
    mix: dict
    setup_s: float
    win: object
    deadline_s: float
    rounds: list
    round_intervals: list
    calls: list
    exec_delta: dict
    trace: dict | None
    peak: dict


def log(tag: str, **fields) -> None:
    print(f"{tag}: {json.dumps(fields, default=str)}", flush=True)


def setup(cfg: dict, mix: dict, seed: int, annotate: bool):
    """Build the deployment and warm every program the window will run."""
    import jax

    from repro.exec import bucket

    t = {}

    def phase(name, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        t[name] = time.perf_counter() - t0
        log("phase", name=name, seconds=t[name])
        return out

    def build():
        dep = deploy.build(cfg, seed)
        jax.block_until_ready(dep.tenants[-1].program.params)
        return dep

    dep = phase("build_s", build)
    spans = drive.HostSpans()
    rec = drive.Recorder(dep, spans)
    rounds = drive.RoundLog(dep, spans)
    rounds.spans = spans
    if annotate:
        drive.annotate_engine(spans)
    s, k = cfg["service"], len(cfg["objectives"])
    phase("warm_store_s", drive.warm_store_passes, k,
          s["warm_store_capacity"])
    phase("warm_frontier_s", drive.warm_frontier_sizes,
          s["warm_frontier_max"], k)
    dep.desk.start()
    warm = traffic.warm_tenants(mix, seed, len(dep.tenants))
    phase("open_s", drive.open_sessions, dep, warm, s["setup_probes"])
    rec.phase = "warm"
    t["groups"] = bucket(min(len(warm), mix["max_groups"]))
    phase("warm_buckets_s", drive.warm_buckets, dep, warm[0], t["groups"])
    rec.phase = "setup"
    return dep, rec, rounds, t


def _exec_stats(dep) -> dict:
    st = dep.executor.stats()
    return {k: st[k] for k in ("compiles", "dispatches", "useful_rows",
                               "padded_rows", "fused_dispatches",
                               "fused_fallbacks")}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def measure(dep, rec, rounds, mix: dict, seed: int, seconds: float,
            trace: bool, counter, rate: float | None = None,
            skip: int = 0):
    """One open-loop window; returns ``(window, exec delta, compiles in the
    window, traced)``, ``traced`` the trace's directory, the host spans and
    the ``perf_counter_ns`` at which the window's own span opened."""
    import jax

    events = traffic.schedule(mix, seed, seconds, len(dep.tenants),
                              rate=rate, skip=skip)
    spans = rounds.spans
    traced = None
    ann = {}
    gcw = drive.GcWatch()

    def on_open():
        nonlocal traced
        rec.phase = "window"
        rounds.on = True
        ann["ex"] = _exec_stats(dep)
        ann["cc"] = counter.snapshot()
        gcw.start()
        if trace:
            log_dir = str(RESULTS / f"trace-{os.getpid()}-{time.time_ns()}")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            ann["span"] = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
            traced = {"dir": log_dir, "anchor_ns": time.perf_counter_ns(),
                      "spans": spans.rows}
            ann["span"].__enter__()
            spans.on = True

    def on_close():
        if trace:
            spans.on = False
            ann["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        ann["cc1"] = counter.snapshot()
        ann["ex1"] = _exec_stats(dep)
        ann["gc"] = gcw.stop()
        rec.phase = "drain"
        rounds.on = False

    win = drive.run_window(dep, events, seconds, mix, spans,
                           on_open=on_open, on_close=on_close)
    compiles = dict(zip(("executables", "traced", "cache_misses", "n"),
                        np.subtract(ann["cc1"], ann["cc"]).tolist()))
    compiles["names"] = counter.names[ann["cc"][3]:ann["cc1"][3]]
    win.gc = ann["gc"]
    return win, _delta(ann["ex"], ann["ex1"]), compiles, traced


def device_info() -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    stats = devs[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    if stats:
        info["memory_peak_bytes"] = int(peak)
    return info


def write_samples(path: str, win, rounds: list) -> None:
    """Every request's due time and latency, and every round, in seconds
    from the window's opening: the raw data behind the tails."""
    t0 = win.t0
    tickets = []
    for row in win.tickets:
        t = row["ticket"]
        end = t.finished_at
        tickets.append([row["due"] - t0, None if end is None
                        else end - row["due"], bool(t.ok), row["tenant"]])
    recs = [[r["due"] - t0, r["end"] - r["due"], bool(r["follow"])]
            for r in win.recs]
    rnd = [[r["t0"] - t0, r["t1"] - t0, r["sessions"], r["probes"],
            r["timing"]] for r in rounds]
    with open(path, "w") as f:
        json.dump({"tickets": tickets, "recommends": recs, "rounds": rnd}, f,
                  default=float)


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, peaks: dict, found=None,
        samples: str | None = None) -> dict:
    """A full run; returns the result line's object.  ``found`` replaces
    :func:`find_cell`'s ``(benchmark, cell, config, mix)`` (tests);
    ``samples`` names a file for :func:`write_samples`."""
    bench, cell, cfg, mix = found if found is not None else \
        find_cell(workload)
    counter = drive.CompileCounter()
    dep, rec, rounds, setup_t = setup(cfg, mix, seed, annotate=trace)
    deadline = _deadline(mix)
    # the window opens here: everything before it is set-up
    setup_s = time.perf_counter() - t_start
    setup_t["setup_s"] = setup_s
    log("setup", **setup_t, fit_rel_error=dep.fit_error,
        tenants=len(dep.tenants))
    win, ex, compiles, traced = measure(dep, rec, rounds, mix, seed,
                                        seconds, trace, counter)
    dep.desk.stop()
    log("window", compiles=compiles, drained=win.drained,
        tickets=len(win.tickets), recommends=len(win.recs),
        fused_dispatches=ex["fused_dispatches"],
        fused_fallbacks_total=dep.executor.fused_fallbacks,
        executor_builds=ex["compiles"], rounds=len(rounds.rounds),
        groups_max=groups_max(rec.calls), groups_warmed=setup_t["groups"],
        frontier_max=_frontier_max(dep), generator_late_s=_lateness(win),
        gc=win.gc)
    if samples:
        write_samples(samples, win, rounds.rounds)
    device = device_info()
    reduced = None
    if trace:
        path = trace_mod.find_xplane(traced["dir"])
        reduced = trace_mod.reduce(
            trace_mod.load(path), host_spans=traced["spans"],
            anchor_ns=traced["anchor_ns"]) if path else None
        if reduced and not reduced["devices"]:
            reduced = None  # no accelerator plane: nothing to read
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            log("trace", file=path, lines=reduced["lines"],
                modules_s=reduced["modules_s"])
    ctx = Context(dep=dep, mix=mix, setup_s=setup_s, win=win,
                  deadline_s=deadline, rounds=rounds.rounds,
                  round_intervals=rounds.intervals, calls=rec.calls,
                  exec_delta=ex, trace=reduced,
                  peak=peaks["devices"][device["kind"]])
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    t0 = time.perf_counter()
    ck = cfg["check"]
    checks, info = check.run_checks(dep, rec.calls, win, ck["limits"], seed,
                                    ck["block"], ck["sessions"], ck["recs"])
    info["seconds"] = time.perf_counter() - t0
    log("check", **info)
    failed = sum(1 for r in win.tickets if not r["ticket"].ok)
    failed += sum(1 for r in win.recs if r["rec"] is None)
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and win.drained)
    out = {"correct": bool(correct),
           "attempted": len(win.tickets) + len(win.recs),
           "failed": int(failed), "metrics": metrics, "device": device}
    if reduced:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def idle_percent(ctx):
    """Percent of the traced window with no operation on the device."""
    if not ctx.trace or not ctx.trace.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def descent_cost(ctx) -> tuple[float, float]:
    """Operations and minimum bytes of the window's descent dispatches."""
    dep = ctx.dep
    cfg = dep.config
    k, d = len(cfg["objectives"]), 13
    s = cfg["service"]
    if dep.kind == "mlp":
        dims = (d, *cfg["surrogate"]["hidden"], 1)
        kw = dict(dims=dims)
    else:
        n = int(dep.tenants[0].weights[0]["x_train"].shape[0])
        kw = dict(n_train=n, d=d, with_std=dep.alpha > 0.0)
    row = flops.step_flops(dep.kind, k, **kw)
    group = flops.param_bytes(dep.kind, k, **kw)
    dispatches: dict[float, list] = {}
    for c in ctx.calls:
        if c["phase"] == "window":
            dispatches.setdefault(c["t"], []).append(len(c["x"]))
    total_f = total_b = 0.0
    for sizes in dispatches.values():
        f, b = flops.dispatch_cost(sum(sizes), len(sizes), s["multistart"],
                                   s["mogd_steps"], k, d, row, group)
        total_f += f
        total_b += b
    return total_f, total_b


def _deadline(mix: dict) -> float:
    from repro.frontdesk.admission import SLO_CLASSES

    return float(SLO_CLASSES[mix["slo"]].deadline_s)


def groups_max(calls: list) -> int:
    """The most tenants one dispatch of the window coalesced (the warm-up
    must have built its group bucket)."""
    per: dict[float, int] = {}
    for c in calls:
        if c["phase"] == "window":
            per[c["t"]] = per.get(c["t"], 0) + 1
    return max(per.values(), default=0)


def _frontier_max(dep) -> int:
    """The largest live frontier of any session (the warm-up must have
    compiled the Pareto mask up to this size)."""
    svc = dep.service
    with svc._lock:
        return max((s.state.store.n_points for s in svc._sessions.values()
                    if s.state is not None), default=0)


def _lateness(win) -> dict:
    late = np.asarray([r["late"] for r in win.tickets]) if win.tickets \
        else np.zeros(1)
    rl = np.asarray([r["start"] - r["due"] for r in win.recs]) if win.recs \
        else np.zeros(1)
    return {"ticket_p50": float(np.median(late)),
            "ticket_max": float(late.max()),
            "recommend_p50": float(np.median(rl)),
            "recommend_p99": float(np.quantile(rl, 0.99))}


def sweep(workload: str, seed: int, seconds: float, rates: list,
          t_start: float) -> None:
    """One set-up, then one window per rate: the share of tickets done by
    their deadline, and the backlog at the window's middle and close."""
    bench, cell, cfg, mix = find_cell(workload)
    counter = drive.CompileCounter()
    dep, rec, rounds, setup_t = setup(cfg, mix, seed, annotate=False)
    deadline = _deadline(mix)
    log("setup", **setup_t, setup_s=time.perf_counter() - t_start)
    skip = 0
    for rate in rates:
        first = len(rec.calls)
        win, ex, compiles, _ = measure(dep, rec, rounds, mix, seed, seconds,
                                       False, counter, rate=rate, skip=skip)
        if mix["pattern"] == "onboard":
            skip += len(win.tickets)
        lat = drive.ticket_latencies(win, deadline)
        ok = [r["ticket"].ok and lat[i] <= deadline
              for i, r in enumerate(win.tickets)]

        def backlog(tau):
            return sum(1 for r in win.tickets if r["due"] <= tau and (
                r["ticket"].finished_at is None
                or r["ticket"].finished_at > tau))

        log("sweep", rate=rate, tickets=len(win.tickets),
            attained=float(np.mean(ok)) if ok else 0.0,
            p50_s=float(np.median(lat)) if len(lat) else None,
            p95_s=float(np.quantile(lat, 0.95)) if len(lat) else None,
            backlog_mid=backlog(win.t0 + seconds / 2),
            backlog_close=backlog(win.t_close),
            rounds=len(rounds.rounds), compiles=compiles["executables"],
            groups_max=groups_max(rec.calls[first:]), gc=win.gc,
            fill=(ex["useful_rows"] / ex["padded_rows"]
                  if ex["padded_rows"] else None),
            late=_lateness(win))
        rounds.rounds.clear()
    dep.desk.stop()
