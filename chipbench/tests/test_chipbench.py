"""CPU tests of the on-chip benchmark's harness.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

They check the benchmark's own pieces at small sizes: that every cell's
configuration, mix and metric readers are found by name, that
``BENCHMARK.json`` keeps to its format, the traffic generator, the FLOP and
byte counts, the trace reduction on a trace recorded on a TPU v5e, the
batched surrogate fit against the repository's serial trainer, that a small
run agrees with the plain reference, that a run with the served path broken
underneath comes out not correct, and that ``run.py`` refuses a CPU and a
checkout without the program.
"""

from __future__ import annotations

import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

from harness import cell, flops, traffic  # noqa: E402
from harness import trace as trace_mod  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CPU_PEAKS = {"devices": {"cpu": {"flops_bf16": 1e12,
                                 "hbm_bytes_per_s": 1e11}}}


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


# -- found by name ------------------------------------------------------------


def test_cells_configs_mixes_and_readers_are_found_by_name(bench):
    for w in bench["workloads"]:
        b, c, cfg, mix = cell.find_cell(w["name"])
        assert c["name"] == w["name"]
        assert cfg["name"] == w["config"]
        assert mix["name"] == w["traffic"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(cell.metric_reader(m["name"]))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in cell.cell_metrics(bench, w["name"], False)]
        layers = cell.cell_metrics(bench, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layers


# -- BENCHMARK.json format ----------------------------------------------------


def test_benchmark_keys_names_units_and_chips(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and re.match(r"^[A-Za-z0-9_.\-/]+$", p)
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        names.add(c["name"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    seen = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in seen
        seen.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024


# -- traffic --------------------------------------------------------------


def test_traffic_is_deterministic_and_hits_its_rates_and_zipf_shares():
    mix = {"pattern": "recurring", "ticket_rate_per_s": 50, "zipf_s": 1.0,
           "recommend_rate_per_s": 100}
    a = traffic.schedule(mix, 2**33 + 5, 30.0, 258)
    b = traffic.schedule(mix, 2**33 + 5, 30.0, 258)
    c = traffic.schedule(mix, 12, 30.0, 258)
    assert a == b and a != c
    for ev in (a, c):
        tickets = [e for e in ev if e.kind == "ticket"]
        recs = [e for e in ev if e.kind == "recommend"]
        assert len(tickets) == 1500 and len(recs) == 3000
        assert all(0.0 <= e.due_s < 30.0 for e in ev)
        counts = np.bincount([e.tenant for e in tickets], minlength=258)
        top = np.sort(counts)[::-1]
        h = np.sum(1.0 / np.arange(1, 259))
        assert abs(top[0] / 1500 - 1.0 / h) < 0.01
        assert abs(top[1] / 1500 - 0.5 / h) < 0.01
    # the same work in another order: equal count per rank
    ca = np.sort(np.bincount([e.tenant for e in a if e.kind == "ticket"],
                             minlength=258))
    cc = np.sort(np.bincount([e.tenant for e in c if e.kind == "ticket"],
                             minlength=258))
    assert (ca == cc).all()
    gaps = np.diff([e.due_s for e in a if e.kind == "ticket"])
    assert abs(gaps.mean() - 1 / 50) < 0.002
    assert abs(np.std(gaps) / gaps.mean() - 1.0) < 0.1  # exponential


def test_onboard_draws_new_tenants_without_replacement():
    mix = {"pattern": "onboard", "ticket_rate_per_s": 4, "warm_tenants": 16}
    ev = traffic.schedule(mix, 3, 30.0, 258)
    warm = set(traffic.warm_tenants(mix, 3, 258))
    got = [e.tenant for e in ev]
    assert len(got) == 120 and len(set(got)) == 120
    assert not warm & set(got) and len(warm) == 16
    nxt = traffic.schedule(mix, 3, 30.0, 258, skip=120)
    assert not set(e.tenant for e in nxt) & set(got)
    with pytest.raises(ValueError):
        traffic.schedule(mix, 3, 100.0, 258)


# -- FLOP and byte counts -----------------------------------------------------


def test_flop_and_byte_counts_match_hand_counts():
    dims = (13, 128, 128, 128, 128, 1)
    edges = 13 * 128 + 3 * 128 * 128 + 128
    assert flops.step_flops("mlp", 2, dims=dims) == 4 * edges * 2
    assert flops.param_bytes("mlp", 2, dims=dims) == 4 * 2 * (
        edges + 4 * 128 + 1)
    n, d = 410, 13
    gp = 2 * n * d + 3 * n + n * n + 2 * n
    assert flops.step_flops("gp", 2, n_train=n, d=d, with_std=True) == \
        2 * 2 * gp
    assert flops.step_flops("gp", 1, n_train=n, d=d) == 2 * (2 * n * d + 3 * n)
    f, b = flops.dispatch_cost(cells=16, groups=1, starts=8, steps=80, k=2,
                               d=13, row_flops=100.0, group_bytes=1000.0)
    assert f == 16 * 8 * 80 * 100.0
    assert b == 1000.0 + 16 * (8 * 13 + 4 + 1 + 13 + 2 + 1) * 4
    share, bound = flops.roofline_share(1e12, 1e9, 1.0, 1e13, 1e11)
    assert bound == "compute" and share == pytest.approx(0.1)


# -- trace reduction ----------------------------------------------------------


FIXTURE = BENCH / "tests" / "data" / "trace_small.xplane.pb"


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_trace_reduction_on_a_trace_recorded_on_the_chip():
    red = trace_mod.reduce(trace_mod.load(str(FIXTURE)))
    assert red["devices"] == 1  # the TPU; the host and Megascale planes are not devices
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] > 0.08
    assert any(k.startswith("jit_traced") for k in red["modules_s"])
    total = sum(red["modules_s"].values())
    assert total <= red["busy_s"] * 1.001
    gap_label, gap_s = red["idle_gaps"][0]
    assert gap_label == "pf.absorb" and gap_s >= 0.07
    assert red["device_ops"] and red["device_ops"][0][1] > 0.0


def test_trace_reduction_of_synthetic_planes():
    ms = 1e6
    planes = {
        "/host:CPU": {"t": [("bench.window", 0.0, 100 * ms),
                            ("service.step_round", 0.0, 40 * ms),
                            ("pf.absorb", 50 * ms, 90 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": [("a", 10 * ms, 20 * ms), ("b", 15 * ms, 30 * ms),
                        ("c", 95 * ms, 110 * ms)],
            "XLA Modules": [("jit_traced(7)", 10 * ms, 30 * ms)]},
    }
    red = trace_mod.reduce(planes)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["modules_s"] == {"jit_traced": pytest.approx(0.02)}
    assert red["idle_gaps"][0] == ["pf.absorb", pytest.approx(0.065)]
    assert red["idle_gaps"][1] == ["service.step_round", pytest.approx(0.01)]


def test_service_busy_counts_each_round_as_far_as_it_overlaps_the_window():
    from types import SimpleNamespace

    win = SimpleNamespace(t0=10.0, t_close=20.0)
    rounds = [(9.0, 11.0), (12.0, 13.5), (19.0, 21.0), (21.0, 22.0)]
    ctx = SimpleNamespace(win=win, round_intervals=rounds)
    read = cell.metric_reader("service_busy_pct")
    assert read(ctx) == pytest.approx(100.0 * (1.0 + 1.5 + 1.0) / 10.0)


# -- set-up fits --------------------------------------------------------------


def test_batched_mlp_fit_lands_in_the_serial_trainers_band():
    import jax

    from harness import deploy, fit, suite
    from repro.modelserver.trainer import TrainerConfig, train_candidate

    consts = {k: v[:3] for k, v in suite.batch_suite(258, 7).items()}
    key = deploy.seed_key(11)
    X, Y = suite.make_traces(key, consts, 512)
    _, _, err = fit.fit_mlps(key, X, Y, (128,) * 4, 40, 3e-3, 0.05)
    Xh, Yh = np.asarray(X, np.float64), np.asarray(Y, np.float64)
    serial = [train_candidate(Xh[w], Yh[w], TrainerConfig(
        hidden=(128,) * 4, max_epochs=40, seed=0)).candidate_error
        for w in range(3)]
    err = np.asarray(jax.device_get(err))
    assert np.all(err < 1.5 * max(serial)) and np.all(err > 0.5 * min(serial))


# -- a run on the CPU at a small size -----------------------------------------


def tiny(config: str, traffic_name: str, **mix_overrides):
    """A configuration and a mix at a size the CPU runs in seconds (the
    onboarding mix has no cell yet, so pairs are named, not cells)."""
    cfg = cell.load_json(BENCH / "configs" / f"{config}.json")
    mix = cell.load_json(BENCH / "traffic" / f"{traffic_name}.json")
    b = copy.deepcopy(cell.load_json(ROOT / "BENCHMARK.json"))
    name = f"{config}.{traffic_name}"
    c = {"name": name, "config": config, "traffic": traffic_name,
         "chips": 1}
    b["workloads"].append(c)
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    cfg["workloads"] = 8
    if cfg["surrogate"]["kind"] == "mlp":
        cfg["surrogate"].update(hidden=[32, 32], epochs=5)
    cfg["surrogate"]["traces"] = 128
    cfg["service"].update(mogd_steps=8, multistart=2,
                          warm_store_capacity=512, warm_frontier_max=48)
    cfg["check"].update(block=8, sessions=8, recs=60)
    if mix["pattern"] == "recurring":
        mix.update(ticket_rate_per_s=6, recommend_rate_per_s=20)
    else:
        mix.update(ticket_rate_per_s=1, warm_tenants=2, max_groups=4)
    mix.update(mix_overrides)
    return b, c, cfg, mix


def tiny_run(pair=("tpcxbb258-mlp", "steady"), seed: int = 5,
             seconds: float = 2.0, **mix_overrides):
    found = tiny(*pair, **mix_overrides)
    return cell.run(found[1]["name"], seed, seconds, False,
                    time.perf_counter(), CPU_PEAKS, found=found)


@pytest.mark.parametrize("pair", [("tpcxbb258-mlp", "steady"),
                                  ("tpcxbb258-gp", "steady-gp"),
                                  ("tpcxbb258-mlp", "onboard")])
def test_small_run_agrees_with_the_plain_reference(pair):
    out = tiny_run(pair, seconds=3.0 if pair[1] == "onboard" else 2.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["descent_gap_p50"]["value"] < 1e-5
    assert out["checks"]["descent_gap_p99"]["value"] < 1e-3
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def _break_absorb(monkeypatch):
    from repro.core.progressive_frontier import ProgressiveFrontier

    orig = ProgressiveFrontier.absorb

    def absorb(self, state, cells, res, pop=None):
        if state.probes > 4:  # once open, a step leaves the state as it was
            state.probes += len(cells)
            return None
        return orig(self, state, cells, res, pop=pop)

    monkeypatch.setattr(ProgressiveFrontier, "absorb", absorb)


def _break_half_batch(monkeypatch):
    from repro.exec import ProbeExecutor

    orig = ProbeExecutor.solve_requests

    def solve_requests(self, requests, *a, **kw):
        x, f, feas = orig(self, requests, *a, **kw)
        h = (len(x) + 1) // 2  # the second half copies the first's answers
        for arr in (x, f, feas):
            arr[h:] = arr[:len(arr) - h]
        return x, f, feas

    monkeypatch.setattr(ProbeExecutor, "solve_requests", solve_requests)


def _break_answers(monkeypatch):
    from repro.service import MOOService

    orig = MOOService.recommend

    def recommend(self, *a, **kw):
        rec = orig(self, *a, **kw)
        rec.objectives = rec.objectives * (1.0 + 1e-3)
        return rec

    monkeypatch.setattr(MOOService, "recommend", recommend)


def _break_probe_answers(monkeypatch):
    from repro.exec import ProbeExecutor

    orig = ProbeExecutor.solve_requests

    def solve_requests(self, requests, *a, **kw):
        x, f, feas = orig(self, requests, *a, **kw)
        x = np.asarray(x).copy()
        x[:, 4] = np.clip(x[:, 4] + 0.01, 0.0, 1.0)  # memory_fraction
        return x, f, feas

    monkeypatch.setattr(ProbeExecutor, "solve_requests", solve_requests)


def _break_one_group(monkeypatch):
    from repro.exec import ProbeExecutor

    orig = ProbeExecutor.solve_requests

    def solve_requests(self, requests, *a, **kw):
        requests = list(requests)
        x, f, feas = orig(self, requests, *a, **kw)
        if len(requests) > 1:  # the last tenant of a coalesced dispatch
            B = int(np.shape(requests[-1].x0s)[0])
            x = np.asarray(x).copy()
            x[-B:, 4] = np.clip(x[-B:, 4] + 0.01, 0.0, 1.0)
        return x, f, feas

    monkeypatch.setattr(ProbeExecutor, "solve_requests", solve_requests)


@pytest.mark.parametrize("fault", [_break_absorb, _break_half_batch,
                                   _break_answers, _break_probe_answers,
                                   _break_one_group])
def test_a_broken_served_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    # enough load that rounds coalesce several tenants
    out = tiny_run(seed=9, ticket_rate_per_s=30)
    assert out["attempted"] > 0
    assert not out["correct"], out["checks"]


def test_control_is_compared_by_the_runs_own_check():
    """The control's readings go through the run's comparison; on the CPU
    there is no lower matmul precision, so both sides read as served."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chipbench_control_check", BENCH / "tests" / "control_check.py")
    control_check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control_check)
    found = tiny("tpcxbb258-mlp", "steady")
    out = control_check.readings(found[1]["name"], 5, 2.0, found=found)
    for side in ("program", "control"):
        assert out[side]["correct"], out
        assert out[side]["cells"] > 0
        assert {"descent_gap_p50", "descent_gap_p99", "descent_off_share",
                "descent_span_off_max"} <= set(out[side])


# -- run.py refuses ----------------------------------------------------------


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "batch258-mlp.steady", "--seed", str(2**33), "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_a_cpu_platform():
    r = _run_py(ROOT)
    assert r.returncode != 0
    assert not r.stdout.strip().startswith("{")
    assert "no TPU" in r.stderr


def test_run_py_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0 and "{" not in r.stdout
