#!/usr/bin/env python3
"""Drive the served tuning path once on the chip and check its answers.

    python chip_smoke.py               # one chip: kernel + served phases
    python chip_smoke.py --four-chips  # four chips: the sharded service only

The deployment is the paper's (§6): 30 tenants, one per TPCx-BB template,
each a Spark job tuned over the 12 most important knobs (13 encoded dims)
for latency and cost.  Every tenant's surrogate is the paper's tuned DNN, a
4x128 ReLU MLP per objective, trained from seeded traces through the model
registry and served by ``registry.task_spec``.

Phases, all in this one process (a chip belongs to one process):

* kernel — the fused MOGD descent kernel, compiled for the chip, against
  the autodiff oracle ``kernels.ref.mogd_descend`` at the served widths;
* served — ``MOOService`` at its defaults behind a ``FrontDesk``: after a
  warm-up batch ticket per tenant, which opens its session and compiles
  every (G, R) bucket, each tenant submits 3 concurrent ``standard``
  tickets.  Every ticket must end ok, every
  ``recommend`` must be finite, the fused kernel must serve and never be
  fallen back from, and each tenant's frontier hypervolume must be within
  0.5% of the same tenants solved on the scan path;
* ``--four-chips`` — instead of the above: the same tenants on a service
  whose probes are sharded over a 4-device mesh, against the one-device
  service, hypervolume within 0.5% per tenant, with the sharded dispatches
  landing on all four devices.

Timings, telemetry and parity go on earlier lines; the last line of
standard output is ``{"ok": true, "device": {...}}``.  A run that finds no
TPU, or any failed check, exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_TENANTS = 30  # TPCx-BB templates 0..29 (paper §6)
HIDDEN = (128, 128, 128, 128)  # the paper's tuned surrogate DNN
N_TRACES = 512  # per tenant
MAX_EPOCHS = 40  # training cap: keeps the cold run inside its time limit
TICKETS_PER_TENANT = 3
PROBES_PER_TICKET = 16  # FrontDesk.submit's default
HV_TOL = 0.005  # per-tenant hypervolume agreement
KERNEL_TOL = 1e-4  # median |kernel - oracle| of a descended point
MESH_ROUNDS = 3  # rounds each service takes in the four-chip phase


class CheckFailed(Exception):
    """A result the smoke run checks came out wrong."""


def log(tag: str, **fields) -> None:
    print(f"{tag}: {json.dumps(fields, default=str)}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def device_info(want: int) -> dict:
    """The device as JAX reports it; anything but ``want`` TPUs exits."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log("device", **info)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU found: JAX runs on {info['platform']!r}; "
                         f"this smoke run needs the chip")
    if info["count"] < want:
        raise SystemExit(f"need {want} TPU devices, found {info['count']}")
    return info


# ---------------------------------------------------------------------------
# Deployment: 30 registry-served tenants
# ---------------------------------------------------------------------------


def build_tenants(seed: int, n_tenants: int = N_TENANTS,
                  hidden: tuple = HIDDEN, n_traces: int = N_TRACES,
                  max_epochs: int = MAX_EPOCHS) -> list:
    """Register, feed and train one workload per template; returns the
    served ``TaskSpec`` of each."""
    from repro.data import batch_problem, batch_suite, generate_traces, \
        spark_space
    from repro.modelserver import ModelRegistry, TrainerConfig

    log("training", tenants=n_tenants, hidden=hidden, traces=n_traces,
        max_epochs_cap=max_epochs)
    reg = ModelRegistry(trainer=TrainerConfig(
        hidden=tuple(hidden), max_epochs=max_epochs, seed=seed))
    t0 = time.perf_counter()
    specs, errors = [], []
    for w in batch_suite()[:n_tenants]:
        sig = reg.register_workload(("tpcx-bb", w.template), spark_space(),
                                    ("latency_s", "cost_usd"), name=w.name)
        X, Y = generate_traces(batch_problem(w), n_traces,
                               seed=seed * 1000 + w.template)
        reg.observe_batch(sig, X, Y)
        report = reg.retrain(sig)
        check(report.improved, f"{w.name}: first training did not promote")
        errors.append(report.outcome.candidate_error)
        specs.append(reg.task_spec(sig))
    log("trained", seconds=time.perf_counter() - t0,
        val_rel_error_mean=sum(errors) / len(errors),
        val_rel_error_max=max(errors))
    return specs


# ---------------------------------------------------------------------------
# Kernel phase
# ---------------------------------------------------------------------------


def kernel_phase(specs, seed: int, G: int = 8, R: int = 128, S: int = 8,
                 steps: int = 80, interpret: bool = False) -> None:
    """The fused descent kernel on ``G`` tenants' real weights, ``R * S``
    rows per tenant, against the autodiff oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.mogd import MOGDConfig
    from repro.kernels import ref
    from repro.kernels.mogd_descend import descend_batch, plan_from_structure

    cfg = MOGDConfig(steps=steps, multistart=S)
    progs = [s.program for s in specs[:G]]
    plan = plan_from_structure(progs[0].structure)
    check(plan is not None, "served surrogates are not fusable")
    params = jax.tree.map(lambda *a: jnp.stack(a),
                          *[p.params for p in progs])
    k, D = plan.k, plan.dim

    # boxes cut from each tenant's sampled objective range
    rng = np.random.default_rng(seed)
    x0s = rng.random((G, R, S, D), dtype=np.float32)
    los, his = np.empty((G, R, k), np.float32), np.empty((G, R, k), np.float32)
    for g, p in enumerate(progs):
        F = np.asarray(jax.vmap(lambda x: p.apply(p.params, x))(
            jnp.asarray(rng.random((1024, D), dtype=np.float32))))
        lo0, hi0 = F.min(0), F.max(0)
        span = hi0 - lo0
        los[g] = lo0 + span * rng.random((R, k)) * 0.5
        his[g] = los[g] + span * (0.25 + 0.5 * rng.random((R, k)))
    inf = np.full((G, R, k), np.inf, np.float32)
    ones = np.ones((G, R, k), np.float32)
    targets = rng.integers(0, k, (G, R)).astype(np.int32)
    batch = (x0s, los, his, -inf, inf, ones, targets)

    fused = jax.jit(lambda p, *b: descend_batch(
        plan, cfg, p, *b, impl="pallas", interpret=interpret))
    t0 = time.perf_counter()
    compiled = fused.lower(params, *batch).compile()
    compile_s = time.perf_counter() - t0
    got = np.asarray(compiled(params, *batch))
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(compiled(params, *batch)))
    run_s = time.perf_counter() - t0

    @jax.jit
    def oracle(params, x0, lo, hi, ulo, uhi, us, t):  # one group
        mlps = tuple(
            (tuple(l["w"] for l in pj["layers"]),
             tuple(l["b"] for l in pj["layers"]),
             pj["x_mean"], pj["x_std"],
             jnp.reshape(pj["y_mean"], ()), jnp.reshape(pj["y_std"], ()))
            for pj in params)
        return ref.mogd_descend(
            x0, mlps, lo, hi, ulo, uhi, us, t, plan.signs, plan.log_targets,
            steps=cfg.steps, lr=cfg.lr, lr_floor=cfg.lr_floor,
            b1=cfg.adam_b1, b2=cfg.adam_b2, adam_eps=cfg.adam_eps,
            penalty=cfg.penalty, tie_eps=cfg.tie_break_eps)

    def rows(a):  # (G, R, ...) per-cell values -> (G, R*S, ...) per row
        a = np.broadcast_to(a[:, :, None], (G, R, S, *a.shape[2:]))
        return jnp.asarray(a.reshape(G, R * S, *a.shape[3:]))

    # the oracle is the accuracy reference: full-f32 matmuls
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.vmap(oracle)(
            params, jnp.asarray(x0s.reshape(G, R * S, D)),
            *(rows(a) for a in batch[1:]))).reshape(G, R, S, D)
    row_diff = np.abs(got - want).max(-1).reshape(-1)
    p50 = float(np.median(row_diff))
    log("kernel", G=G, rows_per_group=R * S, layer_dims=plan.layer_dims[0],
        steps=cfg.steps, compile_s=compile_s, run_s=run_s,
        max_abs_diff=float(row_diff.max()), p50_row_diff=p50,
        p99_row_diff=float(np.quantile(row_diff, 0.99)),
        rows_over_1e3=int((row_diff > 1e-3).sum()))
    check(bool(np.isfinite(got).all()), "kernel returned non-finite points")
    # a few descents sit on a loss discontinuity (a box edge, a ReLU
    # kink) where any rounding difference sends them down another path,
    # so the maximum is reported and the typical descent is checked
    check(p50 <= KERNEL_TOL,
          f"kernel vs oracle: median row |diff| {p50:.3g} > {KERNEL_TOL}")


# ---------------------------------------------------------------------------
# Hypervolume parity
# ---------------------------------------------------------------------------


def hv_ratios(svc_a, sids_a, svc_b, sids_b) -> list:
    """Per tenant, hypervolume of a's frontier over b's, both normalized
    into the box the two frontiers span (reference point 1.1)."""
    import numpy as np

    from repro.core import hypervolume_2d

    out = []
    for sa, sb in zip(sids_a, sids_b):
        Fa, Fb = svc_a.frontier(sa)[0], svc_b.frontier(sb)[0]
        both = np.concatenate([Fa, Fb])
        lo = both.min(0)
        span = np.maximum(both.max(0) - lo, 1e-12)
        ref = np.full(both.shape[1], 1.1)
        hb = hypervolume_2d((Fb - lo) / span, ref)
        out.append(hypervolume_2d((Fa - lo) / span, ref) / max(hb, 1e-12))
    return out


def step_to_probes(svc, sids, probes: list) -> None:
    """Step each session until it has spent ``probes[i]`` probes."""
    while True:
        behind = [s for s, p in zip(sids, probes)
                  if svc.session_info(s).probes < p
                  and not svc.session_exhausted(s)]
        if not behind:
            return
        svc.step_sessions(behind, origin="reference")


# ---------------------------------------------------------------------------
# Served phase
# ---------------------------------------------------------------------------


def served_phase(specs, tickets_per_tenant: int = TICKETS_PER_TENANT,
                 timeout_s: float = 300.0) -> None:
    import numpy as np

    from repro.exec import ProbeExecutor
    from repro.frontdesk import FrontDesk
    from repro.service import MOOService

    executor = ProbeExecutor(mesh=None)
    svc = MOOService(executor=executor)
    desk = FrontDesk(svc, capacity=len(specs) * tickets_per_tenant)

    def burst(slo: str, per_tenant: int) -> tuple[list, float]:
        """Submit every ticket, then let the dispatcher at them: one
        burst, so the first poll claims the whole coalesced group."""
        t0 = time.perf_counter()
        tickets = [desk.submit(spec=s, slo=slo, n_probes=PROBES_PER_TICKET)
                   for s in specs for _ in range(per_tenant)]
        with desk:
            for t in tickets:
                t.wait(timeout=timeout_s)
        return tickets, time.perf_counter() - t0

    # untimed warm-up: each tenant's first request, a never-shed batch
    # ticket.  It creates the tenant's session and runs its reference
    # solves (one tenant at a time: not coalesced, 5.2 s for 30 tenants on
    # a v5e, past the standard 5 s deadline), and its two rounds compile
    # every (G, R) bucket the served rounds use.
    warm, cold_s = burst("batch", 1)
    check(all(t.ok for t in warm), "warm-up tickets not ok")
    compiles_warm = executor.stats()["compiles"]
    log("warmup", seconds=cold_s, compiles=compiles_warm)

    tickets, wall = burst("standard", tickets_per_tenant)
    states = [t.state for t in tickets]
    lat = sorted(t.latency() for t in tickets if t.ok)
    ex = executor.stats()
    log("served", tickets=len(tickets),
        ok=sum(t.ok for t in tickets),
        states={s: states.count(s) for s in set(states)},
        wall_s=wall,
        ticket_latency_s={"min": lat[0] if lat else None,
                          "p50": lat[len(lat) // 2] if lat else None,
                          "max": lat[-1] if lat else None},
        compiles_in_window=ex["compiles"] - compiles_warm,
        frontdesk_dispatches=desk.stats()["dispatches"])
    log("executor", **{k: ex[k] for k in (
        "structures", "compiles", "dispatches", "probes", "fused_structures",
        "fused_dispatches", "fused_fallbacks", "fill_ratio", "last_bucket")})
    check(all(t.ok for t in tickets),
          f"tickets not ok: {[s for s in states if s != 'done']}")
    sids = list(dict.fromkeys(t.session_id for t in tickets))
    check(len(sids) == len(specs), "one session per tenant")
    recs = [svc.recommend(s) for s in sids]
    check(all(np.isfinite(r.objectives).all() for r in recs),
          "a recommendation is not finite")
    check(ex["fused_dispatches"] > 0, "the fused kernel never served")
    check(ex["fused_fallbacks"] == 0,
          "a structure fell back from the fused kernel to the scan path")

    # the same tenants, the same probe budget, on the scan path
    t0 = time.perf_counter()
    scan = MOOService(executor=ProbeExecutor(mesh=None, backend="jnp"))
    rsids = [scan.create_session(s) for s in specs]
    step_to_probes(scan, rsids, [svc.session_info(s).probes for s in sids])
    ratios = hv_ratios(svc, sids, scan, rsids)
    log("hv_parity", reference="scan path (backend='jnp')",
        seconds=time.perf_counter() - t0,
        probes=[svc.session_info(s).probes for s in sids[:3]],
        min_ratio=min(ratios), max_ratio=max(ratios))
    check(all(abs(r - 1.0) <= HV_TOL for r in ratios),
          f"hypervolume off the scan path by more than {HV_TOL:.1%}: "
          f"{[round(r, 5) for r in ratios]}")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def four_chip_phase(specs, n_devices: int = 4,
                    rounds: int = MESH_ROUNDS) -> None:
    from repro.distributed.sharding import probe_mesh
    from repro.service import MOOService

    runs = {}
    for name, mesh in (("one_device", None),
                       ("mesh", probe_mesh(n_devices))):
        svc = MOOService(mesh=mesh)
        sids = [svc.create_session(s) for s in specs]
        t0 = time.perf_counter()
        for _ in range(rounds):
            svc.step_sessions(sids, origin="smoke")
        runs[name] = (svc, sids)
        ex = svc.executor.stats()
        log(name, devices=1 if mesh is None else n_devices,
            cold_s=time.perf_counter() - t0,
            **{k: ex[k] for k in ("dispatches", "sharded_dispatches",
                                  "fused_dispatches", "fused_fallbacks",
                                  "last_bucket", "last_devices")})
    ratios = hv_ratios(*runs["mesh"], *runs["one_device"])
    ex = runs["mesh"][0].executor.stats()
    log("mesh_parity", reference="one-device service",
        min_ratio=min(ratios), max_ratio=max(ratios))
    check(ex["sharded_dispatches"] > 0, "no dispatch was sharded")
    check(ex["last_devices"] == n_devices,
          f"sharded work landed on {ex['last_devices']} devices, "
          f"not {n_devices}")
    check(ex["fused_fallbacks"] == 0,
          "a structure fell back from the fused kernel to the scan path")
    check(all(abs(r - 1.0) <= HV_TOL for r in ratios),
          f"mesh hypervolume off the one-device run by more than "
          f"{HV_TOL:.1%}: {[round(r, 5) for r in ratios]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds traces, training and kernel inputs")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded-service phase")
    args = ap.parse_args(argv)

    want = 4 if args.four_chips else 1
    device = device_info(want)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    log("compile_cache", dir=enable_compile_cache())
    t0 = time.perf_counter()
    specs = build_tenants(args.seed)
    if args.four_chips:
        four_chip_phase(specs)
    else:
        kernel_phase(specs, args.seed)
        served_phase(specs)
    log("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
