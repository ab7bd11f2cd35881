"""Build one configuration's deployment from its file and a seed.

Every tenant is one TPCx-BB workload: its traces come from the ground truth
in ``suite.py``, its surrogates are fitted here (``fit.py``), and the fitted
models are wrapped in the system's own regressor, snapshot and task classes,
the way the model registry serves a promoted version, so that the service
sees exactly what a registry-backed deployment would give it.  The weights
and GP factors stay with the deployment: the plain reference reads them from
here, never from the system.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import fit, suite


@dataclasses.dataclass
class Tenant:
    index: int
    name: str
    spec: object  # repro TaskSpec
    program: object  # the ParamProgram the executor sees
    weights: list  # per objective: reference weights (numpy, float32)


@dataclasses.dataclass
class Deployment:
    config: dict
    tenants: list
    service: object
    desk: object
    executor: object
    kind: str  # "mlp" | "gp"
    alpha: float
    fit_error: float  # mean gate-split relative error over tenants
    by_program: dict  # id(ParamProgram) -> tenant index


def seed_key(seed: int, impl: str = "threefry2x32"):
    """A JAX key from any whole-number seed (64 bits and more welcome).
    ``impl="rbg"`` gives a key for XLA's bit generator, which a TPU runs
    far faster than threefry: the fit draws billions of dropout bits."""
    import jax

    n = 4 if impl == "rbg" else 2
    words = np.random.SeedSequence(int(seed)).generate_state(n)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32), impl=impl)


def _mlp_models(cfg, params, moments):
    """(W, k) stacked device arrays -> per-tenant MLPRegressor tuples."""
    import jax

    from repro.models.mlp import MLPRegressor, MLPSpec

    host = jax.device_get((params, moments))
    params, (xm, xs, ym, ys) = host
    W, k = xm.shape[:2]
    hidden = tuple(cfg["surrogate"]["hidden"])
    dropout = float(cfg["surrogate"]["dropout"])
    per = []
    for w in range(W):
        per.append([
            ([{"w": layer["w"][w, j], "b": layer["b"][w, j]}
              for layer in params],
             xm[w, j], xs[w, j], np.reshape(ym[w, j], (1,)),
             np.reshape(ys[w, j], (1,)))
            for j in range(k)])
    dev = jax.device_put(per)
    models = []
    for w in range(W):
        models.append(tuple(
            MLPRegressor(spec=MLPSpec(in_dim=suite.DIM, hidden=hidden,
                                      out_dim=1, dropout=dropout),
                         params=layers, x_mean=a, x_std=b, y_mean=c,
                         y_std=d, dropout=max(dropout, 0.05))
            for layers, a, b, c, d in dev[w]))
    weights = [[{"layers": [{"w": lw["w"], "b": lw["b"]} for lw in layers],
                 "x_mean": a, "x_std": b, "y_mean": c, "y_std": d}
                for layers, a, b, c, d in per[w]] for w in range(W)]
    return models, weights


def _gp_models(factors):
    import jax.numpy as jnp

    from repro.models.gp import GPRegressor

    models, weights = [], []
    for per_obj in factors:
        ms, ws = [], []
        for f in per_obj:
            f32 = {n: np.asarray(v, np.float32) for n, v in f.items()}
            ms.append(GPRegressor(**{n: jnp.asarray(v) for n, v in f32.items()}))
            ws.append(f32)
        models.append(tuple(ms))
        weights.append(ws)
    return models, weights


def build(cfg: dict, seed: int, interpret: bool | None = None) -> Deployment:
    """Traces, surrogates, task specs, service and front desk for one seed."""
    import jax

    from repro.core import MOGDConfig
    from repro.core.task import Objective, TaskSpec, UtopiaNearest
    from repro.data import spark_space
    from repro.exec import ProbeExecutor
    from repro.frontdesk import FrontDesk
    from repro.modelserver.registry import ModelSnapshot
    from repro.service import MOOService

    k_tr = seed_key(seed)
    k_fit = seed_key(seed, impl="rbg")
    sur = cfg["surrogate"]
    consts = suite.batch_suite(cfg["workloads"], cfg["suite_seed"])
    X, Y = suite.make_traces(k_tr, consts, sur["traces"])
    kind = sur["kind"]
    if kind == "mlp":
        params, moments, errs = fit.fit_mlps(
            k_fit, X, Y, tuple(sur["hidden"]), sur["epochs"], sur["lr"],
            sur["dropout"])
        models, weights = _mlp_models(cfg, params, moments)
        errs = np.asarray(errs)
    elif kind == "gp":
        Xh, Yh = np.asarray(X), np.asarray(Y)
        n = Xh.shape[1]
        rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
        pools, gates = [], []
        for _ in range(Xh.shape[0]):
            perm = rng.permutation(n)
            n_val = max(1, int(n * fit.GATE_FRAC))
            gates.append(perm[:n_val])
            pools.append(perm[n_val:])
        factors = fit.fit_gps(Xh, Yh, pools, noise=sur["noise"])
        models, weights = _gp_models(factors)
        errs = np.array([_gp_gate_error(f, Xh[w][gates[w]], Yh[w][gates[w]])
                         for w, f in enumerate(factors)])
    else:
        raise ValueError(f"unknown surrogate kind {kind!r}")

    alpha = float(cfg.get("alpha", 0.0))
    knobs = tuple(spark_space())
    tenants, by_program = [], {}
    for w, ms in enumerate(models):
        snap = ModelSnapshot(version=1, models=ms, val_error=float(errs[w]),
                             n_traces=int(sur["traces"]), backend=kind,
                             warm_started_from=None)
        name = f"batch-{w}"
        objectives = tuple(Objective(o, alpha=alpha)
                           for o in cfg["objectives"])
        spec = TaskSpec(
            knobs=knobs, objectives=objectives, model=snap.psi(),
            model_stds=snap.psi_std(), preference=UtopiaNearest(),
            model_id=("modelserver", f"tpcx-bb/{name}", snap.version),
            name=name, program=snap.program())
        tenants.append(Tenant(w, name, spec, spec.program, weights[w]))
        by_program[id(spec.program)] = w

    s = cfg["service"]
    # the descent path the configuration states ("fused": the Pallas
    # kernel, with no silent fallback to the scan path on any seed)
    executor = ProbeExecutor(mesh=None,
                             backend=s.get("descent_backend", "auto"))
    service = MOOService(
        mogd=MOGDConfig(steps=s["mogd_steps"], multistart=s["multistart"]),
        grid_l=s["grid_l"], batch_rects=s["batch_rects"],
        max_sessions=s["max_sessions"], executor=executor, mesh=None,
        kernel_interpret=interpret)
    desk = FrontDesk(service, capacity=s["frontdesk_capacity"])
    return Deployment(cfg, tenants, service, desk, executor, kind, alpha,
                      float(errs.mean()), by_program)


def _gp_gate_error(per_obj, Xv, Yv) -> float:
    errs = []
    for j, f in enumerate(per_obj):
        z = (np.asarray(Xv, np.float64) - f["x_mean"]) / f["x_std"]
        d2 = ((z[:, None, :] - f["x_train"][None]) ** 2).sum(-1)
        kx = f["variance"] * np.exp(-0.5 * d2 / f["lengthscale"] ** 2)
        pred = kx @ f["alpha"] * f["y_std"] + f["y_mean"]
        y = np.asarray(Yv[:, j], np.float64)
        errs.append(np.abs(pred - y) / np.maximum(np.abs(y), 1e-9))
    return float(np.mean(np.concatenate(errs)))
