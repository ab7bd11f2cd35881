"""Fused MOGD descend kernel (kernels/mogd_descend) + executor backend seam.

Contracts under test:
* the Pallas kernel and the XLA tier are row-exact (fp32) against the
  ``kernels.ref.mogd_descend`` autodiff oracle — the hand-written backward
  is checked against ``jax.grad``, never against itself;
* ``jax.grad`` through ``mlp_forward_fused``'s custom VJP matches autodiff
  through ``ref.mlp_forward`` at padded/off-bucket batch sizes;
* the executor's ``backend="auto"`` routes stacked-MLP structures through
  the fused path (telemetry proves it) with end states equivalent to the
  ``backend="jnp"`` scan path, and the parity gate falls back safely;
* the mesh partitioning policy picks the axis from the tenant mix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mogd import MOGDConfig, MOGDSolver, solve_grouped
from repro.core.synthetic import make_zdt1, mlp_surrogate_task
from repro.distributed.sharding import choose_probe_partition
from repro.exec import ProbeExecutor
from repro.kernels import ref
from repro.kernels.mogd_descend import (
    DescendPlan,
    descend_batch,
    plan_from_structure,
)
from repro.kernels.mogd_mlp import mlp_forward_fused

CFG = MOGDConfig(steps=25, multistart=2)


def _mk_group_params(key, dims, G, k):
    """Stacked standardizing-MLP params with a leading group axis."""
    params = []
    for _ in range(k):
        layers = []
        for i in range(len(dims) - 1):
            key, kw, kb = jax.random.split(key, 3)
            layers.append({
                "w": jax.random.normal(kw, (G, dims[i], dims[i + 1])) * 0.4,
                "b": jax.random.normal(kb, (G, dims[i + 1])) * 0.1,
            })
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        params.append({
            "layers": layers,
            "x_mean": jax.random.normal(k1, (G, dims[0])) * 0.2,
            "x_std": jnp.exp(jax.random.normal(k2, (G, dims[0])) * 0.2),
            "y_mean": jax.random.normal(k3, (G,)) * 0.1,
            "y_std": jnp.exp(jax.random.normal(k4, (G,)) * 0.2),
        })
    return tuple(params), key


def _mk_batch(key, G, R, S, D, k):
    key, *ks = jax.random.split(key, 6)
    x0s = jax.random.uniform(ks[0], (G, R, S, D))
    los = jax.random.normal(ks[1], (G, R, k)) * 0.5 - 1.0
    his = los + jnp.exp(jax.random.normal(ks[2], (G, R, k))) * 2.0
    ulos, uhis = los - 0.5, his + 2.0
    uscales = jnp.ones((G, R, k))
    targets = jax.random.randint(ks[3], (G, R), 0, k)
    return (x0s, los, his, ulos, uhis, uscales, targets), key


def _oracle(plan, cfg, params, x0s, los, his, ulos, uhis, uscales, targets):
    """Per-group ref.mogd_descend over the grouped batch layout."""
    G, R, S, D = x0s.shape
    k = plan.k
    outs = []
    for g in range(G):
        mlps = tuple(
            (tuple(l["w"][g] for l in params[j]["layers"]),
             tuple(l["b"][g] for l in params[j]["layers"]),
             params[j]["x_mean"][g], params[j]["x_std"][g],
             params[j]["y_mean"][g], params[j]["y_std"][g])
            for j in range(k))
        rep = lambda a: jnp.broadcast_to(
            a[:, None, :], (R, S, k)).reshape(R * S, k)
        t = jnp.broadcast_to(targets[g][:, None], (R, S)).reshape(-1)
        outs.append(ref.mogd_descend(
            x0s[g].reshape(R * S, D), mlps, rep(los[g]), rep(his[g]),
            rep(ulos[g]), rep(uhis[g]), rep(uscales[g]), t,
            plan.signs, plan.log_targets, steps=cfg.steps, lr=cfg.lr,
            lr_floor=cfg.lr_floor, b1=cfg.adam_b1, b2=cfg.adam_b2,
            adam_eps=cfg.adam_eps, penalty=cfg.penalty,
            tie_eps=cfg.tie_break_eps).reshape(R, S, D))
    return jnp.stack(outs)


class TestPlanFromStructure:
    def test_mlp_stack(self):
        task = mlp_surrogate_task(seed=0, d=3, arch=(8, 8), k=2)
        problem = task.compile()
        plan = plan_from_structure(problem.program.structure)
        assert plan is not None
        assert plan.k == 2 and plan.dim == 3
        assert plan.layer_dims[0] == (3, 8, 8, 1)
        assert plan.signs == (1.0, 1.0)

    def test_orient_wrapper_carries_signs(self):
        inner = ("stack", (("mlp", (3, 8, 1), False, 0.0, 16),) * 2)
        plan = plan_from_structure(("orient", (1.0, -1.0), inner))
        assert plan is not None and plan.signs == (1.0, -1.0)

    def test_rejects_non_fusable(self):
        assert plan_from_structure(("closure", ("sig", "x"))) is None
        assert plan_from_structure(("stack", (("gp", 64, False),))) is None
        assert plan_from_structure(("family", "fp", 2)) is None
        fus = ("stack", (("mlp", (3, 8, 1), False, 0.0, 16),))
        assert plan_from_structure(fus) is not None
        assert plan_from_structure(fus, use_std=True) is None


class TestKernelParity:
    """Both fused tiers vs the autodiff oracle — row-exact at fp32."""

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_matches_autodiff_oracle(self, impl):
        plan = DescendPlan(((5, 16, 16, 1),) * 2, (False, True), (1.0, -1.0))
        key = jax.random.PRNGKey(0)
        params, key = _mk_group_params(key, (5, 16, 16, 1), G=2, k=2)
        batch, key = _mk_batch(key, G=2, R=3, S=2, D=5, k=2)
        got = descend_batch(plan, CFG, params, *batch, impl=impl,
                            interpret=True)
        want = _oracle(plan, CFG, params, *batch)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)

    def test_pallas_off_bucket_rows(self):
        # M = R*S = 5 rows: forces in-kernel padding to the block size;
        # padded rows must not perturb real rows
        plan = DescendPlan(((4, 8, 1),), (False,), (1.0,))
        key = jax.random.PRNGKey(1)
        params, key = _mk_group_params(key, (4, 8, 1), G=1, k=1)
        batch, key = _mk_batch(key, G=1, R=5, S=1, D=4, k=1)
        got = descend_batch(plan, CFG, params, *batch, impl="pallas",
                            interpret=True)
        want = descend_batch(plan, CFG, params, *batch, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)


class TestFusedMLPVJP:
    """Satellite: jax.grad through the fused forward's custom VJP."""

    @pytest.mark.parametrize("B", [5, 256, 300])
    def test_grad_matches_ref(self, B):
        ks = jax.random.split(jax.random.PRNGKey(2), 7)
        dims = [6, 32, 32, 1]
        ws = tuple(jax.random.normal(ks[i], (dims[i], dims[i + 1])) * 0.3
                   for i in range(3))
        bs = tuple(jax.random.normal(ks[i + 3], (dims[i + 1],)) * 0.1
                   for i in range(3))
        x = jax.random.uniform(ks[6], (B, 6))

        def fused(x, ws, bs):
            return (mlp_forward_fused(x, ws, bs, interpret=True) ** 2).sum()

        def plain(x, ws, bs):
            return (ref.mlp_forward(x, ws, bs) ** 2).sum()

        gx, gw, gb = jax.grad(fused, argnums=(0, 1, 2))(x, ws, bs)
        wx, ww, wb = jax.grad(plain, argnums=(0, 1, 2))(x, ws, bs)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(wx),
                                   atol=1e-4, rtol=1e-4)
        for g, w in zip(gw + gb, ww + wb):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, rtol=1e-4)


class TestExecutorBackendSeam:
    def _boxes(self, problem, n, seed=0):
        from repro.core.mogd import estimate_objective_bounds

        b = estimate_objective_bounds(problem, n=128, seed=seed)
        rng = np.random.default_rng(seed)
        lo = b[0] + rng.random((n, 2)) * 0.3 * (b[1] - b[0])
        return np.stack([lo, lo + 0.5 * (b[1] - b[0])], axis=1)

    def test_auto_routes_mlp_and_matches_jnp(self):
        task = mlp_surrogate_task(seed=3, d=3, arch=(8, 8), k=2)
        boxes = self._boxes(task.compile(), 6)
        cfg = MOGDConfig(steps=30, multistart=4)
        rs = {}
        for backend in ("auto", "jnp", "fused"):
            ex = ProbeExecutor(mesh=None, backend=backend)
            solver = MOGDSolver(task.compile(), cfg, executor=ex)
            rs[backend] = (solver.solve(boxes), ex.stats())
        auto, jnp_, fused = rs["auto"], rs["jnp"], rs["fused"]
        assert auto[1]["fused_structures"] == 1
        assert auto[1]["fused_dispatches"] >= 1
        assert auto[1]["fused_fallbacks"] == 0
        assert jnp_[1]["fused_dispatches"] == 0
        for other in (jnp_, fused):
            np.testing.assert_allclose(auto[0].x, other[0].x, atol=2e-4)
            np.testing.assert_allclose(auto[0].f, other[0].f, atol=2e-3,
                                       rtol=1e-4)
            np.testing.assert_array_equal(auto[0].feasible,
                                          other[0].feasible)

    def test_closure_program_stays_on_scan(self, zdt1):
        ex = ProbeExecutor(mesh=None, backend="auto")
        MOGDSolver(zdt1, CFG, executor=ex).solve(self._boxes(zdt1, 3))
        s = ex.stats()
        assert s["fused_structures"] == 0 and s["fused_dispatches"] == 0

    def test_fused_backend_rejects_closures(self, zdt1):
        ex = ProbeExecutor(mesh=None, backend="fused")
        solver = MOGDSolver(zdt1, CFG, executor=ex)
        with pytest.raises(ValueError, match="fused"):
            solver.solve(self._boxes(zdt1, 3))

    def test_parity_gate_falls_back(self, monkeypatch):
        # a structure whose fused result diverges must fall back to scan
        monkeypatch.setattr(ProbeExecutor, "_parity_check",
                            lambda self, req, plan: False)
        task = mlp_surrogate_task(seed=4, d=3, arch=(8, 8), k=2)
        ex = ProbeExecutor(mesh=None, backend="auto")
        r = MOGDSolver(task.compile(), MOGDConfig(steps=20, multistart=2),
                       executor=ex).solve(self._boxes(task.compile(), 3))
        s = ex.stats()
        assert s["fused_fallbacks"] == 1 and s["fused_dispatches"] == 0
        assert r.x.shape[0] == 3  # still solved, on the scan path

    def test_parity_gate_raises_kernel_errors(self, monkeypatch):
        # a fused kernel that fails to lower or compile must surface, not
        # quietly send its structure to the scan path
        import repro.kernels.mogd_descend as md

        def broken(*args, **kwargs):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(md, "descend_batch", broken)
        task = mlp_surrogate_task(seed=4, d=3, arch=(8, 8), k=2)
        ex = ProbeExecutor(mesh=None, backend="auto")
        solver = MOGDSolver(task.compile(), MOGDConfig(steps=20, multistart=2),
                            executor=ex)
        with pytest.raises(RuntimeError, match="Mosaic"):
            solver.solve(self._boxes(task.compile(), 3))
        assert ex.stats()["fused_fallbacks"] == 0

    def test_parity_gate_mismatch_falls_back_once(self, monkeypatch):
        # the real gate, fed a kernel whose end states are off by 0.5:
        # the structure falls back to scan and is counted exactly once
        import repro.kernels.mogd_descend as md

        real = md.descend_batch
        monkeypatch.setattr(md, "descend_batch",
                            lambda *a, **kw: real(*a, **kw) + 0.5)
        task = mlp_surrogate_task(seed=4, d=3, arch=(8, 8), k=2)
        ex = ProbeExecutor(mesh=None, backend="auto")
        solver = MOGDSolver(task.compile(), MOGDConfig(steps=20, multistart=2),
                            executor=ex)
        boxes = self._boxes(task.compile(), 3)
        solver.solve(boxes)
        r = solver.solve(boxes)
        s = ex.stats()
        assert s["fused_fallbacks"] == 1 and s["fused_dispatches"] == 0
        assert s["fused_structures"] == 0
        assert r.x.shape[0] == 3  # still solved, on the scan path

    def test_grouped_tenants_share_fused_program(self):
        # two same-architecture tenants: one structure, one fused dispatch
        cfg = MOGDConfig(steps=20, multistart=2)
        ex = ProbeExecutor(mesh=None, backend="auto")
        items = []
        for seed in (5, 6):
            p = mlp_surrogate_task(seed=seed, d=3, arch=(8, 8), k=2).compile()
            items.append((MOGDSolver(p, cfg, executor=ex),
                          self._boxes(p, 3, seed), 0))
        res = solve_grouped(items)
        s = ex.stats()
        assert res.x.shape == (6, 3)
        assert s["fused_structures"] == 1
        assert s["fused_dispatches"] == 1


class TestPartitionPolicy:
    def test_single_device_no_axis(self):
        assert choose_probe_partition(1, 8, 32) == (None, 8, 32)

    def test_many_tenants_shard_groups(self):
        # G divisible: zero-pad group shard beats padding rows
        assert choose_probe_partition(4, 8, 2) == ("group", 8, 2)

    def test_single_tenant_shards_rows(self):
        # G=1: padding groups 1->n wastes (n-1)x the batch; rows win
        axis, gp, rp = choose_probe_partition(8, 1, 64)
        assert (axis, gp, rp) == ("row", 1, 64)
        axis, gp, rp = choose_probe_partition(4, 1, 5)
        assert (axis, gp, rp) == ("row", 1, 8)

    def test_tie_prefers_group_axis(self):
        # both axes already divisible -> group keeps params device-local
        assert choose_probe_partition(2, 4, 4)[0] == "group"

    def test_idempotent_on_own_output(self):
        for n, g, r in [(4, 6, 10), (8, 1, 3), (2, 5, 5), (8, 16, 64)]:
            axis, gp, rp = choose_probe_partition(n, g, r)
            assert choose_probe_partition(n, gp, rp) == (axis, gp, rp)

    def test_single_device_executor_defaults_unsharded(self):
        # mesh="auto" on one device: no mesh, no sharded dispatches
        ex = ProbeExecutor()
        assert ex.mesh is None
        task = make_zdt1(d=3)
        MOGDSolver(task, CFG, executor=ex).solve(
            TestExecutorBackendSeam()._boxes(task, 2))
        assert ex.stats()["sharded_dispatches"] == 0
