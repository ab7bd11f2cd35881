"""The unified probe-executor plane (DESIGN.md §10).

Covers the dispatch-plane contract end to end:

* the ``(structure_key, params)`` splits of the MLP/GP regressors agree
  exactly with the regressors themselves (padded GP factors included);
* bucketed padding is invariant — a batch padded to its bucket solves
  row-for-row identically to the unpadded reference, and pad rows never
  leak into absorbed frontiers;
* solvers over *different* problems sharing one model architecture share
  one compiled structure, and a grouped dispatch over their spans equals
  the per-solver dispatches (bounds / targets / params all ride as data);
* a model-server promotion is a pure params swap: the warm re-solve
  reuses the warm executor with ZERO new compilations (the compile-count
  telemetry asserted here gates CI via the service benchmark);
* the opt-in mesh path is a no-op on one device and bit-compatible on an
  8-device host mesh (subprocess, like tests/test_distributed.py);
* a dispatch assembled in host numpy hands the program the same inputs,
  bit for bit, as one assembled on the device, and a promoted snapshot
  makes its own host copy of its parameters.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MOGDConfig, Objective, continuous
from repro.core.mogd import MOGDSolver, solve_grouped
from repro.core.task import TaskSpec, as_problem
from repro.exec import (
    ProbeExecutor,
    bucket,
    pad_rows,
)
from repro.models.gp import fit_gp
from repro.models.mlp import MLPRegressor, MLPSpec, init_mlp

FAST = MOGDConfig(steps=40, multistart=4)


def mlp_workload(i: int, d: int = 3, arch=(8, 8), k: int = 2,
                 bound=None, name: str | None = None) -> TaskSpec:
    """One synthetic MLP-backed workload; workloads differ in weights
    only, so every ``mlp_workload(i)`` shares one program structure.
    (The builder itself lives in ``repro.core.synthetic`` — shared with
    the service tests and the CI-gated heterogeneous benchmark.)"""
    from repro.core.synthetic import mlp_surrogate_task

    return mlp_surrogate_task(seed=i, d=d, arch=tuple(arch), k=k,
                              bound=bound, name=name)


def boxes_for(problem, n: int, seed: int = 0) -> np.ndarray:
    """n random (lo, hi) probe boxes inside the sampled objective range."""
    from repro.core.mogd import estimate_objective_bounds

    b = estimate_objective_bounds(problem, n=512, seed=seed)
    rng = np.random.default_rng(seed)
    lo = b[0] + rng.random((n, problem.k)) * 0.3 * (b[1] - b[0])
    hi = lo + (0.2 + 0.5 * rng.random((n, problem.k))) * (b[1] - b[0])
    return np.stack([lo, hi], axis=1)


# ---------------------------------------------------------------------------
# Program splits agree with the regressors
# ---------------------------------------------------------------------------


class TestProgramSplits:
    @pytest.mark.parametrize("log_target", [False, True])
    def test_mlp_program_matches_regressor(self, log_target):
        d = 4
        spec = MLPSpec(d, (16, 16), 1)
        reg = MLPRegressor(
            spec=spec, params=init_mlp(jax.random.PRNGKey(3), spec),
            x_mean=jnp.full(d, 0.2), x_std=jnp.full(d, 0.7),
            y_mean=jnp.float32(1.5), y_std=jnp.float32(0.4),
            dropout=0.1, log_target=log_target)
        prog = reg.as_program()
        X = jax.random.uniform(jax.random.PRNGKey(4), (7, d))
        want = np.asarray([reg(x) for x in X])
        got = np.asarray([prog.apply(prog.params, x) for x in X])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
        want_std = np.asarray([reg.predict_std(x) for x in X])
        got_std = np.asarray([prog.apply_std(prog.params, x) for x in X])
        np.testing.assert_allclose(got_std, want_std, rtol=1e-5, atol=1e-7)

    def test_mlp_structure_key_is_weight_free(self):
        a = mlp_workload(0).program
        b = mlp_workload(1).program
        c = mlp_workload(2, arch=(16, 8)).program
        assert a.structure == b.structure  # same arch, different weights
        assert a.structure != c.structure  # different arch

    @pytest.mark.parametrize("log_target", [False, True])
    def test_gp_program_matches_regressor_with_padding(self, log_target):
        rng = np.random.default_rng(0)
        X = rng.random((11, 3))
        y = np.exp(rng.normal(size=11)) if log_target else rng.normal(size=11)
        reg = fit_gp(X, y, log_target=log_target)
        prog = reg.as_program()
        # 11 train points pad to the 16-bucket: padding must be exact
        assert prog.structure == ("gp", 16, log_target)
        Q = rng.random((9, 3))
        want = np.asarray([reg(jnp.asarray(q)) for q in Q])
        got = np.asarray([prog.apply(prog.params, jnp.asarray(q))
                          for q in Q])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)
        want_std = np.asarray([reg.predict_std(jnp.asarray(q)) for q in Q])
        got_std = np.asarray([prog.apply_std(prog.params, jnp.asarray(q))
                              for q in Q])
        np.testing.assert_allclose(got_std, want_std, rtol=1e-4, atol=1e-7)

    def test_explicit_model_beside_program_changes_signature(self):
        """compile() builds problem.objectives from self.model, so an
        explicit model diverging from the program must not collide with
        the program-only spec's signature (signature-keyed caches would
        serve one tenant's compiled problem to a content-different spec)."""
        import jax.numpy as jnp

        base = mlp_workload(0)
        divergent = TaskSpec(
            knobs=base.knobs, objectives=base.objectives,
            model=lambda x: jnp.stack([5.0 * x[0], 5.0 * x[1]]),
            program=base.program, name=base.name)
        assert divergent.signature() != base.signature()
        # re-submitting equal content still hashes equal
        assert mlp_workload(0).signature() == base.signature()

    def test_gp_retrain_within_bucket_is_params_swap(self):
        rng = np.random.default_rng(1)
        r1 = fit_gp(rng.random((10, 3)), rng.normal(size=10))
        r2 = fit_gp(rng.random((14, 3)), rng.normal(size=14))
        assert r1.as_program().structure == r2.as_program().structure

    def test_eval_batch_routes_through_program(self):
        spec = mlp_workload(5)
        problem = spec.compile()
        assert getattr(problem, "program", None) is not None
        X = jax.random.uniform(jax.random.PRNGKey(9), (13, problem.dim))
        want = np.asarray([spec.model(x) for x in X])
        got = np.asarray(problem.evaluate_batch(X))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_eval_batch_empty_input(self):
        problem = mlp_workload(5).compile()
        out = np.asarray(
            problem.evaluate_batch(np.empty((0, problem.dim))))
        assert out.shape == (0, problem.k)


# ---------------------------------------------------------------------------
# Bucketing policy + padding invariance (satellite: single source of truth)
# ---------------------------------------------------------------------------


class TestBucketing:
    def test_bucket_policy(self):
        assert [bucket(b) for b in (1, 4, 5, 8, 9, 33)] == [
            1, 4, 8, 8, 16, 64]
        assert bucket(3, base=4) == 4

    def test_pad_rows_replicates_row_zero(self):
        t = {"a": np.arange(6.0).reshape(3, 2), "b": np.ones((3,))}
        p = pad_rows(t, 2)
        assert p["a"].shape == (5, 2) and p["b"].shape == (5,)
        np.testing.assert_array_equal(p["a"][3:], np.asarray(t["a"])[:1]
                                      .repeat(2, axis=0))

    def test_padded_solve_matches_unpadded_reference(self, zdt1):
        """B=5 pads to the 8-bucket; every returned row must equal the
        unpadded (identity-bucket) reference solve — pad rows are sliced
        off before any caller (or frontier) can see them."""
        ref = MOGDSolver(zdt1, FAST,
                         executor=ProbeExecutor(bucket_fn=lambda b: b))
        pad = MOGDSolver(zdt1, FAST, executor=ProbeExecutor())
        boxes = boxes_for(zdt1, 5)
        r_ref = ref.solve(boxes)
        r_pad = pad.solve(boxes)
        assert r_pad.x.shape == (5, zdt1.dim)
        np.testing.assert_array_equal(r_pad.feasible, r_ref.feasible)
        np.testing.assert_allclose(r_pad.x, r_ref.x, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(r_pad.f, r_ref.f, rtol=1e-5, atol=1e-6)

    def test_padded_refine_matches_unpadded_reference(self, zdt1):
        ref = MOGDSolver(zdt1, FAST,
                         executor=ProbeExecutor(bucket_fn=lambda b: b))
        pad = MOGDSolver(zdt1, FAST, executor=ProbeExecutor())
        x0s = np.asarray(jax.random.uniform(jax.random.PRNGKey(7),
                                            (5, zdt1.dim)))
        box = boxes_for(zdt1, 1)[0]
        xr, fr, sr = ref.refine(x0s, box)
        xp, fp, sp = pad.refine(x0s, box)
        np.testing.assert_array_equal(sp, sr)
        np.testing.assert_allclose(xp, xr, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(fp, fr, rtol=1e-5, atol=1e-6)

    def test_pad_rows_never_leak_into_frontier(self):
        """An off-bucket PF probe batch (B=5 cells with batch_rects=5 at
        most) must only ever absorb points that are solutions of REAL
        cells: every frontier X row re-evaluates to its stored F."""
        from repro.core import ProgressiveFrontier

        spec = mlp_workload(3)
        problem = as_problem(spec)
        pf = ProgressiveFrontier(problem, mode="AP", mogd=FAST, grid_l=2,
                                 batch_rects=3)
        res = pf.run(n_probes=20)
        F_re = np.asarray(problem.evaluate_batch(jnp.asarray(res.X)))
        np.testing.assert_allclose(F_re, res.F, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Structure sharing + everything-as-data grouped dispatch
# ---------------------------------------------------------------------------


class TestStructureSharing:
    def test_same_arch_workloads_share_dispatch_key(self):
        ex = ProbeExecutor()
        s0 = MOGDSolver(as_problem(mlp_workload(0)), FAST, executor=ex)
        s1 = MOGDSolver(as_problem(mlp_workload(1)), FAST, executor=ex)
        s2 = MOGDSolver(as_problem(mlp_workload(2, arch=(16, 8))), FAST,
                        executor=ex)
        assert s0.dispatch_key() == s1.dispatch_key()
        assert s0.dispatch_key() != s2.dispatch_key()

    def test_seed_is_not_part_of_the_structure(self):
        """cfg.seed only feeds each solver's host-side PRNG stream —
        per-tenant seeds must not defeat coalescing (or compile twice)."""
        import dataclasses

        ex = ProbeExecutor()
        p = as_problem(mlp_workload(0))
        s0 = MOGDSolver(p, FAST, executor=ex)
        s1 = MOGDSolver(p, dataclasses.replace(FAST, seed=17), executor=ex)
        assert s0.dispatch_key() == s1.dispatch_key()
        # but a trace-relevant config change still splits the structure
        s2 = MOGDSolver(p, dataclasses.replace(FAST, steps=50), executor=ex)
        assert s0.dispatch_key() != s2.dispatch_key()

    def test_program_cache_is_bounded(self, zdt1, sphere2):
        """A stream of distinct closure structures must not pin compiled
        programs forever (the executor-level analog of the service's
        _evict_cold_tasks)."""
        ex = ProbeExecutor(max_programs=1)
        for problem in (zdt1, sphere2, zdt1):
            MOGDSolver(problem, FAST, executor=ex).solve(
                boxes_for(problem, 2))
        assert len(ex._programs) == 1
        assert ex.structures_compiled == 2  # telemetry keeps counting
        assert ex.total_compiles == 3  # zdt1 evicted, recompiled on reuse

    def test_second_workload_adds_no_structure(self):
        ex = ProbeExecutor()
        p0, p1 = as_problem(mlp_workload(0)), as_problem(mlp_workload(1))
        s0 = MOGDSolver(p0, FAST, executor=ex)
        s1 = MOGDSolver(p1, FAST, executor=ex)
        s0.solve(boxes_for(p0, 4))
        n_structs, n_builds = ex.structures_compiled, ex.total_compiles
        assert n_structs == 1
        s1.solve(boxes_for(p1, 4, seed=1))  # params swap, warm program
        assert ex.structures_compiled == n_structs
        assert ex.total_compiles == n_builds

    def test_grouped_dispatch_equals_individual_solves(self):
        """One coalesced dispatch over two different workloads (one of
        them bound-capped, different targets) == the two per-solver
        dispatches: params, user bounds, and target indices all ride as
        per-box data."""
        spec_a = mlp_workload(0)
        spec_b = mlp_workload(1, bound=(None, 0.5))
        pa, pb = as_problem(spec_a), as_problem(spec_b)
        boxes_a, boxes_b = boxes_for(pa, 3), boxes_for(pb, 5, seed=2)

        def fresh(problem):
            return MOGDSolver(problem, FAST, executor=ProbeExecutor())

        ra = fresh(pa).solve(boxes_a, target=0)
        rb = fresh(pb).solve(boxes_b, target=1)
        ga, gb = fresh(pa), fresh(pb)
        assert ga.dispatch_key() == gb.dispatch_key()  # bounds are data
        shared = ProbeExecutor()
        ga.executor = gb.executor = shared
        res = solve_grouped([(ga, boxes_a, 0), (gb, boxes_b, 1)])
        assert shared.dispatches == 1
        np.testing.assert_allclose(res.x[:3], ra.x, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(res.f[:3], ra.f, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(res.feasible[:3], ra.feasible)
        np.testing.assert_allclose(res.x[3:], rb.x, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(res.f[3:], rb.f, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(res.feasible[3:], rb.feasible)

    def test_mixed_structure_group_rejected(self):
        ex = ProbeExecutor()
        sa = MOGDSolver(as_problem(mlp_workload(0)), FAST, executor=ex)
        sb = MOGDSolver(as_problem(mlp_workload(1, arch=(16, 8))), FAST,
                        executor=ex)
        with pytest.raises(ValueError, match="structure"):
            solve_grouped([(sa, boxes_for(sa.problem, 2), 0),
                           (sb, boxes_for(sb.problem, 2), 0)])

    def test_bound_enforced_through_data_path(self):
        """A declared cap riding as data must still gate feasibility."""
        spec = mlp_workload(4, bound=(None, -1e6))  # unsatisfiable cap
        problem = as_problem(spec)
        res = MOGDSolver(problem, FAST,
                         executor=ProbeExecutor()).solve(
            boxes_for(problem, 4))
        assert not res.feasible.any()


# ---------------------------------------------------------------------------
# Host assembly: a dispatch is built in numpy and crosses once
# ---------------------------------------------------------------------------


def _device_assembled(ex: ProbeExecutor, requests):
    """Call the program ``ex`` last dispatched on inputs assembled on the
    device, as the executor once did: every row field and parameter leaf
    put on the device, the group axis added and open bounds filled there,
    the batch concatenated and padded with device ops; results sliced on
    the device per request and read back."""
    (fn,) = [f for key, f in ex._programs.items()
             if key[-2:] == ex.last_bucket]
    Gp, Rp = ex.last_bucket
    parts = []
    for req in requests:
        x0s = jnp.asarray(req.x0s)
        B = x0s.shape[0]
        los, his = jnp.asarray(req.los), jnp.asarray(req.his)
        k = los.shape[-1]
        if req.bounds is not None:
            ulo, uhi, uscale = (jnp.asarray(b) for b in req.bounds)
        else:
            ulo, uhi = jnp.full((B, k), -jnp.inf), jnp.full((B, k), jnp.inf)
            uscale = jnp.ones((B, k))
        alphas = (jnp.zeros((B, k)) if req.alphas is None
                  else jnp.asarray(req.alphas))
        targets = jnp.asarray(req.targets, jnp.int32).reshape(B)
        rows = (x0s, los, his, ulo, uhi, uscale, alphas, targets)
        if req.params_b is None:
            params = jax.tree.map(lambda a: jnp.asarray(a)[None],
                                  req.program.params)
            parts.append((params, tuple(r[None] for r in rows), 1, B))
        else:
            params = jax.tree.map(jnp.asarray, req.params_b)
            parts.append((params, tuple(r[:, None] for r in rows), B, 1))

    def pad(a, n, axis):
        if n == 0:
            return a
        edge = jnp.take(a, jnp.zeros((n,), jnp.int32), axis=axis)
        return jnp.concatenate([a, edge], axis=axis)

    G = sum(p[2] for p in parts)
    params = jax.tree.map(lambda *ls: pad(jnp.concatenate(ls), Gp - G, 0),
                          *[p[0] for p in parts])
    rows = [pad(jnp.concatenate([pad(p[1][i], Rp - p[3], 1)
                                 for p in parts]), Gp - G, 0)
            for i in range(len(parts[0][1]))]
    x, f, feas = fn(params, *rows)
    outs, g0 = [], 0
    for _, _, n_groups, n_rows in parts:
        outs.append([np.asarray(a[g0:g0 + n_groups, :n_rows]).reshape(
            n_groups * n_rows, *a.shape[2:]) for a in (x, f, feas)])
        g0 += n_groups
    return tuple(np.concatenate(o) for o in zip(*outs))


def _gp_workload(i: int, d: int = 3, alpha: float = 0.0) -> TaskSpec:
    """A two-objective GP-backed workload (scan path); ``alpha`` weights
    the first objective's predictive std."""
    from repro.exec import stack_programs

    rng = np.random.default_rng(100 + i)
    X = rng.random((12, d))
    regs = [fit_gp(X, X @ rng.random(d) + 0.1 * rng.normal(size=12))
            for _ in range(2)]
    return TaskSpec(
        knobs=tuple(continuous(f"x{t}", 0.0, 1.0) for t in range(d)),
        objectives=(Objective("f0", alpha=alpha), Objective("f1")),
        program=stack_programs([r.as_program() for r in regs]),
        name=f"gp-wl-{i}")


def _starts(i: int, shape) -> jax.Array:
    return jax.random.uniform(jax.random.PRNGKey(50 + i), shape)


def _mlp_pair():
    """Two coalesced MLP tenants, the second with a bound on its first
    objective; the first open.  Device-drawn starts, as solve_grouped
    draws them."""
    ex = ProbeExecutor(mesh=None)
    reqs = []
    for i, (spec, B, target) in enumerate(
            [(mlp_workload(0), 3, 0),
             (mlp_workload(1, bound=(None, 0.5)), 5, 1)]):
        s = MOGDSolver(spec.compile(), FAST, executor=ex)
        boxes = boxes_for(s.problem, B, seed=i)
        reqs.append(s._request(_starts(i, (B, FAST.multistart, 3)),
                               boxes[:, 0], boxes[:, 1], target))
    return ex, reqs, ex.solve_requests(reqs)


def _gp_pair():
    """Two coalesced GP tenants on the scan path, weighting their
    predictive std (the alphas row field)."""
    ex = ProbeExecutor(mesh=None)
    reqs = []
    for i in range(2):
        s = MOGDSolver(_gp_workload(i, alpha=0.5).compile(), FAST,
                       executor=ex)
        boxes = boxes_for(s.problem, 4, seed=i)
        reqs.append(s._request(_starts(i, (4, FAST.multistart, 3)),
                               boxes[:, 0], boxes[:, 1], 0))
    out = ex.solve_requests(reqs)
    assert ex.fused_dispatches == 0
    assert all(r.alphas is not None for r in reqs)
    return ex, reqs, out


def _family():
    """Stage-family thetas: per-row parameters, B groups of one row."""
    from repro.core.dag import FamilySolver, make_analytics_family
    from repro.exec import ProbeRequest

    ex = ProbeExecutor(mesh=None)
    fam = FamilySolver(make_analytics_family(), FAST, executor=ex)
    rng = np.random.default_rng(3)
    B = 5
    thetas = np.stack([rng.uniform(1, 4, B), rng.uniform(0, 1, B),
                       rng.uniform(0, 1, B), rng.uniform(0.5, 2, B)], 1)
    lo = np.stack([rng.uniform(0.5, 2, B), rng.uniform(0.5, 4, B)], 1)
    req = ProbeRequest(
        program=fam._program, encoder=fam.family.encoder, cfg=FAST,
        x0s=_starts(0, (B, FAST.multistart, fam.family.encoder.dim)),
        los=lo, his=lo + 3.0, targets=np.zeros((B,), np.int32),
        params_b=thetas)
    return ex, [req], ex.solve_requests([req])


def _refine():
    """``MOGDSolver.refine``: host float64 starts, one start per row."""
    ex = ProbeExecutor(mesh=None)
    s = MOGDSolver(mlp_workload(2).compile(), FAST, executor=ex)
    x0s = np.random.default_rng(4).random((5, 3))
    box = boxes_for(s.problem, 1)[0]
    out = s.refine(x0s, box)
    lo = np.broadcast_to(box[0], (5, 2))
    hi = np.broadcast_to(box[1], (5, 2))
    return ex, [s._request(x0s[:, None, :], lo, hi, 0)], out


class TestHostAssembly:
    @pytest.mark.parametrize("case", [_mlp_pair, _gp_pair, _family,
                                      _refine],
                             ids=["mlp_pair", "gp_scan", "family",
                                  "refine"])
    def test_matches_device_assembly_bit_for_bit(self, case):
        """The program sees the same inputs whether the dispatch was
        assembled in numpy or on the device."""
        ex, reqs, got = case()
        want = _device_assembled(ex, reqs)
        assert len(got) == len(want) == 3
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(g, w)

    def test_promoted_snapshot_makes_its_own_host_copy(self):
        """A promotion builds a new program: its first dispatch reads the
        new weights back instead of reusing the predecessor's copy."""
        from repro.modelserver import ModelRegistry, TrainerConfig

        rng = np.random.default_rng(0)
        knobs = (continuous("a", 0.0, 1.0), continuous("b", 0.0, 1.0))

        def truth(X, shift=False):
            y1 = (3.0 if shift else 1.0) * (X[:, 0] - 0.3) ** 2 + X[:, 1]
            return np.stack([y1 + 0.5, 1.5 - X[:, 0] + 0.2 * X[:, 1]], 1)

        reg = ModelRegistry(
            trainer=TrainerConfig(hidden=(16, 16), max_epochs=25, seed=0))
        w = reg.register_workload(("exec", "promo"), knobs,
                                  (Objective("lat"), Objective("cost")))
        X = rng.random((140, 2))
        reg.observe_batch(w, X, truth(X))
        assert reg.retrain(w).improved
        ex = ProbeExecutor(mesh=None)

        def solve_v():
            s = MOGDSolver(reg.task_spec(w).compile(), FAST, executor=ex)
            s.solve(boxes_for(s.problem, 3))
            return s.program()

        old = solve_v()
        solve_v()
        assert ex.stats()["host_param_misses"] == 1
        assert ex.stats()["host_param_hits"] == 1
        X = rng.random((160, 2))
        reg.observe_batch(w, X, truth(X, shift=True))
        assert reg.retrain(w).version == 2
        new = solve_v()
        assert new is not old
        st = ex.stats()
        assert st["host_param_misses"] == 2 and st["host_param_hits"] == 1
        host = jax.tree.leaves(new._host_params[1])
        assert new._host_params[0] is new.params
        for h, a in zip(host, jax.tree.leaves(new.params)):
            assert np.array_equal(h[0], np.asarray(a))
        assert not all(
            np.array_equal(a, b) for a, b in zip(
                host, jax.tree.leaves(old._host_params[1])))


# ---------------------------------------------------------------------------
# Model promotion == params swap (zero new compilations)
# ---------------------------------------------------------------------------


class TestPromotionParamsSwap:
    def test_warm_resolve_reuses_compiled_executor(self):
        from repro.modelserver import DriftConfig, ModelRegistry, TrainerConfig
        from repro.service import MOOService

        rng = np.random.default_rng(0)
        knobs = (continuous("a", 0.0, 1.0), continuous("b", 0.0, 1.0))
        objectives = (Objective("lat"), Objective("cost"))

        def truth(X, shift=False):
            X = np.atleast_2d(X)
            y1 = (3.0 if shift else 1.0) * (X[:, 0] - 0.3) ** 2 + X[:, 1]
            y2 = 1.5 - X[:, 0] + 0.2 * X[:, 1] ** 2
            return np.stack([y1 + 0.5, y2], axis=1)

        reg = ModelRegistry(
            trainer=TrainerConfig(hidden=(16, 16), max_epochs=25, seed=0),
            drift=DriftConfig(window=16, min_obs=8, mult=3.0, floor=0.1))
        sigs = [reg.register_workload(("exec", f"w{i}"), knobs, objectives)
                for i in range(2)]
        for i, w in enumerate(sigs):
            X = rng.random((140, 2))
            reg.observe_batch(w, X, truth(X) * (1.0 + 0.5 * i))
            assert reg.retrain(w).improved
        svc = MOOService(mogd=FAST, batch_rects=2)
        for w in sigs:
            svc.create_workload_session(reg, w)
        svc.run_until(min_probes=14)
        st = svc.stats()
        # two workloads, one MLP architecture -> one compiled structure
        assert st["executor_structures"] == 1
        builds = st["executor_compiles"]
        # a promotion on w0: new weights, same architecture
        X = rng.random((160, 2))
        reg.observe_batch(sigs[0], X, truth(X, shift=True))
        rep = reg.retrain(sigs[0])
        assert rep.improved and rep.version == 2
        assert svc.stats()["stale_sessions"] == 1
        svc.run_until(min_probes=14)  # triggers the warm re-solve
        st = svc.stats()
        assert st["warm_resolves"] >= 1 and st["stale_sessions"] == 0
        # the params swap reused every compiled program: 0 new builds
        assert st["executor_compiles"] == builds
        assert st["executor_structures"] == 1


# ---------------------------------------------------------------------------
# Mesh path: no-op fallback + multi-device parity (subprocess)
# ---------------------------------------------------------------------------


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import numpy as np
    import jax
    from repro.core import MOGDConfig
    from repro.core.synthetic import make_zdt1
    from repro.core.mogd import MOGDSolver, estimate_objective_bounds
    from repro.distributed.sharding import probe_mesh
    from repro.exec import ProbeExecutor

    assert len(jax.devices()) == 8
    cfg = MOGDConfig(steps=30, multistart=4)
    problem = make_zdt1(d=4)
    b = estimate_objective_bounds(problem, n=256)
    rng = np.random.default_rng(0)
    lo = b[0] + rng.random((6, 2)) * 0.3 * (b[1] - b[0])
    boxes = np.stack([lo, lo + 0.5 * (b[1] - b[0])], axis=1)
    plain = MOGDSolver(problem, cfg, executor=ProbeExecutor(mesh=None))
    mesh = probe_mesh()
    sharded = MOGDSolver(problem, cfg,
                         executor=ProbeExecutor(mesh=mesh))
    r0, r1 = plain.solve(boxes), sharded.solve(boxes)
    print(json.dumps({
        "mesh_devices": int(mesh.devices.size),
        "max_dx": float(np.abs(r0.x - r1.x).max()),
        "max_df": float(np.abs(r0.f - r1.f).max()),
        "feas_equal": bool((r0.feasible == r1.feasible).all()),
    }))
""")


class TestMeshPath:
    def test_single_device_mesh_is_noop(self, zdt1):
        from repro.distributed.sharding import probe_mesh

        boxes = boxes_for(zdt1, 5)
        plain = MOGDSolver(zdt1, FAST, executor=ProbeExecutor())
        mesh = probe_mesh(n_devices=1)
        shard = MOGDSolver(zdt1, FAST, executor=ProbeExecutor(mesh=mesh))
        r0, r1 = plain.solve(boxes), shard.solve(boxes)
        np.testing.assert_allclose(r1.x, r0.x, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(r1.feasible, r0.feasible)

    @pytest.mark.slow
    def test_eight_device_mesh_parity(self):
        env = {**os.environ,
               "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
               "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path)}
        proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                              capture_output=True, text=True, env=env,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["mesh_devices"] == 8
        assert out["feas_equal"]
        assert out["max_dx"] < 1e-5 and out["max_df"] < 1e-5
