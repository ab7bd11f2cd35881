"""FrontierStore: the incremental mask must equal the batch Pareto filter
on arbitrary point streams (the tentpole's correctness invariant)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import FrontierStore, pareto_mask


def _batch_reference(stream):
    """Seed-finalize semantics: dedupe at 1e-9, then full Pareto filter."""
    allF = np.concatenate([f for f, _ in stream])
    allX = np.concatenate([x for _, x in stream])
    _, uniq = np.unique(np.round(allF, 9), axis=0, return_index=True)
    F, X = allF[np.sort(uniq)], allX[np.sort(uniq)]
    mask = np.asarray(pareto_mask(jnp.asarray(F)))
    return F[mask], X[mask]


def _as_set(F):
    return {tuple(np.round(row, 9)) for row in F}


class TestIncrementalEqualsBatch:
    @pytest.mark.parametrize("k,seed", [(2, 0), (2, 1), (3, 2), (4, 3)])
    def test_random_streams(self, k, seed):
        rng = np.random.default_rng(seed)
        store = FrontierStore(k=k, dim=3, capacity=64)
        stream = []
        for _ in range(40):
            b = int(rng.integers(1, 10))
            F = rng.uniform(0, 1, (b, k))
            X = rng.uniform(0, 1, (b, 3))
            stream.append((F, X))
            store.add(F, X)
        F_ref, X_ref = _batch_reference(stream)
        F_got, X_got = store.frontier()
        assert _as_set(F_got) == _as_set(F_ref)
        # X rows stay aligned with their F rows
        lookup = {tuple(np.round(f, 9)): tuple(x) for f, x in zip(F_ref, X_ref)}
        for f, x in zip(F_got, X_got):
            assert lookup[tuple(np.round(f, 9))] == pytest.approx(tuple(x))

    def test_duplicates_collapse(self):
        store = FrontierStore(k=2, dim=1)
        p = np.array([[0.3, 0.7]])
        for _ in range(5):
            store.add(p, np.array([[0.0]]))
        assert store.n_points == 1
        assert store.total_accepted == 1

    def test_dominating_point_retires_many(self):
        store = FrontierStore(k=2, dim=1)
        F = np.array([[0.1, 0.9], [0.5, 0.5], [0.9, 0.1]])
        store.add(F, np.zeros((3, 1)))
        assert store.n_points == 3
        store.add(np.array([[0.05, 0.05]]), np.zeros((1, 1)))
        F_live, _ = store.frontier()
        assert store.n_points == 1
        np.testing.assert_allclose(F_live, [[0.05, 0.05]])

    def test_grow_on_demand_preserves_frontier(self):
        rng = np.random.default_rng(9)
        store = FrontierStore(k=2, dim=2, capacity=64)
        stream = []
        # anti-correlated objectives -> most points survive -> forces growth
        for _ in range(30):
            a = rng.uniform(0, 1, (8, 1))
            F = np.concatenate([a, 1.0 - a + rng.uniform(0, 1e-3, (8, 1))], 1)
            X = rng.uniform(0, 1, (8, 2))
            stream.append((F, X))
            store.add(F, X)
        assert store.capacity > 64  # grew
        F_ref, _ = _batch_reference(stream)
        F_got, _ = store.frontier()
        assert _as_set(F_got) == _as_set(F_ref)

    def test_kernel_path_matches_jnp_path(self):
        rng = np.random.default_rng(4)
        s1 = FrontierStore(k=3, dim=2)
        s2 = FrontierStore(k=3, dim=2, use_kernel=True)
        for _ in range(10):
            # fp32-exact values (multiples of 2^-10) so both paths see
            # identical inputs despite the kernel path's fp32 cast
            F = rng.integers(0, 1024, (6, 3)) / 1024.0
            X = rng.uniform(0, 1, (6, 2))
            s1.add(F, X)
            s2.add(F, X)
        f1, _ = s1.frontier()
        f2, _ = s2.frontier()
        assert _as_set(f1) == _as_set(f2)

    def test_nonfinite_rows_rejected(self):
        store = FrontierStore(k=2, dim=1)
        store.add(np.array([[np.inf, 0.1], [0.2, 0.2]]), np.zeros((2, 1)))
        assert store.n_points == 1

    def test_key_set_stays_bounded(self):
        """Dedup keys track live rows only — rejected and retired offers
        must not accumulate (long-lived service sessions)."""
        rng = np.random.default_rng(11)
        store = FrontierStore(k=2, dim=1)
        for i in range(50):
            F = rng.uniform(0.2, 1.0, (8, 2))
            store.add(F, np.zeros((8, 1)))
        assert len(store._keys) == store.n_points
        # a dominating point retires everything; keys shrink with it
        store.add(np.array([[0.0, 0.0]]), np.zeros((1, 1)))
        assert store.n_points == 1 and len(store._keys) == 1
        # re-offering a retired point is still rejected (transitivity)
        F_old = rng.uniform(0.2, 1.0, (4, 2))
        store.add(F_old, np.zeros((4, 1)))
        assert store.n_points == 1


# -- where the pass runs: the host twin decides as the device pass does -----

def _pass_inputs(k: int, cap: int, bb: int, seed: int):
    """A padded store and offer batch as ``FrontierStore.add`` hands them
    to either pass: live and dead rows, ``+inf`` padding, exact ties and
    pairs that are distinct in float64 but equal in float32.  Offer 1 is
    dominated by offer 0 in float64 only, and offer 2 retires live row
    ``j2`` in float64 only; values sit in [1, 2.25] so that 1e-12 is below
    float32's resolution there."""
    rng = np.random.default_rng(seed)
    n = cap * 3 // 4
    E = np.full((cap, k), np.inf)
    E[:n] = 1.0 + rng.integers(0, 6, (n, k)) / 4.0  # a coarse grid: ties
    alive = np.zeros(cap, dtype=bool)
    alive[:n] = rng.random(n) < 0.6  # the rest of [0, n) are dead rows
    live = np.flatnonzero(alive)
    j = live[np.lexsort(E[live].T[::-1])[0]]  # least first objective
    j2 = live[np.lexsort(E[live].T)[0]]  # least last objective
    nb = bb - 1  # the last offer row is padding
    B = np.full((bb, k), np.inf)
    B[:nb] = 1.25 + rng.integers(0, 6, (nb, k)) / 4.0
    B[0] = E[j]  # an exact tie with a live Pareto row
    B[1] = E[j] + 1e-12
    B[2] = E[j2]
    B[2, 0] -= 1e-12
    bvalid = np.zeros(bb, dtype=bool)
    bvalid[:nb] = True
    return E, alive, B, bvalid, j2


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("cap", [64, 1024])
@pytest.mark.parametrize("bb", [4, 16])
def test_host_pass_equals_device_pass(k, cap, bb):
    from repro.core.frontier_store import _host_pass, _incremental_pass

    for seed in range(4):
        E, alive, B, bvalid, j2 = _pass_inputs(k, cap, bb, seed)
        keep_d, alive_d = _incremental_pass(
            jnp.asarray(E), jnp.asarray(alive), jnp.asarray(B),
            jnp.asarray(bvalid))
        keep_h, alive_h = _host_pass(E, alive, B, bvalid)
        assert np.array_equal(keep_h, np.asarray(keep_d))
        assert np.array_equal(alive_h, np.asarray(alive_d))
        assert keep_h.dtype == bool and alive_h.dtype == bool
        # the cases are there: dead rows never come back, padding never
        # enters, and the float32 twins tie rather than dominate
        assert not alive_h[~alive].any() and not keep_h[~bvalid].any()
        assert keep_h[0] and keep_h[1] and alive_h[j2]


def _run_session(problem, monkeypatch, on_device: bool):
    """One PF-AP session, its passes and masks run by the host twins or,
    with ``on_device``, by the jitted device programs in their place."""
    from repro.core import MOGDConfig, MOGDSolver, ProgressiveFrontier
    from repro.core import frontier_store, pareto

    if on_device:
        calls = []

        def device_pass(E, alive, B, bvalid):
            calls.append(len(B))
            keep, still = frontier_store._incremental_pass(
                jnp.asarray(E), jnp.asarray(alive), jnp.asarray(B),
                jnp.asarray(bvalid))
            return np.asarray(keep), np.asarray(still)

        monkeypatch.setattr(frontier_store, "_host_pass", device_pass)
        monkeypatch.setattr(pareto, "_mask", lambda pts: np.asarray(
            pareto.pareto_mask(jnp.asarray(pts))))
    # a fresh solver: its start draws begin from the same key each time
    solver = MOGDSolver(problem, MOGDConfig(steps=20, multistart=2))
    pf = ProgressiveFrontier(problem, mode="AP", solver=solver,
                             batch_rects=2)
    res = pf.run(n_probes=40)
    monkeypatch.undo()
    return res.state, (calls if on_device else None)


def test_session_is_the_same_on_either_side(zdt1, monkeypatch):
    dev, dev_calls = _run_session(zdt1, monkeypatch, True)
    host, _ = _run_session(zdt1, monkeypatch, False)
    # the device programs really ran every pass of the first session
    assert len(dev_calls) == dev.store.host_passes > 0
    assert host.store.host_passes == dev.store.host_passes
    F_d, X_d = dev.store.frontier()
    F_h, X_h = host.store.frontier()
    assert np.array_equal(F_d, F_h) and np.array_equal(X_d, X_h)
    assert len(F_h) >= 3
    assert dev.gain_log == host.gain_log and dev.hv == host.hv
    for name in ("total_offered", "total_accepted", "total_infeasible",
                 "n_points", "capacity"):
        assert getattr(dev.store, name) == getattr(host.store, name), name
    assert dev.probes == host.probes
    a_d, m_d = dev.store.state_dict()
    a_h, m_h = host.store.state_dict()
    assert m_d == m_h  # the pass counter is not persisted
    assert all(np.array_equal(a_d[key], a_h[key]) for key in a_d)
