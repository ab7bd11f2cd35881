"""Property + unit tests for Pareto primitives (paper Defs. 3.1-3.3)."""

import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; unit tests still run
    from _hypothesis_stub import given, settings, st

from repro.core import (
    crowding_distance,
    dominates,
    hypervolume,
    hypervolume_2d,
    pareto_filter,
    pareto_filter_masked,
    pareto_mask,
)


def _points(draw_k=2, nmax=40):
    return st.lists(
        st.tuples(*([st.floats(-100, 100, allow_nan=False, width=32)] * draw_k)),
        min_size=1,
        max_size=nmax,
    )


class TestDomination:
    def test_simple(self):
        assert bool(dominates(jnp.array([1.0, 1.0]), jnp.array([2.0, 2.0])))
        assert bool(dominates(jnp.array([1.0, 2.0]), jnp.array([1.0, 3.0])))
        assert not bool(dominates(jnp.array([1.0, 3.0]), jnp.array([2.0, 2.0])))

    def test_equal_points_do_not_dominate(self):
        p = jnp.array([1.0, 2.0])
        assert not bool(dominates(p, p))

    @given(_points())
    @settings(max_examples=50, deadline=None)
    def test_antisymmetric(self, pts):
        arr = jnp.asarray(np.array(pts, dtype=np.float64))
        a, b = arr[0], arr[-1]
        assert not (bool(dominates(a, b)) and bool(dominates(b, a)))


class TestParetoMask:
    @given(_points())
    @settings(max_examples=50, deadline=None)
    def test_survivors_mutually_nondominated(self, pts):
        arr = np.array(pts, dtype=np.float64)
        mask = np.asarray(pareto_mask(jnp.asarray(arr)))
        surv = arr[mask]
        for i in range(len(surv)):
            for j in range(len(surv)):
                if i != j:
                    assert not bool(
                        dominates(jnp.asarray(surv[i]), jnp.asarray(surv[j]))
                    )

    @given(_points())
    @settings(max_examples=50, deadline=None)
    def test_eliminated_are_dominated_by_a_survivor(self, pts):
        arr = np.array(pts, dtype=np.float64)
        mask = np.asarray(pareto_mask(jnp.asarray(arr)))
        surv = arr[mask]
        for i in np.where(~mask)[0]:
            assert any(
                bool(dominates(jnp.asarray(s), jnp.asarray(arr[i]))) for s in surv
            )

    @given(_points(draw_k=3, nmax=25))
    @settings(max_examples=30, deadline=None)
    def test_3d(self, pts):
        arr = np.array(pts, dtype=np.float64)
        mask = np.asarray(pareto_mask(jnp.asarray(arr)))
        assert mask.any()  # at least one non-dominated point always exists

    def test_masked_variant(self):
        pts = jnp.asarray([[0.0, 0.0], [1.0, 1.0], [2.0, 2.0]])
        valid = jnp.asarray([False, True, True])
        m = np.asarray(pareto_filter_masked(pts, valid))
        assert m.tolist() == [False, True, False]

    def test_filter_returns_payload(self):
        pts = np.array([[1.0, 2.0], [2.0, 1.0], [3.0, 3.0]])
        pay = np.array([10, 20, 30])
        f, p = pareto_filter(pts, pay)
        assert len(f) == 2 and set(p.tolist()) == {10, 20}


class TestHypervolume:
    def test_single_point(self):
        assert hypervolume_2d(np.array([[0.0, 0.0]]), np.array([1.0, 1.0])) == 1.0

    def test_dominated_point_adds_nothing(self):
        a = hypervolume_2d(np.array([[0.0, 0.0]]), np.array([1.0, 1.0]))
        b = hypervolume_2d(
            np.array([[0.0, 0.0], [0.5, 0.5]]), np.array([1.0, 1.0])
        )
        assert a == b

    def test_monotone_in_points(self):
        ref = np.array([1.0, 1.0])
        base = np.array([[0.5, 0.1]])
        more = np.array([[0.5, 0.1], [0.1, 0.5]])
        assert hypervolume_2d(more, ref) >= hypervolume_2d(base, ref)

    def test_3d_cube(self):
        pts = np.array([[0.0, 0.0, 0.0]])
        assert abs(hypervolume(pts, np.array([1.0, 1.0, 1.0])) - 1.0) < 1e-12

    def test_3d_staircase_exact(self):
        """Two overlapping boxes: |A ∪ B| = |A| + |B| - |A ∩ B|."""
        ref = np.array([1.0, 1.0, 1.0])
        pts = np.array([[0.0, 0.5, 0.2], [0.5, 0.0, 0.6]])
        vol_a = 1.0 * 0.5 * 0.8
        vol_b = 0.5 * 1.0 * 0.4
        vol_ab = 0.5 * 0.5 * 0.4
        assert abs(hypervolume(pts, ref) - (vol_a + vol_b - vol_ab)) < 1e-12

    def test_3d_monotone_in_points(self):
        rng = np.random.default_rng(7)
        ref = np.array([1.0, 1.0, 1.0])
        pts = rng.uniform(0, 1, (12, 3))
        hv_all = hypervolume(pts, ref)
        hv_part = hypervolume(pts[:6], ref)
        assert hv_all >= hv_part - 1e-12
        # adding a dominated point changes nothing
        worst = pts.max(0)[None] * 0.999 + 0.001
        assert abs(hypervolume(np.vstack([pts, worst]), ref) - hv_all) < 1e-12

    @given(_points())
    @settings(max_examples=30, deadline=None)
    def test_nonnegative(self, pts):
        arr = np.array(pts, dtype=np.float64)
        assert hypervolume_2d(arr, np.array([200.0, 200.0])) >= 0.0


def _crowding_reference(pts: np.ndarray) -> np.ndarray:
    """The pre-vectorization O(n·k) loop, kept as the oracle."""
    n, k = pts.shape
    if n <= 2:
        return np.full(n, np.inf)
    dist = np.zeros(n)
    for j in range(k):
        order = np.argsort(pts[:, j])
        fmin, fmax = pts[order[0], j], pts[order[-1], j]
        dist[order[0]] = dist[order[-1]] = np.inf
        if fmax - fmin < 1e-30:
            continue
        for idx in range(1, n - 1):
            dist[order[idx]] += (
                pts[order[idx + 1], j] - pts[order[idx - 1], j]
            ) / (fmax - fmin)
    return dist


class TestCrowding:
    def test_extremes_infinite(self):
        pts = np.array([[0.0, 3.0], [1.0, 2.0], [2.0, 1.0], [3.0, 0.0]])
        cd = crowding_distance(pts)
        assert np.isinf(cd[0]) and np.isinf(cd[-1])
        assert np.isfinite(cd[1]) and np.isfinite(cd[2])

    @pytest.mark.parametrize("n,k,seed", [(3, 2, 0), (25, 2, 1), (40, 3, 2),
                                          (17, 4, 3)])
    def test_vectorized_matches_loop(self, n, k, seed):
        pts = np.random.default_rng(seed).uniform(0, 1, (n, k))
        np.testing.assert_allclose(crowding_distance(pts),
                                   _crowding_reference(pts))

    def test_degenerate_column(self):
        """A constant objective contributes nothing except inf extremes."""
        pts = np.array([[0.0, 5.0], [1.0, 5.0], [2.0, 5.0], [3.0, 5.0]])
        got = crowding_distance(pts)
        np.testing.assert_allclose(got, _crowding_reference(pts))


# -- the host twin: small masks run in numpy, bit for bit -------------------

def _twin_points(n: int, k: int, seed: int) -> np.ndarray:
    """Points with exact ties (a coarse grid) and pairs that are distinct
    in float64 but equal in float32 (values in [1, 2], where 1e-12 is below
    float32's resolution)."""
    rng = np.random.default_rng(seed)
    pts = 1.0 + rng.integers(0, 9, (n, k)) / 8.0 + rng.uniform(0, 1e-3,
                                                          (n, k))
    if n >= 4:
        pts[1] = pts[0]
        pts[3] = pts[2] + 1e-12
    return pts


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("n", [1, 2, 5, 64, 256])
def test_host_mask_and_hypervolume_match_the_device(n, k, monkeypatch):
    from repro.core import pareto

    pts = _twin_points(n, k, seed=10 * n + k)
    q = pts.astype(pareto.compare_dtype())
    host = ~pareto.dominated_by(q, q)
    assert host.dtype == bool
    assert np.array_equal(host, np.asarray(pareto_mask(jnp.asarray(pts))))
    ref = np.full(k, 2.5)
    hv = {"host": (hypervolume(pts, ref),
                   hypervolume_2d(pts[:, :2], ref[:2]))}
    monkeypatch.setattr(pareto, "_mask", lambda p: np.asarray(
        pareto_mask(jnp.asarray(p))))
    hv["device"] = (hypervolume(pts, ref),
                    hypervolume_2d(pts[:, :2], ref[:2]))
    assert hv["host"] == hv["device"]  # identical floats, not approx
    assert hv["host"][0] > 0.0


def test_small_absorb_sends_nothing_to_the_device(monkeypatch):
    """One store pass of 16 offers against 64 live rows and the gain
    telemetry's hypervolume run with every transfer to or from the device
    forbidden, and with both device programs replaced by a trap: the
    small path is host numpy end to end."""
    import jax

    from repro.core import (FrontierStore, PFState, RectangleQueue,
                            make_rectangle, pareto)
    from repro.core import frontier_store
    from repro.core.progressive_frontier import frontier_hypervolume

    rng = np.random.default_rng(0)
    a = np.sort(rng.uniform(0, 1, 64))
    store = FrontierStore(k=2, dim=3)
    store.add(np.stack([a, 1.0 - a], 1), rng.uniform(0, 1, (64, 3)))
    assert store.n_points == 64
    state = PFState(queue=RectangleQueue(make_rectangle(np.zeros(2),
                                                        np.ones(2))),
                    store=store, utopia=np.zeros(2), nadir=np.ones(2),
                    bounds=np.array([[0.0, 0.0], [1.0, 1.0]]))
    device_pass = frontier_store._incremental_pass

    def trap(*args, **kw):
        raise AssertionError("a device program ran on the small path")

    monkeypatch.setattr(frontier_store, "_incremental_pass", trap)
    monkeypatch.setattr(pareto, "pareto_mask", trap)
    F = rng.uniform(0, 1, (16, 2))
    with jax.transfer_guard("disallow"):
        store.add(F, rng.uniform(0, 1, (16, 3)))
        hv = frontier_hypervolume(state)
    assert store.host_passes == 2
    assert 0.0 < hv < 1.0
    # the guard is live: the device pass on the same numpy arrays is refused
    with jax.transfer_guard("disallow"), pytest.raises(
            jax.errors.JaxRuntimeError, match="Disallowed"):
        device_pass(store._F, store._alive, np.ones((16, 2)),
                    np.ones(16, bool))
