"""Pareto geometry primitives (paper Defs. 3.1-3.3).

All functions operate on arrays of objective-space points with shape
``(N, k)`` under *minimization* semantics.  The O(N^2) masked comparison is
exactly what the Pallas ``pareto_filter`` kernel tiles for TPU; the jnp
implementation here doubles as its oracle (see ``repro.kernels.pareto_filter``).

The frontier store's pass and the hypervolume's mask run on the host
instead (DESIGN.md §3): ``dominated_by`` is the numpy twin of the
dominance predicate, compared at the dtype the device programs see, so
either side makes the same decisions bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def dominates(f1: Array, f2: Array) -> Array:
    """Def 3.1: f1 Pareto-dominates f2 (leq everywhere, lt somewhere).

    Broadcasts: ``f1: (..., k)``, ``f2: (..., k)`` -> bool array ``(...)``.
    """
    leq = jnp.all(f1 <= f2, axis=-1)
    lt = jnp.any(f1 < f2, axis=-1)
    return jnp.logical_and(leq, lt)


@jax.jit
def pareto_mask(points: Array) -> Array:
    """Boolean mask of non-dominated points among ``points: (N, k)``.

    A point is kept iff no other point dominates it (Def 3.2).  Duplicate
    points do not dominate each other, so all copies of a non-dominated
    value survive (dedupe separately if needed).
    """
    a = points[:, None, :]  # (N, 1, k)
    b = points[None, :, :]  # (1, N, k)
    dom = jnp.logical_and(jnp.all(b <= a, axis=-1), jnp.any(b < a, axis=-1))
    return ~jnp.any(dom, axis=1)


def compare_dtype():
    """The float dtype the device programs compare at: ``jnp.asarray`` of
    a float64 array gives float32 unless x64 is enabled.  Host twins cast
    to it, so both sides decide on the same values."""
    return jax.dtypes.canonicalize_dtype(np.float64)


def dominated_by(P: np.ndarray, Q: np.ndarray) -> np.ndarray:
    """Host twin of the dominance predicate: ``(rows,)`` mask of the rows
    of ``P: (rows, k)`` that some row of ``Q: (cols, k)`` dominates (Def
    3.1), compared in the dtype they are given."""
    le = np.ones((len(P), len(Q)), dtype=bool)
    lt = np.zeros((len(P), len(Q)), dtype=bool)
    for c in range(P.shape[1]):
        p, q = P[:, c, None], Q[None, :, c]
        le &= q <= p
        lt |= q < p
    return np.any(le & lt, axis=1)


def _mask(pts: np.ndarray) -> np.ndarray:
    """``pareto_mask`` of float64 ``pts``, computed in host numpy at the
    device's dtype: the same mask, bit for bit, without a round trip."""
    q = pts.astype(compare_dtype())
    return ~dominated_by(q, q)


def pareto_filter(points: Array, payload: Array | None = None):
    """Return the Pareto subset of points (and aligned payload rows)."""
    mask = np.asarray(pareto_mask(jnp.asarray(points)))
    pts = np.asarray(points)[mask]
    if payload is None:
        return pts
    return pts, np.asarray(payload)[mask]


def pareto_filter_masked(points: Array, valid: Array) -> Array:
    """Pareto mask restricted to ``valid`` rows; invalid rows are neither
    dominators nor survivors.  Used by the PF loop where some CO probes
    return infeasible (no-point) results (Prop. 3.3/3.4)."""
    big = jnp.where(valid[:, None], points, jnp.inf)
    a = big[:, None, :]
    b = big[None, :, :]
    dom = jnp.logical_and(jnp.all(b <= a, axis=-1), jnp.any(b < a, axis=-1))
    return jnp.logical_and(~jnp.any(dom, axis=1), valid)


def hypervolume_2d(points: Array, ref: Array) -> float:
    """Exact 2-D hypervolume dominated by ``points`` w.r.t. ``ref`` point
    (minimization).  Used by tests/benchmarks to score frontier quality."""
    pts = np.asarray(points, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    pts = pts[np.all(pts <= ref, axis=1)]
    if len(pts) == 0:
        return 0.0
    pts = pts[_mask(pts)]
    order = np.argsort(pts[:, 0])
    pts = pts[order]
    hv, prev_y = 0.0, ref[1]
    for x, y in pts:
        if y < prev_y:
            hv += (ref[0] - x) * (prev_y - y)
            prev_y = y
    return float(hv)


def hypervolume(points: Array, ref: Array) -> float:
    """Hypervolume for k<=3 (exact recursive sweep); tests/benchmark metric."""
    pts = np.asarray(points, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    k = pts.shape[1] if pts.ndim == 2 and len(pts) else len(ref)
    if len(pts) == 0:
        return 0.0
    if k == 1:
        return float(max(0.0, ref[0] - pts[:, 0].min()))
    if k == 2:
        return hypervolume_2d(pts, ref)
    if k == 3:
        # Sweep over sorted z; accumulate 2-D HV slabs.
        pts = pts[np.all(pts <= ref, axis=1)]
        if len(pts) == 0:
            return 0.0
        pts = pts[_mask(pts)]
        zs = np.unique(pts[:, 2])
        hv, prev_z = 0.0, ref[2]
        for z in zs[::-1]:
            # The slab of heights (z, prev_z] contains no point z-coords, so
            # its dominated cross-section is the union of the 2-D boxes of
            # exactly the points with z-coordinate <= z.
            area = hypervolume_2d(pts[pts[:, 2] <= z + 1e-18][:, :2], ref[:2])
            hv += area * (prev_z - z)
            prev_z = z
        return float(hv)
    raise NotImplementedError("hypervolume implemented for k<=3")


def crowding_distance(points: Array) -> Array:
    """NSGA-II crowding distance (used by the Evo baseline and coverage
    metrics).  (N, k) -> (N,) with inf at extremes."""
    pts = np.asarray(points, dtype=np.float64)
    n, k = pts.shape
    if n <= 2:
        return np.full(n, np.inf)
    order = np.argsort(pts, axis=0)  # (n, k): order[r, j] = r-th smallest
    srt = np.take_along_axis(pts, order, axis=0)
    span = srt[-1] - srt[0]  # (k,)
    # Interior contribution per column: neighbour gap normalized by span;
    # degenerate columns (zero span) contribute nothing to interior points.
    inner = np.where(span > 1e-30, (srt[2:] - srt[:-2]) / np.where(
        span > 1e-30, span, 1.0), 0.0)  # (n-2, k)
    per_col = np.zeros((n, k))
    cols = np.arange(k)[None, :]
    per_col[order[1:-1], cols] = inner
    per_col[order[[0, -1]], cols] = np.inf  # extremes (also when degenerate)
    return per_col.sum(axis=1)


def coverage_spread(points: Array) -> float:
    """Frontier coverage metric: mean nearest-neighbour gap along the
    normalized frontier (lower = denser/more even coverage).  Quantifies
    the paper's "poor coverage of WS" observation (Fig 4b)."""
    pts = np.asarray(points, dtype=np.float64)
    if len(pts) < 2:
        return float("inf")
    lo, hi = pts.min(0), pts.max(0)
    span = np.where(hi - lo < 1e-30, 1.0, hi - lo)
    z = (pts - lo) / span
    d2 = ((z[:, None, :] - z[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    return float(np.sqrt(d2.min(axis=1)).mean())
