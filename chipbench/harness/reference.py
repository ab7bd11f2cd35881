"""The plain reference: what the served path should have computed.

Straight ``jax.numpy`` from the deployment's own weights and GP factors
(made by the benchmark, never read from the system): the surrogate forward
(a standardized ReLU MLP, or an exact GP's mean plus ``alpha`` times its
predictive std), the paper's Eq. 4 penalty loss differentiated by
``jax.grad``, projected Adam with cosine learning-rate decay, the snap to
realizable knob values and the multistart pick; and, on the host, a
brute-force Pareto filter and the utopia-nearest recommendation.  It runs
once the window has closed, at ``precision`` (``"highest"`` for the check,
``"high"`` for the control).
"""

from __future__ import annotations

import numpy as np

from . import suite

ROWS = 16  # cells per tenant span, padded (batch_rects 4 x grid 2^2)


def _mlp(p, x):
    import jax

    z = (x - p["x_mean"]) / p["x_std"]
    h = z
    n = len(p["layers"])
    for i, layer in enumerate(p["layers"]):
        h = h @ layer["w"] + layer["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h[..., 0] * p["y_std"][0] + p["y_mean"][0]


def _gp(p, x, alpha):
    import jax
    import jax.numpy as jnp

    z = (x - p["x_mean"]) / p["x_std"]
    a = z / p["lengthscale"]
    b = p["x_train"] / p["lengthscale"]
    d2 = jnp.sum(a * a) + jnp.sum(b * b, -1) - 2.0 * (b @ a)
    kx = p["variance"] * jnp.exp(-0.5 * d2)
    mean = kx @ p["alpha"] * p["y_std"] + p["y_mean"]
    if alpha == 0.0:
        return mean
    v = jax.scipy.linalg.solve_triangular(p["chol"], kx[:, None],
                                          lower=True)[:, 0]
    var = jnp.clip(p["variance"] - jnp.sum(v * v), 1e-12, None)
    return mean + alpha * jnp.sqrt(var) * p["y_std"]


def objective_fn(kind: str, alpha: float):
    """``f(params, x: (D,)) -> (k,)``: the effective objective vector."""
    import jax.numpy as jnp

    if kind == "mlp":
        return lambda ps, x: jnp.stack([_mlp(p, x) for p in ps])
    return lambda ps, x: jnp.stack([_gp(p, x, alpha) for p in ps])


def eq4_loss(f, lo, hi, target, penalty, tie_eps):
    """Paper Eq. 4 over one objective vector."""
    import jax
    import jax.numpy as jnp

    width = jnp.maximum(hi - lo, 1e-12)
    fhat = (f - lo) / width
    ft = jnp.sum(fhat * jax.nn.one_hot(target, f.shape[-1], dtype=fhat.dtype))
    target_term = jnp.where((ft >= 0.0) & (ft <= 1.0), ft * ft, 0.0)
    violated = (fhat < 0.0) | (fhat > 1.0)
    viol = jnp.where(violated, (fhat - 0.5) ** 2 + penalty, 0.0).sum()
    tie = tie_eps * jnp.sum(jnp.where(violated, 0.0,
                                      jnp.clip(fhat, 0.0, 1.0) ** 2))
    return target_term + viol + tie


def descend(loss, x0, mogd: dict):
    """Projected Adam with cosine decay from one start (paper §4.2.1)."""
    import jax
    import jax.numpy as jnp

    b1, b2, eps = mogd["adam_b1"], mogd["adam_b2"], mogd["adam_eps"]
    steps, lr0, floor = mogd["steps"], mogd["lr"], mogd["lr_floor"]
    grad = jax.grad(loss)

    def step(carry, _):
        x, m, v, t = carry
        g = grad(x)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        frac = (t - 1.0) / steps
        lr = lr0 * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
        x = jnp.clip(x - lr * mh / (jnp.sqrt(vh) + eps), 0.0, 1.0)
        return (x, m, v, t + 1.0), None

    z = jnp.zeros_like(x0)
    (x, _, _, _), _ = jax.lax.scan(step, (x0, z, z, jnp.float32(1.0)), None,
                                   length=steps)
    return x


def solve_spans(kind: str, alpha: float, mogd: dict, params, x0s, los, his,
                targets, precision: str = "highest"):
    """Re-solve tenant spans: ``params`` stacked over spans (P, ...),
    ``x0s: (P, ROWS, S, D)``, ``los/his: (P, ROWS, k)``, ``targets:
    (P, ROWS)``.  Returns ``(x, f, feasible)`` per row."""
    import jax
    import jax.numpy as jnp

    f_of = objective_fn(kind, alpha)
    pen, tie, tol = mogd["penalty"], mogd["tie_break_eps"], mogd["feas_tol"]

    def row(p, x0_s, lo, hi, t):
        def loss(x):
            return eq4_loss(f_of(p, x), lo, hi, t, pen, tie)

        finals = jax.vmap(lambda x0: descend(loss, x0, mogd))(x0_s)
        snapped = suite.snap(finals)
        fv = jax.vmap(lambda x: f_of(p, x))(snapped)
        fhat = (fv - lo) / jnp.maximum(hi - lo, 1e-12)
        feas = jnp.all((fhat >= -tol) & (fhat <= 1.0 + tol), axis=-1)
        score = jnp.where(feas, fv[:, t], jnp.inf)
        best = jnp.argmin(score)
        return snapped[best], fv[best], jnp.any(feas)

    def span(p, *rows):
        return jax.vmap(lambda *r: row(p, *r))(*rows)

    with jax.default_matmul_precision(precision):
        fn = jax.jit(jax.vmap(span))
        out = fn(params, jnp.asarray(x0s, jnp.float32),
                 jnp.asarray(los, jnp.float32), jnp.asarray(his, jnp.float32),
                 jnp.asarray(targets, jnp.int32))
    return tuple(np.asarray(a) for a in out)


def pareto_rows(F: np.ndarray) -> tuple[list, np.ndarray]:
    """Brute-force Pareto set of the offered points, compared in float32 as
    the store compares them, deduplicated at 1e-9.  Returns ``(keys,
    rows)`` in the order the points were first offered: the store's order,
    which decides ties in the recommendation."""
    F = np.asarray(F, np.float64).reshape(len(F), -1)
    keys, rows = [], []
    seen = set()
    for row in F:
        key = np.round(row, 9).tobytes()
        if key in seen or not np.all(np.isfinite(row)):
            continue
        seen.add(key)
        keys.append(key)
        rows.append(row)
    if not rows:
        return [], np.zeros((0, F.shape[1]))
    E = np.asarray(rows, np.float32)
    le = np.all(E[None, :, :] <= E[:, None, :], axis=-1)  # [i, j]: j <= i
    lt = np.any(E[None, :, :] < E[:, None, :], axis=-1)
    keep = ~np.any(le & lt, axis=1)
    return ([k for k, ok in zip(keys, keep) if ok],
            np.asarray(rows)[keep])


def utopia_nearest(F: np.ndarray, refs: np.ndarray) -> np.ndarray:
    """The recommended row of ``F``: nearest to utopia after normalizing by
    the reference points' utopia-to-nadir span (paper §5, UN)."""
    utopia, nadir = refs.min(0), refs.max(0)
    span = np.maximum(nadir - utopia, 1e-9)
    span = np.maximum((utopia + span) - utopia, 1e-12)
    z = (F - utopia) / span
    return F[int(np.argmin(np.linalg.norm(z, axis=1)))]
