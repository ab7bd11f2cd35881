"""Surrogate fits for every tenant at once.

The MLP fit follows the repository's serial trainer (``fit_mlp`` under the
registry's ``TrainerConfig``): a 20% gate split held out, a 15% early-stop
split inside the rest, standardized inputs and target, He init, Adam with
weight decay, train-time dropout, batches of 256 with the short last batch
padded from the epoch's permutation, and the parameters of the best
validation epoch kept.  Here every (tenant, objective) model trains in one
jitted program, vmapped over models, for a fixed number of epochs.

The GP fit is the closed-form exact GP of ``models/gp.py`` (median-heuristic
lengthscale, RBF kernel, Cholesky), in float64 numpy, one model at a time.
"""

from __future__ import annotations

import numpy as np

GATE_FRAC = 0.2
EARLY_STOP_FRAC = 0.15
WEIGHT_DECAY = 1e-4


def fit_mlps(key, X, Y, hidden, epochs: int, lr: float, dropout: float,
             batch: int = 256):
    """Fit one MLP per (tenant, objective).

    ``X: (W, n, D)``, ``Y: (W, n, k)`` device arrays.  Returns
    ``(params, moments, gate_err)``: ``params`` a list of ``{"w", "b"}``
    layers with leading ``(W, k)`` axes, ``moments`` the standardization
    ``(x_mean (W,k,D), x_std, y_mean (W,k), y_std)``, and the per-tenant
    mean relative error on the gate split ``(W,)``."""
    prog = fit_program(X.shape, Y.shape[-1], hidden, epochs, lr, dropout,
                       batch)
    params, moments, err = prog(key, X, Y)
    return params, moments, err.mean(axis=1)


def fit_program(shape, k: int, hidden, epochs: int, lr: float,
                dropout: float, batch: int = 256):
    """The jitted fit of :func:`fit_mlps` for traces of ``shape (W, n, D)``
    and ``k`` objectives."""
    import jax
    import jax.numpy as jnp

    W, n, D = shape
    n_gate = max(1, int(n * GATE_FRAC))
    n_pool = n - n_gate
    n_es = max(1, int(n_pool * EARLY_STOP_FRAC))
    n_tr = n_pool - n_es
    bs = min(batch, n_tr)
    steps = -(-n_tr // bs)
    dims = (D, *hidden, 1)

    hi = jax.lax.Precision.HIGHEST

    def take(onehot, a):
        # rows by a one-hot matmul, exact at HIGHEST: a TPU runs per-model
        # row gathers under vmap so slowly that the fit never ends
        return jnp.matmul(onehot, a, precision=hi)

    def fit_one(key, x, y):
        kperm, kinit, ktrain = jax.random.split(key, 3)
        # the splits as masks: a row's place in a random permutation
        place = jnp.argsort(jax.random.permutation(kperm, n))
        m_gate = place < n_gate
        m_es = (place >= n_gate) & (place < n_gate + n_es)
        m_tr = place >= n_gate + n_es
        w_tr = m_tr / n_tr
        xm = w_tr @ x
        xs = jnp.sqrt(w_tr @ (x - xm) ** 2) + 1e-9
        ym = w_tr @ y
        ys = jnp.sqrt(w_tr @ (y - ym) ** 2) + 1e-9
        z = (x - xm) / xs
        t = (y - ym) / ys
        layers = []
        for i, kk in enumerate(jax.random.split(kinit, len(dims) - 1)):
            w = jax.random.normal(kk, (dims[i], dims[i + 1])) * jnp.sqrt(
                2.0 / dims[i])
            layers.append({"w": w, "b": jnp.zeros(dims[i + 1])})

        def fwd(p, h, key=None):
            for i, layer in enumerate(p):
                h = h @ layer["w"] + layer["b"]
                if i < len(p) - 1:
                    h = jax.nn.relu(h)
                    if key is not None and dropout > 0.0:
                        key, sub = jax.random.split(key)
                        keep = jax.random.bernoulli(sub, 1.0 - dropout,
                                                    h.shape)
                        h = jnp.where(keep, h / (1.0 - dropout), 0.0)
            return h[..., 0]

        def val_loss(p):
            return jnp.sum(m_es * (fwd(p, z) - t) ** 2) / n_es

        zeros = jax.tree.map(jnp.zeros_like, layers)

        def step(carry, idx_key):
            p, m, v, step_t = carry
            idx, kd = idx_key
            onehot = jax.nn.one_hot(idx, n, dtype=z.dtype)
            zb, tb = take(onehot, z), take(onehot, t)

            def loss(p):
                return jnp.mean((fwd(p, zb, kd) - tb) ** 2)

            g = jax.grad(loss)(p)
            step_t = step_t + 1.0
            m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, m, g)
            v = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, v, g)

            def upd(p, m, v):
                mh = m / (1 - 0.9 ** step_t)
                vh = v / (1 - 0.999 ** step_t)
                return p - lr * (mh / (jnp.sqrt(vh) + 1e-8) + WEIGHT_DECAY * p)

            p = jax.tree.map(upd, p, m, v)
            return (p, m, v, step_t), None

        def epoch(carry, ekey):
            state, best, best_v = carry
            kp, kd = jax.random.split(ekey)
            # train rows in a random order: sort random keys, others last
            u = jnp.where(m_tr, jax.random.uniform(kp, (n,)), 2.0)
            order = jnp.argsort(u)[:n_tr]
            padded = jnp.concatenate([order, order[:steps * bs - n_tr]])
            idx = padded.reshape(steps, bs)
            state, _ = jax.lax.scan(step, state, (idx, jax.random.split(
                kd, steps)))
            vl = val_loss(state[0])
            better = vl < best_v - 1e-6
            best = jax.tree.map(lambda a, b: jnp.where(better, a, b),
                                state[0], best)
            return (state, best, jnp.where(better, vl, best_v)), None

        init = ((layers, zeros, zeros, jnp.float32(0.0)), layers,
                jnp.float32(jnp.inf))
        (_, best, _), _ = jax.lax.scan(epoch, init,
                                       jax.random.split(ktrain, epochs))
        pred = fwd(best, z) * ys + ym
        rel = jnp.abs(pred - y) / jnp.maximum(jnp.abs(y), 1e-9)
        return best, (xm, xs, ym, ys), jnp.sum(m_gate * rel) / n_gate

    @jax.jit
    def fit_all(key, X, Y):
        keys = jax.random.split(key, W * k).reshape(W, k)
        xs = jnp.broadcast_to(X[:, None], (W, k, n, D))
        ys = jnp.moveaxis(Y, -1, 1)  # (W, k, n)
        return jax.vmap(jax.vmap(fit_one))(keys, xs, ys)

    return fit_all


def fit_gps(X: np.ndarray, Y: np.ndarray, pool_idx, noise: float = 1e-2,
            variance: float = 1.0):
    """Exact GP per (tenant, objective) on each tenant's train pool.

    ``X: (W, n, D)``, ``Y: (W, n, k)`` numpy; ``pool_idx[w]`` the rows the
    tenant trains on.  Returns a list over tenants of a list over objectives
    of dicts ``x_train (N, D), alpha (N,), chol (N, N), lengthscale,
    variance, x_mean (D,), x_std (D,), y_mean, y_std`` in float64."""
    out = []
    for w in range(X.shape[0]):
        x = np.asarray(X[w][pool_idx[w]], np.float64)
        per_obj = []
        for j in range(Y.shape[-1]):
            y = np.asarray(Y[w][pool_idx[w], j], np.float64)
            xm, xs = x.mean(0), x.std(0) + 1e-9
            ym, ys = y.mean(), y.std() + 1e-9
            z = (x - xm) / xs
            t = (y - ym) / ys
            sq = (z * z).sum(1)
            d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * z @ z.T, 0.0)
            np.fill_diagonal(d2, 0.0)
            med = np.median(d2[d2 > 0]) if (d2 > 0).any() else 1.0
            ls = float(np.sqrt(med / 2.0) + 1e-9)
            K = variance * np.exp(-0.5 * d2 / ls ** 2)
            K[np.diag_indices_from(K)] += noise
            L = np.linalg.cholesky(K)
            alpha = np.linalg.solve(L.T, np.linalg.solve(L, t))
            per_obj.append(dict(x_train=z, alpha=alpha, chol=L,
                                lengthscale=ls, variance=variance,
                                x_mean=xm, x_std=xs, y_mean=ym, y_std=ys))
        out.append(per_obj)
    return out
