"""Fused MOGD descend-project inner loop (paper §4.2.1) as one Pallas kernel.

The executor's jnp path (``adam_project_descend``) launches one matmul per
MLP layer per Adam step, round-tripping the ``(B, 128)`` activations, the
gradient, and the optimizer moments through HBM for all ``cfg.steps``.
This kernel keeps the surrogate-MLP weights, the running activation, and
the Adam ``(m, v)`` state **resident in VMEM across the whole descent**:
one grid step loads a ``(BLOCK_M, D)`` tile of starts plus its group's
weights, runs every descend-project iteration in registers/VMEM, and
writes back only the final projected point.

The backward pass is hand-written, not autodiff: paper Eq. 4 is separable
per objective — ``L(x) = Σ_j g_j(f_j(x))`` over the target, violation,
tie-break, and user-bound terms — so ``dL/dx`` is one scalar ``dL/df_j``
per objective chained through the MLP transpose (``g @ Wᵀ`` with ReLU
masks).  No weight gradients exist in this loop, which is what makes the
whole VJP small enough to fuse.

Layout mirrors the executor plane (DESIGN.md §10): the batch is
``(G groups, M rows)`` where rows of a group share their surrogate weights
(``M = R cells x S starts``), the grid is ``(G, M/BLOCK_M)``, and the
standardization affine is folded into the first/last layers outside the
kernel so the in-kernel program is a plain ReLU MLP.

Three implementation tiers, selected by :func:`descend_batch`:

* ``"pallas"`` — the fused kernel (TPU/GPU; ``interpret=True`` on CPU for
  tests only — the interpreter is orders of magnitude slower than XLA).
* ``"xla"`` — the same hand-written forward+backward math as straight-line
  jnp under jit: the production CPU tier, and the shape the roofline
  model in ``benchmarks/kernelbench.py`` scores against the scan path.
* oracle — ``kernels.ref.mogd_descend`` differentiates the Eq. 4 loss
  with ``jax.grad``, so the hand-written backward is checked against
  autodiff, never against itself.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import default_interpret, resolve_interpret

BLOCK_M = 256
# Full-f32 matmuls, as the executor's scan path runs them: at the TPU
# default (one bf16 pass) 80 Adam steps of two correct implementations end
# tenths apart, and the parity gate cannot tell a broken kernel from noise.
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Plan: the static half of a fusable program
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DescendPlan:
    """Static description of a fusable surrogate program: per-objective
    MLP layer dims, log-target flags, and orientation signs.  Derived
    purely from the executor's structure token, so plan identity ==
    structure identity."""

    layer_dims: tuple  # per objective: (D, hidden..., 1)
    log_targets: tuple  # per objective: bool
    signs: tuple  # per objective: +-1.0

    @property
    def k(self) -> int:
        return len(self.layer_dims)

    @property
    def dim(self) -> int:
        return self.layer_dims[0][0]


def plan_from_structure(structure, use_std: bool = False) -> DescendPlan | None:
    """Parse an executor structure token into a :class:`DescendPlan`.

    Returns None for anything the kernel cannot fuse — GP programs,
    opaque closures, stage families, uncertainty-aware (``use_std``)
    requests — which routes the executor to its ``lax.scan`` path."""
    if use_std:
        return None  # MC-dropout std term: not separable, stays on jnp
    s = structure
    signs = None
    if isinstance(s, tuple) and len(s) == 3 and s[0] == "orient":
        signs = tuple(float(x) for x in s[1])
        s = s[2]
    if not (isinstance(s, tuple) and len(s) == 2 and s[0] == "stack"):
        return None
    dims, logs = [], []
    for m in s[1]:
        if not (isinstance(m, tuple) and len(m) == 5 and m[0] == "mlp"):
            return None
        layer_dims = tuple(int(d) for d in m[1])
        if len(layer_dims) < 2 or layer_dims[-1] != 1:
            return None
        dims.append(layer_dims)
        logs.append(bool(m[2]))
    if not dims or len({d[0] for d in dims}) != 1:
        return None
    k = len(dims)
    if signs is None:
        signs = (1.0,) * k
    if len(signs) != k:
        return None
    return DescendPlan(tuple(dims), tuple(logs), signs)


def fold_affine(plan: DescendPlan, params):
    """Fold each objective's standardization affine into its MLP.

    ``z = (x - xm)/xs`` folds into layer 0 (``W0' = W0/xs``,
    ``b0' = b0 - (xm/xs) @ W0``); ``y = raw*ys + ym`` folds into the last
    layer.  Works batched (leading G axis) or unbatched; returns a tuple
    over objectives of ``(ws, bs)`` plain ReLU-MLP weights."""
    out = []
    for j in range(plan.k):
        p = params[j]
        ws = [jnp.asarray(l["w"]) for l in p["layers"]]
        bs = [jnp.asarray(l["b"]) for l in p["layers"]]
        xm, xs = jnp.asarray(p["x_mean"]), jnp.asarray(p["x_std"])
        # target moments are one scalar per group, stored as () or (1,)
        # (a trained regressor keeps its (1,) column moments)
        lead = ws[0].shape[:-2]
        ym = jnp.asarray(p["y_mean"]).reshape(lead)
        ys = jnp.asarray(p["y_std"]).reshape(lead)
        bs[0] = bs[0] - jnp.einsum("...d,...dh->...h", xm / xs, ws[0])
        ws[0] = ws[0] / xs[..., :, None]
        ws[-1] = ws[-1] * ys[..., None, None]
        bs[-1] = bs[-1] * ys[..., None] + ym[..., None]
        out.append((tuple(ws), tuple(bs)))
    return tuple(out)


# ---------------------------------------------------------------------------
# Hand-written gradient of the Eq. 4 loss (shared by the XLA tier and the
# Pallas kernel body — both trace this exact jnp code).
# ---------------------------------------------------------------------------


def _dloss_df(f, lo, hi, ulo, uhi, us, tsel, tie_eps):
    """Per-objective dL/df at ``f`` (elementwise over any shape).

    Eq. 4 is separable, so each term differentiates in isolation:
    target (one-hot ``tsel``, active inside the box), violation
    (quadratic-plus-penalty outside), tie-break (inside), and the user
    value-bound penalty (unnormalized units)."""
    width = jnp.maximum(hi - lo, 1e-12)
    fhat = (f - lo) / width
    violated = jnp.logical_or(fhat < 0.0, fhat > 1.0)
    d = tsel * jnp.where(violated, 0.0, 2.0 * fhat)
    d = d + jnp.where(violated, 2.0 * (fhat - 0.5), 0.0)
    d = d + jnp.where(violated, 0.0, tie_eps * 2.0 * jnp.clip(fhat, 0.0, 1.0))
    d = d / width
    over = f - uhi
    under = ulo - f
    excess = jnp.maximum(under, 0.0) + jnp.maximum(over, 0.0)
    bsign = jnp.where(over > 0.0, 1.0, jnp.where(under > 0.0, -1.0, 0.0))
    return d + jnp.where(
        excess > 0.0, 2.0 * excess / (us * us) * bsign, 0.0)


def _grad_rows(plan: DescendPlan, tie_eps, wbs, x, lo, hi, ulo, uhi, us,
               tsel):
    """dL/dx for a row tile sharing one weight set.

    ``x: (M, D)``; row constants ``(M, k)``.  Forward keeps pre-activations
    for the ReLU masks; backward chains the scalar dL/df_j through the
    transposed layers — input gradient only, no weight gradients."""
    dx = jnp.zeros_like(x)
    for j in range(plan.k):
        ws, bs = wbs[j]
        n_layers = len(ws)
        h = x
        acts = []
        for l in range(n_layers):
            a = jnp.dot(h, ws[l], precision=HIGHEST,
                        preferred_element_type=jnp.float32)
            a = a + bs[l]  # (H,) in the XLA tier, (1, H) in the kernel
            if l < n_layers - 1:
                acts.append(a)
                h = jnp.maximum(a, 0.0)
            else:
                h = a
        raw = h[:, 0]  # (M,)
        sj = plan.signs[j]
        if plan.log_targets[j]:
            ex = jnp.exp(raw)
            fj, dfdraw = sj * ex, sj * ex
        else:
            fj, dfdraw = sj * raw, sj
        dldf = _dloss_df(fj, lo[:, j], hi[:, j], ulo[:, j], uhi[:, j],
                         us[:, j], tsel[:, j], tie_eps)
        g = (dldf * dfdraw)[:, None]  # (M, 1)
        for l in range(n_layers - 1, -1, -1):
            g = jnp.dot(g, ws[l].T, precision=HIGHEST,
                        preferred_element_type=jnp.float32)
            if l > 0:
                g = g * (acts[l - 1] > 0.0)
        dx = dx + g
    return jnp.where(jnp.isfinite(dx), dx, 0.0)


def adam_schedule(cfg):
    """``(steps, 3)`` per-step scalars of the descent: the cosine-decayed
    learning rate and the Adam bias-correction denominators ``1 - b1**t``,
    ``1 - b2**t`` (t = 1..steps).  Computed once in XLA with the scan
    path's own expressions, so both tiers divide by the very values
    ``adam_project_descend`` does, and the kernel evaluates no
    transcendental (Mosaic cannot lower ``powf`` with a traced
    exponent)."""
    t = jnp.arange(1, cfg.steps + 1, dtype=jnp.float32)
    frac = (t - 1.0) / cfg.steps
    lr = cfg.lr * (cfg.lr_floor
                   + (1 - cfg.lr_floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.stack([lr, 1 - cfg.adam_b1 ** t, 1 - cfg.adam_b2 ** t], 1)


def _adam_update(x, m, v, g, lr, d1, d2, cfg):
    """One projected-Adam step — the update of ``adam_project_descend``,
    with one row of :func:`adam_schedule` as ``(lr, d1, d2)``."""
    m = cfg.adam_b1 * m + (1 - cfg.adam_b1) * g
    v = cfg.adam_b2 * v + (1 - cfg.adam_b2) * g * g
    mh = m / d1
    vh = v / d2
    x = jnp.clip(x - lr * mh / (jnp.sqrt(vh) + cfg.adam_eps), 0.0, 1.0)
    return x, m, v


# ---------------------------------------------------------------------------
# Tier 1: straight-line XLA (the production CPU tier)
# ---------------------------------------------------------------------------


def _descend_rows_xla(plan: DescendPlan, cfg, wbs, x0, lo, hi, ulo, uhi, us,
                      tsel):
    """One group's rows, hand-written backward, ``lax.scan`` over steps."""
    tie_eps = cfg.tie_break_eps

    def step(carry, sched):
        x, m, v = carry
        g = _grad_rows(plan, tie_eps, wbs, x, lo, hi, ulo, uhi, us, tsel)
        return _adam_update(x, m, v, g, *sched, cfg), None

    z = jnp.zeros_like(x0)
    (x, _, _), _ = jax.lax.scan(step, (x0, z, z), adam_schedule(cfg))
    return x


# ---------------------------------------------------------------------------
# Tier 2: the fused Pallas kernel
# ---------------------------------------------------------------------------


def _make_kernel(plan: DescendPlan, cfg, block_m: int):
    tie_eps = cfg.tie_break_eps
    n_wb = sum(len(d) - 1 for d in plan.layer_dims) * 2

    def kernel(sched_ref, x0_ref, lo_ref, hi_ref, ulo_ref, uhi_ref, us_ref,
               tsel_ref, *rest):
        out_ref = rest[n_wb]
        # Rebuild the per-objective (ws, bs) weight lists from the flat
        # variadic refs — loaded once per grid step, resident thereafter.
        # Biases arrive as (1, H) rows of a (G, 1, H) array.
        wbs, i = [], 0
        for dims in plan.layer_dims:
            ws, bs = [], []
            for _ in range(len(dims) - 1):
                ws.append(rest[i][0])
                bs.append(rest[i + 1][0])
                i += 2
            wbs.append((tuple(ws), tuple(bs)))
        x0 = x0_ref[0]
        lo, hi = lo_ref[0], hi_ref[0]
        ulo, uhi, us = ulo_ref[0], uhi_ref[0], us_ref[0]
        tsel = tsel_ref[0]

        def body(i, carry):
            x, m, v = carry
            g = _grad_rows(plan, tie_eps, wbs, x, lo, hi, ulo, uhi, us, tsel)
            lr, d1, d2 = (sched_ref[3 * i + c] for c in range(3))
            return _adam_update(x, m, v, g, lr, d1, d2, cfg)

        z = jnp.zeros_like(x0)
        x, _, _ = jax.lax.fori_loop(0, cfg.steps, body, (x0, z, z))
        out_ref[0] = x

    return kernel


def _descend_pallas(plan: DescendPlan, cfg, folded, x, lo, hi, ulo, uhi, us,
                    tsel, interpret: bool):
    """``x: (G, M, D)`` rows + per-group folded weights -> finals."""
    G, M, D = x.shape
    k = plan.k
    block_m = BLOCK_M
    while block_m > 8 and block_m >= 2 * M:
        block_m //= 2
    pad = (-M) % block_m
    if pad:
        cfgs = [(x, 0.0), (lo, 0.0), (hi, 1.0), (ulo, -1e30), (uhi, 1e30),
                (us, 1.0), (tsel, 0.0)]
        x, lo, hi, ulo, uhi, us, tsel = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=c)
            for a, c in cfgs)
    Mp = M + pad
    grid = (G, Mp // block_m)

    row_spec = lambda w: pl.BlockSpec((1, block_m, w), lambda g, t: (g, t, 0))
    # the schedule rides flat in SMEM: scalars read by step index
    in_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM), row_spec(D)]
                + [row_spec(k)] * 6)
    args = [adam_schedule(cfg).reshape(-1), x, lo, hi, ulo, uhi, us, tsel]
    for ws, bs in folded:
        for w, b in zip(ws, bs):
            # (G, H) biases ride as (G, 1, H): a (1, H) block of a (G, H)
            # array breaks the TPU (8, 128) block rule
            b = b[:, None, :]
            in_specs.append(
                pl.BlockSpec((1, *w.shape[1:]), lambda g, t: (g, 0, 0)))
            in_specs.append(
                pl.BlockSpec((1, *b.shape[1:]), lambda g, t: (g, 0, 0)))
            args.extend([w, b])

    out = pl.pallas_call(
        _make_kernel(plan, cfg, block_m),
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec(D),
        out_shape=jax.ShapeDtypeStruct((G, Mp, D), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:, :M]


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def descend_batch(plan: DescendPlan, cfg, params, x0s, los, his, ulos, uhis,
                  uscales, targets, *, impl: str | None = None,
                  interpret: bool | None = None):
    """Fused multi-start descent over the executor's grouped batch.

    ``params``: stacked program params (tuple over objectives, leading G
    axis); ``x0s: (G, R, S, D)``; row constants ``(G, R, k)``;
    ``targets: (G, R)`` int.  Returns finals ``(G, R, S, D)`` — the
    executor snaps/scores them exactly as it does the scan path's.

    ``impl``: None = "pallas" on compiled backends, "xla" elsewhere (the
    Pallas interpreter is a debug surface, never a production tier).
    """
    if impl is None:
        impl = "xla" if default_interpret() else "pallas"
    x0s = jnp.asarray(x0s, jnp.float32)
    G, R, S, D = x0s.shape
    M = R * S
    x = x0s.reshape(G, M, D)

    def per_row(a, fill=None):
        a = jnp.asarray(a, jnp.float32)  # (G, R, k) -> (G, M, k)
        return jnp.broadcast_to(
            a[:, :, None, :], (G, R, S, a.shape[-1])).reshape(G, M, -1)

    lo, hi = per_row(los), per_row(his)
    ulo, uhi, us = per_row(ulos), per_row(uhis), per_row(uscales)
    tsel = per_row(jax.nn.one_hot(
        jnp.asarray(targets, jnp.int32), plan.k, dtype=jnp.float32))
    folded = fold_affine(plan, params)

    if impl == "xla":
        finals = jax.vmap(
            lambda wbs, *rows: _descend_rows_xla(plan, cfg, wbs, *rows)
        )(folded, x, lo, hi, ulo, uhi, us, tsel)
    elif impl == "pallas":
        finals = _descend_pallas(plan, cfg, folded, x, lo, hi, ulo, uhi, us,
                                 tsel, resolve_interpret(interpret))
    else:
        raise ValueError(f"unknown descend impl {impl!r}")
    return finals.reshape(G, R, S, D)
