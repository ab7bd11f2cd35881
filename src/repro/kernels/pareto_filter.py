"""Blocked O(n^2) Pareto domination count.

Alg. 1's final filter ("remove plan dominated by another plan") is an
all-pairs domination test; the PF trace and the baselines (NSGA-II's
non-dominated sort) hit it with tens of thousands of points.  The jnp
oracle materializes the full (N, N, k) comparison; this kernel tiles it
into (BJ, BI) VMEM blocks with an fp32 accumulator of dominator counts,
so peak memory is O(BI * BJ) and the inner compare is vectorized over the
8 x 128 VPU lanes.

Layout: candidates ride objective-major, ``(k, N)``, so each objective is
one lane-dense ``(1, BI)`` row; dominators ride row-major, ``(M, k)``, so
each objective is one ``(BJ, 1)`` column.  Their broadcast compare is a
``(BJ, BI)`` tile, and the count is a sublane reduction into the
lane-dense ``(1, BI)`` output block — the layout the TPU compiler accepts
(a 1-D output block does not match XLA's tiling of the ``(N,)`` result).

Grid is (N/BI, M/BJ); the j axis is the reduction axis (sequential on
TPU), accumulating into the output block — the standard Pallas
accumulate-across-grid pattern with an init at j == 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import resolve_interpret

BI = 128
BJ = 128


def _kernel(fi_ref, fj_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    fi = fi_ref[...]  # (k, BI)  candidates, objective-major
    fj = fj_ref[...]  # (BJ, k)  potential dominators
    le = lt = None
    for o in range(fi.shape[0]):
        a, b = fi[o:o + 1, :], fj[:, o:o + 1]  # (1, BI), (BJ, 1)
        le = b <= a if le is None else jnp.logical_and(le, b <= a)
        lt = b < a if lt is None else jnp.logical_or(lt, b < a)
    dom = jnp.logical_and(le, lt)  # (BJ, BI): fj dominates fi
    out_ref[...] += jnp.sum(dom.astype(jnp.float32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cross_dominator_counts(FA, FB, interpret: bool | None = None):
    """Cross-set domination: for each row of ``FA: (N, k)``, count rows of
    ``FB: (M, k)`` that Pareto-dominate it -> ``(N,)`` int32.

    This is the batched primitive behind the incremental frontier store
    (``repro.core.frontier_store``): one call scores a probe batch against
    the live frontier (and vice versa) without materializing the full
    (N, M, k) comparison in one buffer.  ``pareto_counts_blocked`` is the
    ``FA is FB`` special case.  Rows equal to ``+inf`` (padding / dead
    slots) dominate nothing and are reported as dominated — callers mask.
    ``interpret=None`` resolves through ``kernels.platform``.
    """
    N, k = FA.shape
    M = FB.shape[0]
    # empty boundary states (no candidates / empty dominator set): nothing
    # dominates, and Pallas cannot slice blocks out of zero-row operands
    if N == 0 or M == 0:
        return jnp.zeros((N,), jnp.int32)
    # pad with +inf so padded rows dominate nothing and are dominated
    pad_i = (-N) % BI
    if pad_i:
        FA = jnp.pad(FA, ((0, pad_i), (0, 0)), constant_values=jnp.inf)
    pad_j = (-M) % BJ
    if pad_j:
        FB = jnp.pad(FB, ((0, pad_j), (0, 0)), constant_values=jnp.inf)
    Np = FA.shape[0]
    grid = (Np // BI, FB.shape[0] // BJ)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, BI), lambda i, j: (0, i)),
            pl.BlockSpec((BJ, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BI), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(FA.T, FB)
    return out[0, :N].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pareto_counts_blocked(F, interpret: bool | None = None):
    """F: (N, k) fp32 -> (N,) int32 dominator counts (0 => Pareto)."""
    return cross_dominator_counts(F, F, interpret=interpret)
