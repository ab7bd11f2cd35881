"""Blocked all-pairs frontier composition (DAG stage composition, §8).

Composing two per-stage Pareto frontiers along a job DAG evaluates every
pair: ``C[i*M + j, o] = A[i, o] (+|max) B[j, o]`` — ``+`` for objectives
that accumulate over the edge (series latency, total cost), ``max`` for
parallel branches on the critical path.  The jnp oracle
(``kernels.ref.pairwise_compose``) materializes the full ``(N, M, k)``
broadcast in one buffer; this kernel tiles it into ``(k, BI, BJ)`` VMEM
blocks so peak memory is O(BI·BJ·k) while the N·M·k compose streams
through the 8×128 VPU lanes.  The composed tiles feed straight into the
incremental ``FrontierStore`` dominance pass (``kernels.pareto_filter``),
which is the Pareto re-filter of the composition pipeline.

Layout: the output is objective-major, ``(k, N, M)``, so every tile is a
lane-dense ``(BI, BJ)`` plane per objective — with k minor, each k-wide
row would pad to 128 lanes.  A's objective ``o`` is a ``(BI, 1)`` column
of the row-major ``(N, k)`` input, B's a ``(1, BJ)`` row of the
objective-major ``(k, M)`` input; their broadcast is the tile.  The
per-objective operator (add or max) is static: a DAG has a handful of
edge relations, each one compiled program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .platform import resolve_interpret

BI = 128
BJ = 128


def _make_kernel(add_ops: tuple):
    def kernel(fa_ref, fb_ref, out_ref):
        fa = fa_ref[...]  # (BI, k)
        fb = fb_ref[...]  # (k, BJ)
        for o, add in enumerate(add_ops):
            a, b = fa[:, o:o + 1], fb[o:o + 1, :]
            out_ref[o] = a + b if add else jnp.maximum(a, b)

    return kernel


@functools.partial(jax.jit, static_argnames=("add_ops", "interpret"))
def _compose_padded(FA, FB, add_ops: tuple, interpret: bool):
    grid = (FA.shape[0] // BI, FB.shape[0] // BJ)
    k = FA.shape[1]
    return pl.pallas_call(
        _make_kernel(add_ops),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BI, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, BJ), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((k, BI, BJ), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (k, FA.shape[0], FB.shape[0]), jnp.float32),
        interpret=interpret,
    )(FA, FB.T)


def pairwise_compose_blocked(FA, FB, add_mask, interpret: bool | None = None):
    """``FA: (N, k)``, ``FB: (M, k)``, ``add_mask: (k,)`` bool ->
    ``(N*M, k)`` fp32 in the oracle's row-major order (row ``i*M + j``).

    Inputs are padded to block multiples with ``+inf`` (``inf + x`` and
    ``max(inf, x)`` are both ``inf``, so padding rows compose to ``+inf``
    and can never enter a frontier); padding is sliced off before the
    row-major flatten, so output order matches ``ref.pairwise_compose``
    exactly.  ``add_mask`` must be concrete (it selects the compiled
    program); ``interpret=None`` resolves through ``kernels.platform``.
    """
    FA = jnp.asarray(FA, jnp.float32)
    FB = jnp.asarray(FB, jnp.float32)
    N, k = FA.shape
    M = FB.shape[0]
    if N == 0 or M == 0:
        return jnp.zeros((0, k), jnp.float32)
    pad_i = (-N) % BI
    if pad_i:
        FA = jnp.pad(FA, ((0, pad_i), (0, 0)), constant_values=jnp.inf)
    pad_j = (-M) % BJ
    if pad_j:
        FB = jnp.pad(FB, ((0, pad_j), (0, 0)), constant_values=jnp.inf)
    add_ops = tuple(bool(a) for a in np.asarray(add_mask).reshape(k))
    out = _compose_padded(FA, FB, add_ops, resolve_interpret(interpret))
    return out[:, :N, :M].transpose(1, 2, 0).reshape(N * M, k)
