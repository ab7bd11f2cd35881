"""Ahead-of-time compiles of the served path's Pallas kernels for a TPU v5e.

Each test lowers a kernel at the widths the served path runs and compiles
it with the TPU compiler for one v5e chip that is described, not attached.
What Mosaic refuses — a block that breaks the (8, 128) tiling, an output
layout XLA disagrees with, an op it cannot lower, more VMEM than a kernel
may use — fails here at no chip time.  Nothing runs, so these tests say
nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mogd import MOGDConfig
from repro.kernels.compose import pairwise_compose_blocked
from repro.kernels.mogd_descend import DescendPlan, descend_batch
from repro.kernels.pareto_filter import cross_dominator_counts

D = 13  # spark_space(): 12 knobs, 13 encoded dims
HIDDEN = (128, 128, 128, 128)  # the paper's surrogate DNN


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("G,M", [(32, 128), (8, 1024)])
def test_descend_kernel_compiles(one_chip, G, M):
    k, S = 2, 8
    R = M // S
    dims = (D, *HIDDEN, 1)
    plan = DescendPlan((dims,) * k, (False,) * k, (1.0,) * k)
    cfg = MOGDConfig(steps=80, multistart=S)
    sds = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    params = tuple(
        {"layers": [{"w": sds(G, dims[i], dims[i + 1]),
                     "b": sds(G, dims[i + 1])}
                    for i in range(len(dims) - 1)],
         "x_mean": sds(G, D), "x_std": sds(G, D),
         "y_mean": sds(G, 1), "y_std": sds(G, 1)}
        for _ in range(k))
    rows = [sds(G, R, k) for _ in range(5)]
    text = _compiled_text(
        lambda p, *b: descend_batch(plan, cfg, p, *b, impl="pallas",
                                    interpret=False),
        params, sds(G, R, S, D), *rows, sds(G, R, dt=jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [2, 3])
def test_dominator_counts_compile(one_chip, k):
    sds = lambda n: jax.ShapeDtypeStruct((n, k), jnp.float32,
                                         sharding=one_chip)
    text = _compiled_text(
        lambda a, b: cross_dominator_counts(a, b, interpret=False),
        sds(4096), sds(1024))
    assert "tpu_custom_call" in text


def test_pairwise_compose_compiles(one_chip):
    k = 2
    sds = lambda n: jax.ShapeDtypeStruct((n, k), jnp.float32,
                                         sharding=one_chip)
    mask = np.array([True, False])  # series latency adds, cost maxes
    text = _compiled_text(
        lambda a, b: pairwise_compose_blocked(a, b, mask, interpret=False),
        sds(512), sds(512))
    assert "tpu_custom_call" in text
