"""Sharded serving parity: decode with the sequence-sharded KV cache on a
multi-device mesh must reproduce single-device logits (subprocess with 8
host devices; the main process keeps 1)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke
    from repro.distributed import ShardingRules, named_sharding_tree
    from repro.nn import decode_step, init_cache, init_params, prefill
    from repro.nn.blocks import blocks_cache_init
    from repro.nn.layers import split_tree

    cfg = get_smoke("mistral-nemo-12b")  # GQA kv=2 < model axis: fallback path
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    S, B = 24, 2
    toks = (jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) * 7) % cfg.vocab

    # --- single device reference -----------------------------------
    lg_ref, cache_ref = prefill(params, cfg, {"tokens": toks}, max_seq=S + 4)
    dl_ref, _ = decode_step(params, cfg, cache_ref,
                            {"tokens": toks[:, -1:]}, jnp.int32(S))

    # --- sharded: data=2 x model=4, cache seq-sharded over model ----
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = ShardingRules(mesh)
    p_sh = named_sharding_tree(rules, params, axes)
    params_s = jax.tree.map(jax.device_put, params, p_sh)
    bs = NamedSharding(mesh, P("data", None))
    toks_s = jax.device_put(toks, bs)

    pf = jax.jit(lambda p, b: prefill(p, cfg, b, rules, max_seq=S + 4))
    lg_s, cache_s = pf(params_s, {"tokens": toks_s})
    ds = jax.jit(lambda p, c, b, pos: decode_step(p, cfg, c, b, pos, rules))
    dl_s, _ = ds(params_s, cache_s, {"tokens": toks_s[:, -1:]}, jnp.int32(S))

    out = {
        "prefill_max_diff": float(jnp.abs(
            lg_ref.astype(jnp.float32) - lg_s.astype(jnp.float32)).max()),
        "decode_max_diff": float(jnp.abs(
            dl_ref.astype(jnp.float32) - dl_s.astype(jnp.float32)).max()),
        "logit_scale": float(jnp.abs(lg_ref.astype(jnp.float32)).max()),
    }
    print("RESULT::" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def results():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=Path(__file__).resolve().parents[1])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    return json.loads(line[0][len("RESULT::"):])


class TestShardedServingParity:
    def test_prefill_logits_match(self, results):
        assert results["prefill_max_diff"] <= 0.05 * max(
            results["logit_scale"], 1.0)

    def test_decode_logits_match(self, results):
        assert results["decode_max_diff"] <= 0.05 * max(
            results["logit_scale"], 1.0)
