"""Distribution layer tests.

Multi-device behaviour (sharding rules on a real mesh, int8 compressed
all-reduce under shard_map, sharded-vs-single-device train-step parity)
runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps seeing 1 device.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import ShardingRules, logical_spec


class TestLogicalSpec:
    def _rules(self):
        mesh = jax.make_mesh((1,), ("data",))

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}

        return ShardingRules(FakeMesh())

    def test_divisible_dims_shard(self):
        r = self._rules()
        assert logical_spec(r, ("d_model", "d_ff"), (1024, 4096)) == \
            P(None, "model")

    def test_indivisible_falls_back(self):
        r = self._rules()
        # 60 experts on a 16-wide axis => replicate
        assert logical_spec(r, ("expert", None, None), (60, 4, 4)) == \
            P(None, None, None)

    def test_axis_used_once(self):
        r = r2 = self._rules().with_overrides(
            d_model=("data",), d_model_out=("data",))
        spec = logical_spec(r2, ("d_model", "d_model_out"), (256, 256))
        assert spec == P("data", None)  # second use of data blocked

    def test_multi_axis_batch(self):
        class FakeMesh:
            axis_names = ("pod", "data", "model")
            shape = {"pod": 2, "data": 16, "model": 16}

        r = ShardingRules(FakeMesh())
        assert logical_spec(r, ("batch", None), (64, 7)) == \
            P(("pod", "data"), None)
        # batch=1 (long_500k): not divisible => replicated
        assert logical_spec(r, ("batch", None), (1, 7)) == P(None, None)


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType

    out = {}
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    # --- 1. compressed all-reduce under shard_map ---------------------
    from repro.distributed import compressed_psum
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    res = jnp.zeros((4, 64))

    def f(gl, rl):
        m, r = compressed_psum(gl[0], rl[0], "data")
        return m[None], r[None]

    spec = P("data", None)
    mean, resid = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(spec, spec),
        out_specs=(spec, spec), check_vma=False))(g, res)
    true_mean = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
    err = float(jnp.abs(mean - true_mean).max())
    scale = float(jnp.abs(g).max())
    out["psum_rel_err"] = err / scale
    # error feedback: residual equals what quantization dropped
    out["resid_norm"] = float(jnp.abs(resid).max())

    # --- 2. sharded train step == single-device train step ------------
    from repro.configs import get_smoke
    from repro.distributed import ShardingRules, named_sharding_tree
    from repro.nn import init_params
    from repro.training import AdamConfig, TrainStepConfig, adam_init, make_train_step

    cfg = get_smoke("qwen3-4b")
    params, axes = init_params(jax.random.PRNGKey(1), cfg)
    rules = ShardingRules(mesh)
    batch = {"tokens": jnp.arange(4 * 32, dtype=jnp.int32).reshape(4, 32)
             % cfg.vocab}
    adam = AdamConfig(lr=1e-2)
    opt = adam_init(params, adam)

    step_plain = jax.jit(make_train_step(cfg, TrainStepConfig(adam=adam)))
    p_ref, o_ref, m_ref = step_plain(params, opt, batch)

    p_sh = named_sharding_tree(rules, params, axes)
    params_s = jax.tree.map(jax.device_put, params, p_sh)
    bs = NamedSharding(mesh, P("data", None))
    batch_s = jax.tree.map(lambda a: jax.device_put(a, bs), batch)
    step_sh = jax.jit(make_train_step(cfg, TrainStepConfig(adam=adam),
                                      rules))
    p_s, o_s, m_s = step_sh(params_s, adam_init(params_s, adam), batch_s)
    out["loss_plain"] = float(m_ref["loss"])
    out["loss_sharded"] = float(m_s["loss"])
    dmax = max(float(jnp.abs(a - b).max())
               for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_s)))
    out["param_delta_max"] = dmax

    # --- 3. full production mesh smoke (8 devices stand in) ----------
    assert len(jax.devices()) == 8
    print("RESULT::" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def subprocess_results():
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=Path(__file__).resolve().parents[1])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT::")][0]
    return json.loads(line[len("RESULT::"):])


class TestMultiDevice:
    def test_compressed_psum_accuracy(self, subprocess_results):
        # int8 quantization: relative error bounded by ~1/127 per element
        assert subprocess_results["psum_rel_err"] < 0.03

    def test_error_feedback_nonzero(self, subprocess_results):
        assert subprocess_results["resid_norm"] > 0

    def test_sharded_training_parity(self, subprocess_results):
        r = subprocess_results
        assert abs(r["loss_plain"] - r["loss_sharded"]) < 5e-2
        assert r["param_delta_max"] < 5e-2


class TestQuantize:
    def test_roundtrip_small(self):
        from repro.distributed import dequantize_int8, quantize_int8

        x = np.linspace(-3, 3, 128).astype(np.float32)
        q, s = quantize_int8(x)
        rt = np.asarray(dequantize_int8(q, s))
        assert np.abs(rt - x).max() <= float(s) * 0.5 + 1e-6
