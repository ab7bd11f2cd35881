"""The TPCx-BB batch deployment as data: workloads, knobs, ground truth.

A copy kept with the benchmark, so that a change to the program cannot move
the yardstick.  It follows the paper's evaluation (arXiv 2005.03314 §6):
258 batch workloads made from 30 templates (one scale factor each), the 12
most important Spark knobs (13 encoded dimensions: the serializer is a
two-way one-hot), and the latency and cost objectives.  The ground truth is
the analytic Spark-like cost model the repository's trace generator uses,
written here batched over workloads so that every tenant's traces come from
one program on the device.
"""

from __future__ import annotations

import numpy as np

# name, kind, low, high  (kind: int | cont | bool | cat2) in encoded order
KNOBS = (
    ("parallelism", "int", 8, 512),
    ("num_executors", "int", 2, 32),
    ("cores_per_executor", "int", 1, 8),
    ("mem_per_executor_gb", "int", 1, 32),
    ("memory_fraction", "cont", 0.2, 0.9),
    ("shuffle_compress", "bool", 0, 1),
    ("rdd_compress", "bool", 0, 1),
    ("serializer", "cat2", 0, 1),  # (java, kryo) one-hot
    ("shuffle_partitions", "int", 8, 512),
    ("broadcast_threshold_mb", "int", 1, 256),
    ("locality_wait_s", "cont", 0.0, 10.0),
    ("speculation", "bool", 0, 1),
)
DIM = 13

FIELDS = ("w_cpu", "w_serial", "w_shuffle_gb", "input_gb", "task_overhead_ms",
          "mem_need_gb", "kryo_gain", "compress_ratio", "compress_cpu", "skew")
_RANGES = ((200, 12000), (2, 40), (0.5, 200), (5, 100), (5, 60), (0.5, 6.0),
           (0.05, 0.25), (0.3, 0.8), (0.02, 0.15), (0.0, 0.5))
_SCALED = ("w_cpu", "w_serial", "w_shuffle_gb", "input_gb")

CORE_PRICE_PER_S = 0.000012
MEM_PRICE_PER_S = 0.0000015
NET_GBPS = 1.25
TRACE_NOISE = 0.08  # multiplicative log-normal run-to-run noise


def batch_suite(n: int = 258, seed: int = 7) -> dict:
    """Per-workload constants as ``{field: (n,) array}`` plus ``template``:
    template ``i % 30`` fixes the job's shape, one scale factor in [0.5, 2]
    per workload stretches its data volumes."""
    rng = np.random.default_rng(seed)
    cols = {f: np.empty(n) for f in FIELDS}
    template = np.empty(n, dtype=np.int64)
    for i in range(n):
        t = i % 30
        trng = np.random.default_rng(1000 + t)
        base = {f: float(trng.uniform(lo, hi))
                for f, (lo, hi) in zip(FIELDS, _RANGES)}
        scale = float(rng.uniform(0.5, 2.0))
        for f in _SCALED:
            base[f] *= scale
        for f in FIELDS:
            cols[f][i] = base[f]
        template[i] = t
    cols["template"] = template
    return cols


def snap(x):
    """Round a relaxed encoded point onto realizable knob values (jnp)."""
    import jax
    import jax.numpy as jnp

    parts, off = [], 0
    for _name, kind, lo, hi in KNOBS:
        if kind == "cat2":
            block = x[..., off:off + 2]
            parts.append(jax.nn.one_hot(jnp.argmax(block, axis=-1), 2,
                                        dtype=x.dtype))
            off += 2
            continue
        block = x[..., off:off + 1]
        if kind == "bool":
            parts.append(jnp.round(block))
        elif kind == "int":
            n = float(hi - lo)
            parts.append(jnp.round(block * n) / max(n, 1.0))
        else:
            parts.append(block)
        off += 1
    return jnp.concatenate(parts, axis=-1)


def _decode_soft(x):
    out, off = {}, 0
    for name, kind, lo, hi in KNOBS:
        if kind == "cat2":
            block = x[..., off:off + 2]
            out[name] = block / (block.sum(-1, keepdims=True) + 1e-9)
            off += 2
            continue
        v = x[..., off]
        out[name] = v if kind == "bool" else lo + v * (hi - lo)
        off += 1
    return out


def latency_cost(x, w: dict):
    """Ground-truth ``(latency_s, cost_usd)`` of configurations ``x: (..., 13)``
    for workloads whose constants ``w[field]`` broadcast against ``x[..., 0]``."""
    import jax
    import jax.numpy as jnp

    c = _decode_soft(x)
    execs, cores = c["num_executors"], c["cores_per_executor"]
    total_cores = execs * cores
    par = c["parallelism"]
    kryo = c["serializer"][..., 1]
    cpu_work = w["w_cpu"] * (1.0 - w["kryo_gain"] * kryo)
    cpu_work = cpu_work * (1.0 + w["compress_cpu"] * (
        c["shuffle_compress"] + 0.5 * c["rdd_compress"]))
    eff_par = jnp.minimum(par, total_cores * 4.0)
    util = jnp.clip(eff_par / total_cores, 0.0, 1.0)
    skew_penalty = 1.0 + w["skew"] / jnp.sqrt(eff_par)
    t_compute = cpu_work * skew_penalty / (
        total_cores ** 0.92 * (0.25 + 0.75 * util))
    vol = w["w_shuffle_gb"] * (
        1.0 - (1.0 - w["compress_ratio"]) * c["shuffle_compress"])
    bw = NET_GBPS * execs ** 0.85 * (1.0 + 0.03 * c["locality_wait_s"])
    t_shuffle = vol / bw + 0.4 * c["locality_wait_s"]
    mem_per_core = c["mem_per_executor_gb"] * c["memory_fraction"] / cores
    deficit = jax.nn.softplus((w["mem_need_gb"] - mem_per_core) * 2.0) / 2.0
    t_spill = (w["input_gb"] / total_cores) * deficit * 1.8
    n_tasks = jnp.maximum(par, c["shuffle_partitions"])
    t_sched = n_tasks * (w["task_overhead_ms"] / 1000.0) / jnp.maximum(
        execs, 1.0)
    spec_gain = 1.0 - 0.12 * w["skew"] * c["speculation"]
    t_sched = t_sched * (1.0 + 0.05 * c["speculation"])
    lat = (w["w_serial"] + t_compute + t_shuffle + t_spill + t_sched) * spec_gain
    mem = execs * c["mem_per_executor_gb"]
    cost = lat * (total_cores * CORE_PRICE_PER_S + mem * MEM_PRICE_PER_S) * 1e4
    return lat, cost


def make_traces(key, consts: dict, n_traces: int):
    """Seeded traces for every workload in one program: uniform random
    configurations snapped to realizable values, their ground-truth
    objectives, times log-normal noise.  Returns ``X: (W, n, 13)``,
    ``Y: (W, n, 2)`` as float32 device arrays."""
    import jax
    import jax.numpy as jnp

    w = {f: jnp.asarray(consts[f], jnp.float32)[:, None] for f in FIELDS}
    W = len(consts["w_cpu"])

    @jax.jit
    def build(key):
        kx, kn = jax.random.split(key)
        X = snap(jax.random.uniform(kx, (W, n_traces, DIM)))
        lat, cost = latency_cost(X, w)
        Y = jnp.stack([lat, cost], axis=-1)
        noise = jax.random.normal(kn, Y.shape) * TRACE_NOISE
        return X, Y * jnp.exp(noise)

    return build(key)
