"""The unified probe-executor plane (DESIGN.md §10).

Every MOGD device dispatch in the system goes through one
:class:`ProbeExecutor`.  Compiled programs are keyed by **structure** —
the surrogate program's content token (model-family pytree treedef /
shapes), the encoder's snap structure, the objective count, the
:class:`~repro.core.mogd.MOGDConfig`, and the padded batch bucket — while
everything problem-specific rides through the jitted program as batched
pytree **data**: model parameters (MLP weights, GP factors, stage theta),
the per-cell constraint boxes, user value bounds, per-objective
uncertainty weights, and the target-objective index.

Consequences (the reason this module exists):

* Probe cells from tenants with *different* workloads but a shared model
  architecture batch into ONE dispatch — the compiled program is the
  same, only the per-box params differ.
* A model-server promotion (new weights, same architecture) is a pure
  params swap: the warm re-solve reuses the already-compiled program
  with zero recompilation.
* The mesh path is default-on (``mesh="auto"``): with more than one
  device the probe batch axis is sharded with ``shard_map`` over a 1-D
  mesh, the axis (groups vs rows) and device-divisible bucket sizes
  chosen by ``repro.distributed.sharding.choose_probe_partition`` from
  the tenant mix.  Single devices — and buckets a mesh cannot divide —
  fall back to the unsharded program; never fail.
* A ``backend`` seam routes fusable programs (stacked standardizing-MLP
  surrogates — the paper's workload models) through the fused Pallas
  descend kernel (``repro.kernels.mogd_descend``), parity-gated per
  structure against the ``lax.scan`` path; GP/closure/uncertainty
  programs keep the scan path.  Zero caller API change.

The module is dependency-light by design: it imports only jax/numpy, so
``repro.core.mogd``, ``repro.core.dag``, ``repro.models`` and
``repro.service`` can all build on it without cycles.  The Eq. 4 penalty
loss and the projected-Adam descent kernel live here (re-exported from
``repro.core.mogd`` for compatibility) because they ARE the dispatch
plane's compute body.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Every MOGD program the executor builds runs its matmuls in full f32.  On
# a TPU the default for f32 operands is one bf16 pass: over 80 projected-
# Adam steps that sends descents from the same start to points tenths
# apart (TPU v5e), so results would depend on the implementation and the
# fused kernel's parity gate could not pass.  The fused kernel sets the
# same precision on its own dots (``kernels.mogd_descend.HIGHEST``).
MATMUL_PRECISION = "highest"


def _f32_matmuls(fn: Callable) -> Callable:
    """``fn`` traced with every matmul at :data:`MATMUL_PRECISION` (the
    precision is fixed into each dot as it is traced)."""

    def traced(*args):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return fn(*args)

    return traced


# ---------------------------------------------------------------------------
# Math primitives (paper Eq. 4 + §4.2.1 projected descent).  Moved here from
# core/mogd.py so the executor owns the full compute body; core re-exports.
# ---------------------------------------------------------------------------


def _eq4_loss(
    f: Array, lo: Array, hi: Array, target: Array, penalty: float,
    tie_break_eps: float = 0.0,
) -> Array:
    """Paper Eq. 4 over one objective vector ``f: (k,)``.

    ``target`` is a *traced* index (one-hot selection) so a single jit
    serves every CO target — and, in the executor plane, every *box's*
    target rides as per-row data.
    """
    width = jnp.maximum(hi - lo, 1e-12)
    fhat = (f - lo) / width
    onehot = jax.nn.one_hot(target, f.shape[-1], dtype=fhat.dtype)
    ft = jnp.sum(fhat * onehot)
    inside_t = jnp.logical_and(ft >= 0.0, ft <= 1.0)
    target_term = jnp.where(inside_t, ft * ft, 0.0)
    violated = jnp.logical_or(fhat < 0.0, fhat > 1.0)
    viol_term = jnp.where(violated, (fhat - 0.5) ** 2 + penalty, 0.0).sum()
    tie_term = tie_break_eps * jnp.sum(
        jnp.where(violated, 0.0, jnp.clip(fhat, 0.0, 1.0) ** 2)
    )
    return target_term + viol_term + tie_term


def adam_project_descend(loss_fn: Callable, x0: Array, cfg) -> Array:
    """Multi-step Adam descent with cosine LR decay and projection onto
    ``[0,1]^D`` (§4.2.1), from one start.  ``cfg`` is a
    :class:`~repro.core.mogd.MOGDConfig` (duck-typed)."""
    grad_fn = jax.grad(loss_fn)

    def step(carry, _):
        x, m, v, t = carry
        g = grad_fn(x)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        m = cfg.adam_b1 * m + (1 - cfg.adam_b1) * g
        v = cfg.adam_b2 * v + (1 - cfg.adam_b2) * g * g
        mh = m / (1 - cfg.adam_b1 ** t)
        vh = v / (1 - cfg.adam_b2 ** t)
        frac = (t - 1.0) / cfg.steps
        lr = cfg.lr * (
            cfg.lr_floor
            + (1 - cfg.lr_floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        )
        x = x - lr * mh / (jnp.sqrt(vh) + cfg.adam_eps)
        # Projection: walk back to the boundary of [0,1]^D (§4.2.1).
        x = jnp.clip(x, 0.0, 1.0)
        return (x, m, v, t + 1.0), None

    z = jnp.zeros_like(x0)
    (x, _, _, _), _ = jax.lax.scan(
        step, (x0, z, z, jnp.float32(1.0)), None, length=cfg.steps
    )
    return x


# ---------------------------------------------------------------------------
# Bucketing policy — the single source of truth.  MOGDSolver, FamilySolver
# and the service coalescer all pad through here, so a PF session hits a
# handful of jit specializations instead of one per grid size.
# ---------------------------------------------------------------------------


def bucket(B: int, base: int = 1) -> int:
    """Smallest power-of-two-scaled bucket >= B (floor ``base``)."""
    b = base
    while b < B:
        b *= 2
    return b


def pad_rows(tree, n_pad: int, axis: int = 0):
    """Pad every array leaf's ``axis`` by replicating slice 0 ``n_pad``
    times.  Pad rows are real (duplicate) problems whose results are
    sliced off before anyone sees them — they can never enter a frontier.

    Padding runs host-side in numpy: done with jnp ops, every new
    (unpadded, padded) shape pair jit-builds its own slice/broadcast/
    concatenate kernels — under a serving plane the tenant mix shifts
    constantly, and those ~1s micro-build bursts stall the dispatcher.
    The padded batch crosses to the device once, at the program call."""
    if n_pad == 0:
        return tree

    def one(a):
        a = np.asarray(a)
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(0, 1)
        shape = list(a.shape)
        shape[axis] = n_pad
        return np.concatenate(
            [a, np.broadcast_to(a[tuple(idx)], shape)], axis=axis)

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# Programs: the (structure, params) split
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ParamProgram:
    """A surrogate objective program split into structure and data.

    ``apply(params, x) -> (k,)`` (or a scalar for single-objective
    building blocks) must be a pure function whose *behavior* is fully
    determined by ``structure``: the executor compiles one jitted program
    per structure token and routes every program with an equal token
    through it, feeding each call's ``params`` pytree as batched data.

    ``params`` is any pytree of arrays (stackable along a new leading
    axis).  ``apply_std`` optionally returns predictive standard
    deviations of the same shape (uncertainty-aware MOGD, §4.2.3).
    """

    apply: Callable
    params: Any
    structure: tuple
    apply_std: Callable | None = None
    # ``(params, host copy)``: the executor's numpy copy of ``params``
    # with a leading group axis, read back once on first use.  Keyed by
    # the identity of ``params``; ``dataclasses.replace`` starts a
    # derived program without one.
    _host_params: Any = dataclasses.field(
        default=None, init=False, repr=False, compare=False)


def closure_program(fn: Callable, token) -> ParamProgram:
    """Wrap an opaque objective closure as a program with empty params.

    The legacy path: each distinct model content is its own structure, so
    nothing coalesces across tenants — exactly the pre-executor behavior."""
    return ParamProgram(
        apply=lambda _p, x: fn(x), params=(), structure=("closure", token))


def orient_program(program: ParamProgram, signs) -> ParamProgram:
    """Flip max-objectives to minimized orientation (TaskSpec.compile).
    Predictive stds are direction-invariant and pass through unchanged."""
    signs = tuple(float(s) for s in np.asarray(signs).reshape(-1))
    if all(s == 1.0 for s in signs):
        return program
    sj = jnp.asarray(signs)
    inner = program.apply
    return dataclasses.replace(
        program,
        apply=lambda p, x: sj * inner(p, x),
        structure=("orient", signs, program.structure),
    )


def stack_programs(programs) -> ParamProgram:
    """k single-output programs -> one ``(k,)``-vector program — the Ψ a
    model-server snapshot exposes (one regressor per objective)."""
    programs = tuple(programs)
    applies = tuple(p.apply for p in programs)
    params = tuple(p.params for p in programs)
    structure = ("stack", tuple(p.structure for p in programs))

    def apply(ps, x):
        return jnp.stack([a(p, x) for a, p in zip(applies, ps)])

    apply_std = None
    if all(p.apply_std is not None for p in programs):
        stds = tuple(p.apply_std for p in programs)

        def apply_std(ps, x):
            return jnp.stack([s(p, x) for s, p in zip(stds, ps)])

    return ParamProgram(apply, params, structure, apply_std)


def encoder_structure(encoder) -> tuple:
    """The part of a :class:`~repro.core.problem.SpaceEncoder` that the
    compiled program's ``snap`` actually depends on: per-knob kind, encoded
    width, and the integer level count.  Two workloads with equal encoder
    structure trace identical snap computations."""
    out = []
    for s in encoder.specs:
        if s.kind == "integer":
            out.append(("integer", float(s.high - s.low)))
        elif s.kind == "categorical":
            out.append(("categorical", s.width))
        else:
            out.append((s.kind, 1))
    return tuple(out)


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------


# Per-row field count of the rows tuple `_materialize` builds
# (x0s, los, his, ulo, uhi, uscale, alphas, targets) — concatenation and
# the mesh row-shard in_specs both derive from this, so adding a field
# only requires touching `_materialize` and this constant.
N_ROW_FIELDS = 8


@dataclasses.dataclass
class ProbeRequest:
    """One caller's span of CO problems, everything-as-data.

    ``x0s: (B, S, D)`` multistart seeds; ``los``/``his: (B, k)`` the PF
    constraint boxes; ``targets: (B,)`` int32 target-objective indices.
    ``params_b`` optionally pre-batches per-box params (leading B — the
    stage-family theta path); None broadcasts ``program.params`` to every
    box.  ``bounds`` is ``(ulo, uhi, uscale)`` each ``(B, k)`` (None =
    open edges); ``alphas: (B, k)`` uncertainty weights (used only when
    ``use_std``)."""

    program: ParamProgram
    encoder: Any
    cfg: Any  # MOGDConfig (frozen dataclass — hashable)
    x0s: Any
    los: Any
    his: Any
    targets: Any
    params_b: Any = None
    bounds: Any = None
    alphas: Any = None
    use_std: bool = False


class _Moves:
    """Counts the arrays one dispatch moves between host and device
    (``exec.transfers`` / ``exec.transfer_bytes``): host data handed to
    the device, and device arrays read back to the host.  ``param_hits``
    and ``param_misses`` count the requests whose parameters came from
    their program's host copy, and those that had to make it."""

    __slots__ = ("count", "bytes", "param_hits", "param_misses")

    def __init__(self):
        self.count = 0
        self.bytes = 0
        self.param_hits = 0
        self.param_misses = 0

    def _add(self, a) -> None:
        # host data crosses at its canonical dtype (float64 as float32)
        self.count += 1
        self.bytes += int(np.size(a)) * jax.dtypes.canonicalize_dtype(
            a.dtype).itemsize

    def to_host(self, a, dtype=None) -> np.ndarray:
        """``a`` as host numpy at the dtype ``jnp.asarray(a, dtype)``
        would give it (float64 becomes float32 with x64 off); a device
        array is read back, and counted."""
        if isinstance(a, jax.Array):
            self._add(a)
        a = np.asarray(a, dtype)
        return a.astype(jax.dtypes.canonicalize_dtype(a.dtype), copy=False)

    def launch(self, args) -> None:
        """Count the host leaves a jitted call will copy to the device."""
        for a in jax.tree.leaves(args):
            if not isinstance(a, jax.Array):
                self._add(a)


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

_exec_ids = itertools.count()  # per-instance metric label suffix


class ProbeExecutor:
    """Structure-keyed compiler + dispatcher for batched MOGD probes.

    Batches are laid out as ``(G groups, R rows)``: params are per-GROUP
    data (one group per tenant span — rows inside a group share their
    model weights, so the surrogate forward stays a shared-weight
    matmul), rows are the individual CO cells.  A per-row-params caller
    (the stage-family theta path) simply contributes R=1 groups.

    One instance owns a cache of jitted ``solve`` programs keyed by
    ``(structure, k, S, D, G-bucket, R-bucket)`` plus compile-count
    telemetry per bucketless structure key (``compile_counts``).  The
    service exposes these counters in ``stats()``; benchmarks and CI
    gate on them.

    ``mesh="auto"`` (the default) builds a 1-D probe mesh over all local
    devices when there is more than one, else stays unsharded — callers
    never opt in.  An explicit :class:`jax.sharding.Mesh` pins the
    device set; ``mesh=None`` disables sharding.  The sharded batch axis
    (groups vs rows) and device-divisible bucket sizes come from the
    partitioning policy (``distributed.sharding.choose_probe_partition``)
    applied to the tenant mix; rows are independent, no collectives.
    Buckets a mesh cannot divide fall back to the plain program.

    ``backend`` selects the descend implementation: ``"auto"`` routes
    stacked-MLP structures through the fused Pallas/XLA kernel after a
    one-time per-structure parity check against the scan path (and
    everything else — GP, closures, ``use_std`` — through ``lax.scan``);
    ``"jnp"`` forces the scan path; ``"fused"`` requires a fusable
    structure and skips the parity gate (benchmarks, kernel tests).
    """

    def __init__(self, mesh="auto", mesh_axis: str | None = None,
                 bucket_fn: Callable[[int], int] = bucket,
                 max_programs: int = 512, backend: str = "auto",
                 obs=None):
        if isinstance(mesh, str):
            if mesh != "auto":
                raise ValueError(f"mesh must be 'auto', None or a Mesh, "
                                 f"got {mesh!r}")
            mesh = None
            if len(jax.devices()) > 1:
                from repro.distributed.sharding import probe_mesh

                mesh = probe_mesh()
        if backend not in ("auto", "jnp", "fused"):
            raise ValueError(f"backend must be auto|jnp|fused, got "
                             f"{backend!r}")
        self.backend = backend
        self.mesh = mesh
        self.mesh_axis = (
            mesh_axis if mesh_axis is not None
            else (mesh.axis_names[0] if mesh is not None else None))
        self.bucket_fn = bucket_fn
        # LRU bound on compiled programs: a stream of distinct closure
        # structures (one-shot tasks) must not pin XLA executables — and
        # their model closures — forever.  Evicted programs recompile on
        # next use; counters keep counting (they are the PR-5 telemetry).
        self.max_programs = max_programs
        self._programs: dict[tuple, Callable] = {}
        self._built_buckets: dict[tuple, set[tuple]] = {}
        self._evals: dict[tuple, Callable] = {}
        self._lock = threading.RLock()
        self.compile_counts: dict[tuple, int] = {}
        # structure key -> DescendPlan (fused backend) or None (scan path);
        # populated once per structure by _descend_plan's parity gate
        self._descend_plans: dict[tuple, Any] = {}
        # typed dispatch-plane telemetry (DESIGN.md §14): counters live
        # in the shared observability registry; the int attribute
        # surface below stays as read-only views.  Mutations still run
        # under the executor lock, so the numbers stay exact for shared
        # executors.
        from repro.obs import Observability

        self.obs = obs if obs is not None else Observability()
        m = self.obs.metrics
        self._labels = {"executor": f"ex{next(_exec_ids)}"}
        self._c_dispatches = m.counter(
            "exec.dispatches", self._labels, help="device dispatches")
        self._c_requests = m.counter(
            "exec.requests", self._labels,
            help="coalesced requests (tenant spans) dispatched")
        self._c_transfers = m.counter(
            "exec.transfers", self._labels,
            help="arrays moved between host and device by dispatches")
        self._c_transfer_bytes = m.counter(
            "exec.transfer_bytes", self._labels,
            help="bytes of the arrays exec.transfers counts")
        self._c_param_hits = m.counter(
            "exec.host_param_hits", self._labels,
            help="requests whose parameters came from the host copy")
        self._c_param_misses = m.counter(
            "exec.host_param_misses", self._labels,
            help="requests whose parameters were read back to the host")
        self._c_probes = m.counter(
            "exec.probes", self._labels, help="useful probe rows solved")
        self._c_fused_dispatches = m.counter(
            "exec.fused_dispatches", self._labels)
        self._c_fused_fallbacks = m.counter(
            "exec.fused_fallbacks", self._labels)
        self._c_sharded_dispatches = m.counter(
            "exec.sharded_dispatches", self._labels)
        self.last_shard_axis: str | None = None
        # batcher seam telemetry (DESIGN.md §12): how full the padded
        # (G, R) buckets actually run — the signal the frontdesk's
        # adaptive micro-batching window exists to maximize — plus a
        # per-origin dispatch count so serving-plane traffic is
        # distinguishable from direct solver calls.
        self._c_useful_rows = m.counter("exec.useful_rows", self._labels)
        self._c_padded_rows = m.counter("exec.padded_rows", self._labels)
        self.last_bucket: tuple | None = None
        self.last_fill: float = 1.0
        # devices the last dispatch's results live on: 1 unsharded, the
        # mesh size when the bucket was sharded
        self.last_devices: int = 0

    # legacy int counter surface: views over the registry ------------------
    @property
    def dispatches(self) -> int:
        return int(self._c_dispatches.value)

    @property
    def probes(self) -> int:
        return int(self._c_probes.value)

    @property
    def fused_dispatches(self) -> int:
        return int(self._c_fused_dispatches.value)

    @property
    def fused_fallbacks(self) -> int:
        return int(self._c_fused_fallbacks.value)

    @property
    def sharded_dispatches(self) -> int:
        return int(self._c_sharded_dispatches.value)

    @property
    def useful_rows(self) -> int:
        return int(self._c_useful_rows.value)

    @property
    def padded_rows(self) -> int:
        return int(self._c_padded_rows.value)

    @property
    def dispatch_origins(self) -> dict:
        """Per-origin dispatch counts, read from the labeled
        ``exec.dispatches_by_origin`` counters."""
        out = {}
        for inst in self.obs.metrics.instruments("exec.dispatches_by_origin"):
            if all(inst.labels.get(k) == v for k, v in self._labels.items()):
                out[inst.labels["origin"]] = int(inst.value)
        return out

    # -- telemetry ---------------------------------------------------------
    @property
    def structures_compiled(self) -> int:
        """Distinct (bucketless) structure keys ever compiled."""
        return len(self.compile_counts)

    @property
    def total_compiles(self) -> int:
        """Total solve-program jit builds (all structures, all buckets)."""
        return sum(self.compile_counts.values())

    def stats(self) -> dict:
        return {
            "structures": self.structures_compiled,
            "compiles": self.total_compiles,
            "dispatches": self.dispatches,
            "requests": int(self._c_requests.value),
            "transfers": int(self._c_transfers.value),
            "transfer_bytes": int(self._c_transfer_bytes.value),
            "host_param_hits": int(self._c_param_hits.value),
            "host_param_misses": int(self._c_param_misses.value),
            "probes": self.probes,
            "fused_structures": sum(
                1 for p in self._descend_plans.values() if p is not None),
            "fused_dispatches": self.fused_dispatches,
            "fused_fallbacks": self.fused_fallbacks,
            "sharded_dispatches": self.sharded_dispatches,
            "useful_rows": self.useful_rows,
            "padded_rows": self.padded_rows,
            "fill_ratio": (self.useful_rows / self.padded_rows
                           if self.padded_rows else 1.0),
            "last_bucket": self.last_bucket,
            "last_devices": self.last_devices,
            "dispatch_origins": dict(self.dispatch_origins),
        }

    # -- batcher seam ------------------------------------------------------
    def plan_buckets(self, G: int, R: int) -> tuple[int, int]:
        """The padded ``(G, R)`` bucket a dispatch of this size would run
        at (bucket policy + mesh divisibility; the per-structure reuse
        window is intentionally ignored — it needs the compiled history).

        This is the frontdesk batcher's fill target: holding arrivals
        until the pending group count reaches ``plan_buckets(G, R)[0]``
        fills the padded bucket instead of paying for replicated pad
        rows (DESIGN.md §12)."""
        want_g = self.bucket_fn(max(1, int(G)))
        R = max(1, int(R))
        want_r = self.bucket_fn(R) if R == 1 else max(4, self.bucket_fn(R))
        n = self._mesh_div()
        if n > 1:
            from repro.distributed.sharding import choose_probe_partition

            _, want_g, want_r = choose_probe_partition(n, want_g, want_r)
        return want_g, want_r

    # -- keys --------------------------------------------------------------
    def structure_key(self, program: ParamProgram, encoder, cfg,
                      use_std: bool = False) -> tuple:
        """The coalescing identity: requests with equal structure keys are
        solved by one compiled program (params ride as data).

        ``cfg.seed`` is host-only (it feeds each solver's own PRNG stream,
        never the trace), so it is normalized out — tenants differing only
        in seed still coalesce.  ``cfg.alpha`` stays: closure programs
        bake it into ``effective_objectives``."""
        if dataclasses.is_dataclass(cfg):
            cfg = dataclasses.replace(cfg, seed=0)
        return (program.structure, encoder_structure(encoder), cfg,
                bool(use_std))

    def _mesh_div(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.mesh_axis])

    def _choose_buckets(self, base_key: tuple, G: int, R: int) -> tuple:
        """(G, R) bucketing with reuse: prefer an already-built bucket
        pair within 4x total padded size of the wanted one over compiling
        a new program — a warm executor serves shrinking/growing batches
        (and post-promotion warm re-solves) with zero new builds.

        Multi-row groups floor the row bucket at 4 (the historical
        MOGDSolver floor: B in 2..4 share one program); single-row groups
        stay exact so the per-row-params (stage-family) path pays no
        padding.

        On a multi-device mesh the wanted buckets then pass through the
        partitioning policy (``choose_probe_partition``), which picks the
        sharded axis from the tenant mix and rounds that axis's bucket up
        to device-divisible.  Returns ``(Gp, Rp, axis)``."""
        want_g = self.bucket_fn(G)
        want_r = self.bucket_fn(R) if R == 1 else max(4, self.bucket_fn(R))
        n = self._mesh_div()
        if n > 1:
            from repro.distributed.sharding import choose_probe_partition

            _, want_g, want_r = choose_probe_partition(n, want_g, want_r)
        built = self._built_buckets.get(base_key, ())
        reuse = [
            (g, r) for (g, r) in built
            if g >= want_g and r >= want_r
            and g * r <= 4 * want_g * want_r
        ]
        Gp, Rp = (min(reuse, key=lambda t: t[0] * t[1]) if reuse
                  else (want_g, want_r))
        axis = None
        if n > 1:
            from repro.distributed.sharding import choose_probe_partition

            # the policy is idempotent on its own output, so the axis a
            # reused bucket was built with is re-derived, never stored
            axis, _, _ = choose_probe_partition(n, Gp, Rp)
            if (axis == "group" and Gp % n) or (axis == "row" and Rp % n):
                axis = None  # reused pre-policy bucket: unsharded fallback
        return Gp, Rp, axis

    # -- fused backend (kernels/mogd_descend) ------------------------------
    def _descend_plan(self, req: ProbeRequest, skey: tuple):
        """Resolve (and cache) the fused-backend plan for one structure.

        ``backend="auto"``: structural selection first (stacked
        standardizing-MLP programs only), then a one-time numeric parity
        gate against the scan path — a structure that fails either check
        falls back to ``lax.scan`` forever (``fused_fallbacks`` counts
        the numeric rejections).  ``backend="fused"`` skips the gate and
        raises on non-fusable structures."""
        if self.backend == "jnp":
            return None
        if skey in self._descend_plans:
            return self._descend_plans[skey]
        from repro.kernels.mogd_descend import plan_from_structure

        plan = plan_from_structure(req.program.structure,
                                   use_std=req.use_std)
        if plan is None:
            if self.backend == "fused":
                raise ValueError(
                    "backend='fused' requires a stacked-MLP program "
                    f"structure; got {req.program.structure[0]!r}")
        elif self.backend == "auto" and not self._parity_check(req, plan):
            self._c_fused_fallbacks.inc()
            plan = None
        self._descend_plans[skey] = plan
        return plan

    def _parity_check(self, req: ProbeRequest, plan) -> bool:
        """One-time per-structure numeric gate: fused descend must match
        the scan path's end state on a tiny slice of the real request
        before the structure commits to the fused backend.

        A numeric mismatch is the only reason to fall back.  A kernel
        that fails to lower or compile raises out of the executor: the
        scan path must never hide a broken fused tier."""
        from repro.kernels.mogd_descend import descend_batch

        cfg = req.cfg
        x0 = jnp.asarray(req.x0s, jnp.float32)[:1, :2]  # (1, S', D)
        lo = jnp.asarray(req.los, jnp.float32)[:1]
        hi = jnp.asarray(req.his, jnp.float32)[:1]
        k = lo.shape[-1]
        if req.bounds is not None:
            ulo, uhi, uscale = (jnp.asarray(b, jnp.float32)[:1]
                                for b in req.bounds)
        else:
            ulo = jnp.full((1, k), -jnp.inf)
            uhi = jnp.full((1, k), jnp.inf)
            uscale = jnp.ones((1, k))
        target = jnp.asarray(req.targets, jnp.int32).reshape(-1)[:1]
        if req.params_b is None:
            params = req.program.params
            params_g = jax.tree.map(lambda a: jnp.asarray(a)[None], params)
        else:
            params_g = jax.tree.map(lambda a: jnp.asarray(a)[:1],
                                    req.params_b)
            params = jax.tree.map(lambda a: a[0], params_g)

        apply = req.program.apply
        penalty, tie_eps = cfg.penalty, cfg.tie_break_eps

        def loss_fn(x):
            f = apply(params, x)
            excess = (jnp.maximum(ulo[0] - f, 0.0)
                      + jnp.maximum(f - uhi[0], 0.0))
            bound = jnp.where(
                excess > 0.0, (excess / uscale[0]) ** 2 + penalty, 0.0
            ).sum()
            return _eq4_loss(f, lo[0], hi[0], target[0], penalty,
                             tie_eps) + bound

        with jax.default_matmul_precision(MATMUL_PRECISION):
            want = jax.vmap(
                lambda x0_: adam_project_descend(loss_fn, x0_, cfg))(x0[0])
        got = descend_batch(
            plan, cfg, params_g, x0[:, None], lo[:, None], hi[:, None],
            ulo[:, None], uhi[:, None], uscale[:, None], target[:, None],
        )[0, 0]
        return bool(jnp.max(jnp.abs(got - want)) <= 1e-3)

    # -- compilation -------------------------------------------------------
    def _build(self, req: ProbeRequest, Gp: int, Rp: int, skey: tuple,
               axis: str | None, plan) -> Callable:
        """Compile the grouped descend-snap-select program for one
        structure at one (G, R) bucket pair.  Mirrors the pre-refactor
        MOGDSolver semantics exactly; user bounds always participate with
        ±inf open edges (``max(-inf - f, 0) == 0`` — a no-op for
        unbounded rows).  Params enter once per GROUP, so the surrogate
        forward inside each group keeps its shared-weight form.

        ``plan`` (a :class:`~repro.kernels.mogd_descend.DescendPlan`, or
        None) selects the descend body: the fused kernel computes the
        whole batch's finals in one call, the scan path descends inside
        the per-row vmap.  Snap/score/select are shared — the fused
        backend changes *where* the descent runs, never the semantics.
        ``axis`` is the partitioning policy's shard axis for this bucket.
        """
        apply = req.program.apply
        apply_std = req.program.apply_std
        use_std = req.use_std
        snap = req.encoder.snap
        cfg = req.cfg
        penalty, tie_eps, feas_tol = cfg.penalty, cfg.tie_break_eps, cfg.feas_tol

        def make_eff(params, alphas):
            if use_std:
                def eff(x):
                    return apply(params, x) + alphas * apply_std(params, x)
            else:
                def eff(x):
                    return apply(params, x)
            return eff

        def score_one(params, finals, lo, hi, ulo, uhi, uscale, alphas,
                      target):
            eff = make_eff(params, alphas)
            snapped = snap(finals)
            fvals = jax.vmap(eff)(snapped)  # (S, k)
            width = jnp.maximum(hi - lo, 1e-12)
            fhat = (fvals - lo) / width
            feas = jnp.all(
                jnp.logical_and(fhat >= -feas_tol, fhat <= 1.0 + feas_tol),
                axis=-1)
            tol = feas_tol * uscale
            feas = jnp.logical_and(feas, jnp.all(
                jnp.logical_and(fvals >= ulo - tol, fvals <= uhi + tol),
                axis=-1))
            onehot = jax.nn.one_hot(target, fvals.shape[-1],
                                    dtype=fvals.dtype)
            ft = jnp.sum(fvals * onehot, axis=-1)  # (S,)
            score = jnp.where(feas, ft, jnp.inf)
            best = jnp.argmin(score)
            return snapped[best], fvals[best], jnp.any(feas)

        def solve_one(params, x0_s, lo, hi, ulo, uhi, uscale, alphas, target):
            eff = make_eff(params, alphas)

            def bound_pen(f):
                # 0 at open (±inf) edges: max(-inf, 0) == 0
                excess = jnp.maximum(ulo - f, 0.0) + jnp.maximum(f - uhi, 0.0)
                return jnp.where(
                    excess > 0.0, (excess / uscale) ** 2 + penalty, 0.0
                ).sum()

            def loss_fn(x):
                f = eff(x)
                return _eq4_loss(f, lo, hi, target, penalty,
                                 tie_eps) + bound_pen(f)

            finals = jax.vmap(
                lambda x0: adam_project_descend(loss_fn, x0, cfg))(x0_s)
            return score_one(params, finals, lo, hi, ulo, uhi, uscale,
                             alphas, target)

        if plan is None:
            def solve_group(params, x0s, los, his, ulo, uhi, uscale, alphas,
                            targets):
                # rows of one group share params -> shared-weight forwards
                return jax.vmap(
                    lambda *rows: solve_one(params, *rows)
                )(x0s, los, his, ulo, uhi, uscale, alphas, targets)

            batched = jax.vmap(solve_group)
        else:
            from repro.kernels.mogd_descend import descend_batch

            def score_group(params, finals, los, his, ulo, uhi, uscale,
                            alphas, targets):
                return jax.vmap(
                    lambda *rows: score_one(params, *rows)
                )(finals, los, his, ulo, uhi, uscale, alphas, targets)

            def batched(params, x0s, los, his, ulo, uhi, uscale, alphas,
                        targets):
                # one fused descend over the whole (G, R, S) batch; the
                # shared snap/score stays in jnp (encoder logic is cheap
                # and runs once, not cfg.steps times)
                finals = descend_batch(plan, cfg, params, x0s, los, his,
                                       ulo, uhi, uscale, targets)
                return jax.vmap(score_group)(params, finals, los, his, ulo,
                                             uhi, uscale, alphas, targets)

        n = self._mesh_div()
        if n > 1:
            from jax.sharding import PartitionSpec as P

            if axis == "group" and Gp % n == 0:
                # shard the group axis: params and rows partition together
                spec = P(self.mesh_axis)
                batched = jax.shard_map(batched, mesh=self.mesh,
                                        in_specs=spec, out_specs=spec,
                                        check_vma=False)
            elif axis == "row" and Rp % n == 0:
                # groups replicated, rows sharded (params fully replicated)
                row_spec = P(None, self.mesh_axis)
                batched = jax.shard_map(
                    batched, mesh=self.mesh,
                    in_specs=(P(), *([row_spec] * N_ROW_FIELDS)),
                    out_specs=row_spec, check_vma=False)
            # else: indivisible bucket — unsharded fallback, never fail
        self.compile_counts[skey] = self.compile_counts.get(skey, 0) + 1
        return jax.jit(_f32_matmuls(batched))

    # -- assembly ----------------------------------------------------------
    @staticmethod
    def _host_copy(program: ParamProgram, moved: "_Moves"):
        """``program.params`` as host numpy with a leading group axis of
        one: read back on the program's first dispatch, then reused for
        as long as ``program.params`` is the same object."""
        cached = program._host_params
        if cached is not None and cached[0] is program.params:
            moved.param_hits += 1
            return cached[1]
        moved.param_misses += 1
        host = jax.tree.map(lambda a: moved.to_host(a)[None], program.params)
        program._host_params = (program.params, host)
        return host

    @classmethod
    def _materialize(cls, req: ProbeRequest, moved: "_Moves") -> tuple:
        """One request -> its group list ``(params, rows, n_groups,
        n_rows)``, all host numpy: the dispatch crosses to the device
        once, at the program call.

        A shared-params request is ONE group of B rows; a per-row-params
        request (stage-family thetas) is B groups of one row each."""
        to_host = moved.to_host
        x0s = to_host(req.x0s)
        B = int(x0s.shape[0])
        los = to_host(req.los)
        his = to_host(req.his)
        k = los.shape[-1]
        if req.bounds is not None:
            ulo, uhi, uscale = (to_host(b) for b in req.bounds)
        else:
            ulo = np.full((B, k), -np.inf, np.float32)
            uhi = np.full((B, k), np.inf, np.float32)
            uscale = np.ones((B, k), np.float32)
        alphas = (np.zeros((B, k), np.float32) if req.alphas is None
                  else to_host(req.alphas))
        targets = to_host(req.targets, np.int32).reshape(B)
        rows = (x0s, los, his, ulo, uhi, uscale, alphas, targets)
        if req.params_b is None:
            # one group: (1, ...) params, (1, B, ...) rows
            params = cls._host_copy(req.program, moved)
            return params, tuple(r[None] for r in rows), 1, B
        # per-row params: B groups of one row each
        params = jax.tree.map(to_host, req.params_b)
        return params, tuple(r[:, None] for r in rows), B, 1

    # -- dispatch ----------------------------------------------------------
    def solve_requests(self, requests, origin: str | None = None,
                       parent_span=None) -> tuple:
        """Concatenate the requests' spans into one padded (G, R) batch,
        solve in a single device dispatch, and slice results back per
        caller.

        Every request must carry the same structure key — that is the
        coalescing contract the service's grouping upholds.  Returns
        ``(x: (B, D), f: (B, k), feasible: (B,))`` numpy arrays over the
        concatenated (unpadded) spans, in request order.  ``origin``
        optionally tags the dispatch source (``"frontdesk"`` for the
        async admission plane) in ``dispatch_origins`` telemetry.
        ``parent_span`` parents the ``exec.dispatch`` span — the whole
        host path of the dispatch — under the caller's trace (DESIGN.md
        §14); its children are ``exec.materialize``, ``exec.compile``
        (bucket miss only), ``exec.pack``, ``exec.launch``, ``exec.wait``
        and ``exec.unpack``.
        """
        requests = list(requests)
        if not requests:
            raise ValueError("solve_requests needs at least one request")
        r0 = requests[0]
        skey = self.structure_key(r0.program, r0.encoder, r0.cfg, r0.use_std)
        for r in requests[1:]:
            other = self.structure_key(r.program, r.encoder, r.cfg, r.use_std)
            if other != skey:
                raise ValueError(
                    "solve_requests spans mix structure keys — group by "
                    "ProbeExecutor.structure_key before dispatching")
        with self.obs.tracer.span("exec.dispatch", cat="exec",
                                  parent=parent_span,
                                  args={"origin": origin,
                                        "requests": len(requests)}) as sp:
            return self._dispatch(requests, r0, skey, origin, sp)

    def _dispatch(self, requests, r0, skey, origin, sp) -> tuple:
        """The body of :meth:`solve_requests`, each phase a child of the
        dispatch span ``sp``; ``exec.wait`` blocks on the outputs only
        while tracing, so the untraced path enqueues and reads back as
        it always has."""
        moved = _Moves()
        with sp.child("exec.materialize", cat="exec"):
            parts = [self._materialize(r, moved) for r in requests]
        G = sum(p[2] for p in parts)
        R = max(p[3] for p in parts)
        S, D = parts[0][1][0].shape[-2:]
        k = parts[0][1][1].shape[-1]
        base_key = (skey, k, S, D)
        with self._lock:
            plan = self._descend_plan(r0, skey)
            Gp, Rp, axis = self._choose_buckets(base_key, G, R)
            key = (*base_key, Gp, Rp)
            fn = self._programs.pop(key, None)  # re-insert as newest (LRU)
            if fn is None:
                with sp.child("exec.compile", cat="exec",
                              args={"bucket": [Gp, Rp],
                                    "structure": str(skey)}):
                    fn = self._build(r0, Gp, Rp, skey, axis, plan)
                self._built_buckets.setdefault(base_key, set()).add((Gp, Rp))
            self._programs[key] = fn
            while len(self._programs) > self.max_programs:
                old = next(iter(self._programs))
                self._programs.pop(old)
                built = self._built_buckets.get(old[:-2])
                if built is not None:
                    built.discard(old[-2:])
        # pad each part's rows to Rp, concatenate groups, pad groups to
        # Gp — all host-side numpy (see pad_rows): no per-shape jit ops
        with sp.child("exec.pack", cat="exec"):
            params = jax.tree.map(
                lambda *ls: np.concatenate(ls, axis=0),
                *[p[0] for p in parts])
            rows = [
                np.concatenate(
                    [pad_rows(p[1][i], Rp - p[3], axis=1) for p in parts],
                    axis=0)
                for i in range(N_ROW_FIELDS)
            ]
            if Gp != G:
                params, rows = pad_rows((params, rows), Gp - G)
        with sp.child("exec.launch", cat="exec"):
            moved.launch((params, rows))
            x, f, feas = fn(params, *rows)
        if sp.enabled:
            with sp.child("exec.wait", cat="exec"):
                jax.block_until_ready((x, f, feas))
        # read the results back once, then slice them per request on the
        # host: group g contributes its first n_rows rows
        with sp.child("exec.unpack", cat="exec"):
            xh, fh, feash = (moved.to_host(a) for a in (x, f, feas))
            outs_x, outs_f, outs_feas = [], [], []
            g0 = 0
            for _, _, n_groups, n_rows in parts:
                span = slice(g0, g0 + n_groups), slice(0, n_rows)
                outs_x.append(xh[span].reshape(-1, xh.shape[-1]))
                outs_f.append(fh[span].reshape(-1, fh.shape[-1]))
                outs_feas.append(feash[span].reshape(-1))
                g0 += n_groups
            out = (np.concatenate(outs_x), np.concatenate(outs_f),
                   np.concatenate(outs_feas))
        useful = sum(p[2] * p[3] for p in parts)
        sp.set("bucket", [Gp, Rp])
        sp.set("fill", useful / (Gp * Rp))
        with self._lock:  # shared executors: keep telemetry exact
            self._c_dispatches.inc()
            self._c_requests.inc(len(requests))
            self._c_probes.inc(useful)
            self._c_useful_rows.inc(useful)
            self._c_padded_rows.inc(Gp * Rp)
            self._c_transfers.inc(moved.count)
            self._c_transfer_bytes.inc(moved.bytes)
            self._c_param_hits.inc(moved.param_hits)
            self._c_param_misses.inc(moved.param_misses)
            self.last_bucket = (Gp, Rp)
            self.last_fill = useful / (Gp * Rp)
            self.last_devices = len(x.sharding.device_set)
            if origin is not None:
                self.obs.metrics.counter(
                    "exec.dispatches_by_origin",
                    {**self._labels, "origin": origin}).inc()
            if plan is not None:
                self._c_fused_dispatches.inc()
            if axis is not None:
                self._c_sharded_dispatches.inc()
                self.last_shard_axis = axis
        return out

    # -- batched evaluation (bounds estimation, frontier re-seeding) -------
    def eval_batch(self, program: ParamProgram, X) -> Array:
        """``(N, D) -> (N, k)`` through the program split: one jitted
        vmapped forward per structure (params unbatched — they are shared
        across rows here), padded to the shared bucket grid so equal-
        architecture workloads reuse each other's traces."""
        X = jnp.asarray(X)
        N = X.shape[0]
        key = ("eval", program.structure)
        with self._lock:
            fn = self._evals.pop(key, None)  # re-insert as newest (LRU)
            if fn is None:
                apply = program.apply
                fn = jax.jit(
                    _f32_matmuls(jax.vmap(apply, in_axes=(None, 0))))
            self._evals[key] = fn
            while len(self._evals) > self.max_programs:
                self._evals.pop(next(iter(self._evals)))
        if N == 0:
            # pad_rows cannot replicate a row of an empty batch; evaluate
            # one dummy row and keep the empty slice (shape/dtype correct)
            Xp = jnp.zeros((1, *X.shape[1:]), X.dtype)
            return fn(program.params, Xp)[:0]
        Np = bucket(N)
        Xp = pad_rows(X, Np - N) if Np != N else X
        return fn(program.params, Xp)[:N]


# ---------------------------------------------------------------------------
# The process-default executor: solvers constructed outside a service (the
# baselines, solve_pf, grid_reference_solve) share one dispatch plane.
# ---------------------------------------------------------------------------

_DEFAULT: ProbeExecutor | None = None
_DEFAULT_LOCK = threading.Lock()


def default_executor() -> ProbeExecutor:
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = ProbeExecutor()
    return _DEFAULT
