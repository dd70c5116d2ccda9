"""Train step: loss -> grad -> (WRHT) gradient sync -> AdamW.

Gradient-sync modes (``TrainConfig.sync_algorithm``):

  auto          pure GSPMD: batch sharded over ('pod','data'); XLA inserts
                the gradient all-reduce.  Baseline, FSDP-compatible.
  psum|ring|rd|bt|wrht
                the step body runs inside shard_map, *manual* over the DP
                axes ('model' stays auto/GSPMD for TP): gradients are synced
                explicitly by repro.core.collectives, per size-capped bucket.
                With multiple DP axes the chosen algorithm runs per level
                innermost->outermost — exactly the paper's hierarchical-group
                structure with pods as top-level WRHT groups.
  hier_faithful | hier_scatter
                the mesh-factorized WRHT port (full-vector psum per level /
                reduce-scatter down + all-gather up).
  planned       per-bucket α–β planner choice (core.planner), the Lemma-1
                machinery deciding flat vs tree vs hierarchical per size;
                every bucket is planned once at setup via the amortized
                ``planner.plan_buckets`` batch API (DESIGN.md §10) and each
                traced step dispatches from the precomputed plan.
  planned_sharded
                ZeRO-style sharded sync (DESIGN.md §11): each bucket runs a
                planned reduce-scatter down the DP axes then a planned
                all-gather back up — between the phases every device holds
                only its owned shard, so the bytes moved are the
                bandwidth-optimal 2·(S-1)/S·d instead of the monolithic
                all-reduce's per-step full vector.  Both phases are planned
                per bucket through ``planner.plan_buckets(collective=...)``
                (ring pass vs the single-step all-to-all finisher).
  planned_pipelined
                planned_sharded with the bucket loop software-pipelined
                (DESIGN.md §13): bucket k+1's reduce-scatter is issued
                before bucket k's all-gather is drained
                (``bucketing.bucketed_apply_pipelined``), so the two ride
                one composed ring schedule (``core.compose``) — the planner
                costs the interleaving via ``plan_buckets(depth=...)`` and
                the RS+AG pair fuses onto disjoint wavelengths.  Per-bucket
                numerics are identical to planned_sharded.
  planned_compressed | planned_sharded_compressed
                the planned / planned_sharded sync with bits-per-element as
                a plan axis (DESIGN.md §15): at setup each bucket is swept
                over ``compress_bits`` wire widths and the cheapest wins —
                small latency-bound buckets *decline* compression because
                the quantize overhead exceeds the β saving.  Compressed
                buckets run int8/int4 symmetric quantization with per-block
                scales and error feedback (the residual rides in the train
                state and is checkpointed); the planned collective reduces
                the dequantized values, so convergence follows the EF-SGD
                guarantee.  The chosen widths are frozen per run — an
                online re-plan (SyncController) swaps strategies only,
                never widths, preserving the zero-retrace property.

``compress_pod_axis`` swaps the pod level for int8+error-feedback recursive
doubling (cross-pod links are the scarce resource at 512+ chips).
"""

from __future__ import annotations

import math
import time
from collections import OrderedDict
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.core import bucketing, compression, planner
from repro.core import collectives as C
from repro.models import api as mapi
from repro.optim import adamw_init, adamw_update, make_lr_schedule
from repro.parallel.sharding import param_partition_specs

MANUAL_ALGOS = ("psum", "ring", "rd", "bt", "wrht", "hier_faithful",
                "hier_scatter", "planned", "planned_sharded",
                "planned_pipelined", "planned_compressed",
                "planned_sharded_compressed")

# modes that plan per-(axis, bucket) RS/AG schedules at setup and support
# the no-retrace online re-plan path (SyncController)
SHARDED_ALGOS = ("planned_sharded", "planned_pipelined",
                 "planned_sharded_compressed")

# modes that carry EF residual state and quantize each bucket to the
# planner-chosen wire width before its collective (DESIGN.md §15)
COMPRESSED_ALGOS = ("planned_compressed", "planned_sharded_compressed")


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("data", "pod") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

def make_train_state(cfg: ModelConfig, tc: TrainConfig, key) -> dict:
    api = mapi.get_api(cfg, compute_dtype=_dtype(tc.compute_dtype), remat=tc.remat)
    params = api.init(key, _dtype(tc.param_dtype))
    state = {
        "params": params,
        "opt": adamw_init(params, _dtype(tc.opt_state_dtype)),
        "step": jnp.zeros((), jnp.int32),
    }
    if tc.compress_pod_axis or tc.sync_algorithm in COMPRESSED_ALGOS:
        state["ef"] = compression.init_ef_state(params)
    return state


def abstract_train_state(cfg: ModelConfig, tc: TrainConfig):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda k: make_train_state(cfg, tc, k), key)


def _on_mesh(spec: P, axis_names) -> P:
    """``spec`` with every mesh axis that ``axis_names`` lacks dropped."""
    def keep(entry):
        if isinstance(entry, tuple):
            return tuple(a for a in entry if a in axis_names) or None
        return entry if entry in axis_names else None

    return P(*(keep(e) for e in spec))


def train_state_specs(state, mesh, fsdp: bool = False) -> dict:
    """PartitionSpecs of the train state on ``mesh``: params, optimizer
    moments and EF residual by the partition rules (TP over 'model', ZeRO
    over every DP axis when ``fsdp``), counters replicated.  Axes the mesh
    lacks are dropped, so on a DP-only mesh the state is replicated."""
    pspecs = param_partition_specs(state["params"],
                                   dp_axes_of(mesh) if fsdp else None)
    pspecs = jax.tree.map(lambda s: _on_mesh(s, mesh.axis_names), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    specs = {"params": pspecs,
             "opt": {"m": pspecs, "v": pspecs, "count": P()},
             "step": P()}
    if "ef" in state:
        specs["ef"] = pspecs
    return specs


# ---------------------------------------------------------------------------
# gradient sync (explicit modes)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GradSyncPlans:
    """Setup-time product of the amortized planner (DESIGN.md §10): the
    gradient bucket partition plus one schedule choice per (DP axis,
    bucket).  For ``"planned_sharded"`` the monolithic per-axis plan is
    replaced by a reduce-scatter plan and an all-gather plan per (axis,
    bucket) (DESIGN.md §11).

    ``bits`` (the compressed modes, DESIGN.md §15) is the per-bucket wire
    width the planner's compression sweep picked at setup — 32 on buckets
    that declined.  It is frozen for the run: :meth:`SyncController.replan`
    re-plans *strategies* under the frozen widths so the traced step's
    quantization graph never changes (no retrace)."""

    spec: bucketing.BucketSpec
    plans: dict[str, tuple[planner.Plan, ...]]   # DP axis -> per-bucket plan
    rs_plans: dict[str, tuple[planner.Plan, ...]] | None = None
    ag_plans: dict[str, tuple[planner.Plan, ...]] | None = None
    bits: tuple[int, ...] | None = None          # per-bucket wire width


def _plan_axis_with_bits(size, bucket_bytes, bits, cost, backend, failures,
                         collective: str = "allreduce", depth: int = 1):
    """Plan one DP axis's buckets at *fixed* per-bucket wire widths by
    grouping buckets of equal width into one batched planner call each —
    the frozen-bits path of a compressed re-plan (widths never re-swept)."""
    out: list = [None] * len(bucket_bytes)
    groups: dict[int, list[int]] = {}
    for i, w in enumerate(bits):
        groups.setdefault(int(w), []).append(i)
    for w, idx in groups.items():
        sub = planner.plan_buckets(
            size, [bucket_bytes[i] for i in idx], cost, backend=backend,
            collective=collective, failures=failures, depth=depth, bits=w)
        for i, pl in zip(idx, sub):
            out[i] = pl
    return tuple(out)


def plan_gradient_sync(grads, tc: TrainConfig, mesh,
                       cost: planner.CostParams | None = None,
                       backend: str = "analytic",
                       sharded: bool = False,
                       failures=None,
                       depth: int = 1,
                       compress: bool = False,
                       bits_overrides=None) -> GradSyncPlans:
    """Partition the gradient pytree into size-capped buckets and plan every
    bucket's schedule for every DP axis in one batched planner call.

    ``grads`` may be abstract (``jax.ShapeDtypeStruct`` leaves) — only
    shapes/dtypes are read, so ``make_train_step`` runs this once at setup
    instead of re-planning inside every trace.  Bucket bytes are counted in
    the wire dtype (``tc.sync_dtype``), matching what each collective
    actually moves.

    ``sharded=True`` plans the ``"planned_sharded"`` mode: per (DP axis,
    bucket), a ``reduce_scatter`` plan for the way down and an
    ``all_gather`` plan for the way back up (DESIGN.md §11) — the
    all-gather sees the shard left by every axis *inside* it, so its byte
    count shrinks by the already-scattered factors, exactly what
    ``_sharded_sync_axes`` executes.

    ``failures`` re-plans every (axis, bucket) choice against a degraded
    ring (:class:`~repro.core.topology.FailureMask`, DESIGN.md §12) — the
    online re-plan path (:class:`SyncController`) calls back in here with
    the mask the watchdog/injector reported.

    ``depth > 1`` (``"planned_pipelined"``) costs each reduce-scatter plan
    against its composed RS+AG interleaving (``core.compose``, DESIGN.md
    §13): winning buckets carry ``detail["pipeline"]`` with the measured
    composed-vs-serial gain, and their ``cost_s`` is the amortized
    per-constituent share of the composed total.

    ``compress=True`` (the ``*_compressed`` modes, DESIGN.md §15) sweeps
    each bucket over ``tc.compress_bits`` wire widths on the *first* DP
    axis (the outermost sync level, which moves the most bytes), freezes
    the winning width per bucket — ``GradSyncPlans.bits`` — and plans every
    remaining axis/phase at those fixed widths, since a bucket is quantized
    once before its first collective and stays compressed on the wire
    through all levels.  ``bits_overrides`` skips the sweep and plans at
    the given per-bucket widths — the re-plan path, which must keep the
    widths the traced step was compiled with.
    """
    spec = bucketing.plan_buckets(grads, tc.bucket_bytes)
    itemsize = jnp.dtype(_dtype(tc.sync_dtype)).itemsize
    bucket_bytes = [s * itemsize for s in spec.bucket_sizes]
    axes = dp_axes_of(mesh)
    bits = tuple(int(w) for w in bits_overrides) if bits_overrides else None
    if not sharded:
        if not compress and bits is None:
            plans = {
                ax: tuple(planner.plan_buckets(mesh.shape[ax], bucket_bytes,
                                               cost, backend=backend,
                                               failures=failures))
                for ax in axes
            }
            return GradSyncPlans(spec, plans)
        plans = {}
        for ax in axes:
            if bits is None:
                swept = planner.plan_buckets(
                    mesh.shape[ax], bucket_bytes, cost, backend=backend,
                    failures=failures,
                    bits_candidates=tuple(tc.compress_bits))
                bits = tuple(int(p.detail.get("bits", 32)) for p in swept)
                plans[ax] = tuple(swept)
            else:
                plans[ax] = _plan_axis_with_bits(
                    mesh.shape[ax], bucket_bytes, bits, cost, backend,
                    failures)
        return GradSyncPlans(spec, plans, bits=bits)
    rs_plans, ag_plans = {}, {}
    shard_bytes = list(bucket_bytes)
    for ax in axes:
        size = mesh.shape[ax]
        if compress and bits is None:
            swept = planner.plan_buckets(
                size, shard_bytes, cost, backend=backend,
                collective="reduce_scatter", failures=failures, depth=depth,
                bits_candidates=tuple(tc.compress_bits))
            bits = tuple(int(p.detail.get("bits", 32)) for p in swept)
            rs_plans[ax] = tuple(swept)
        elif bits is not None:
            rs_plans[ax] = _plan_axis_with_bits(
                size, shard_bytes, bits, cost, backend, failures,
                collective="reduce_scatter", depth=depth)
        else:
            rs_plans[ax] = tuple(planner.plan_buckets(
                size, shard_bytes, cost, backend=backend,
                collective="reduce_scatter", failures=failures, depth=depth))
        if bits is not None:
            ag_plans[ax] = _plan_axis_with_bits(
                size, shard_bytes, bits, cost, backend, failures,
                collective="all_gather")
        else:
            ag_plans[ax] = tuple(planner.plan_buckets(
                size, shard_bytes, cost, backend=backend,
                collective="all_gather", failures=failures))
        shard_bytes = [b / size for b in shard_bytes]
    return GradSyncPlans(spec, {}, rs_plans=rs_plans, ag_plans=ag_plans,
                         bits=bits)


def _dispatch_planned(flat, axis, size, plan: planner.Plan):
    """Run one bucket's planned schedule on one DP axis."""
    if plan.strategy == "flat":
        return lax.psum(flat, axis)
    if plan.strategy == "rd":
        return C.allreduce_rd(flat, axis, size)
    if plan.strategy == "wrht_tree":
        return C.allreduce_wrht_tree(
            flat, axis, size, m=plan.m,
            alltoall_max=plan.m if plan.alltoall else None)
    # hier_scatter on one axis == ring reduce-scatter + all-gather
    return C.allreduce_ring(flat, axis, size)


def _dispatch_rs(flat, axis, size, plan: planner.Plan):
    """One bucket's planned reduce-scatter on one DP axis (DESIGN.md §11)."""
    if size == 1:
        return flat
    if plan.strategy == "alltoall":
        return C.reduce_scatter_alltoall(flat, axis, size)
    return C.reduce_scatter_ring(flat, axis, size)


def _dispatch_ag(shard, axis, size, plan: planner.Plan):
    """One bucket's planned all-gather on one DP axis (DESIGN.md §11)."""
    if size == 1:
        return shard
    if plan.strategy == "alltoall":
        return C.all_gather_alltoall(shard, axis, size)
    return C.all_gather_ring(shard, axis, size)


# ---------------------------------------------------------------------------
# online re-plan (DESIGN.md §12): traced strategy codes + SyncController
# ---------------------------------------------------------------------------

# the planned_sharded strategy menu per (axis, bucket, phase) is exactly
# {ring pass, single-step all-to-all}; encoding the choice as a traced int32
# makes the jitted step a *function of the plan*, so a mid-run re-plan swaps
# schedules by feeding new arrays — never by retracing
STRAT_RING = 0
STRAT_ALLTOALL = 1


def _plan_code(plan: planner.Plan) -> int:
    return STRAT_ALLTOALL if plan.strategy == "alltoall" else STRAT_RING


def _dispatch_rs_dyn(flat, axis, size, code):
    """Traced-code twin of :func:`_dispatch_rs` — both branches are traced
    once, the running plan picks at execution time.  The code array is
    replicated across devices, so every device takes the same branch."""
    if size == 1:
        return flat
    return lax.cond(code == STRAT_ALLTOALL,
                    lambda x: C.reduce_scatter_alltoall(x, axis, size),
                    lambda x: C.reduce_scatter_ring(x, axis, size),
                    flat)


def _dispatch_ag_dyn(shard, axis, size, code):
    """Traced-code twin of :func:`_dispatch_ag`."""
    if size == 1:
        return shard
    return lax.cond(code == STRAT_ALLTOALL,
                    lambda x: C.all_gather_alltoall(x, axis, size),
                    lambda x: C.all_gather_ring(x, axis, size),
                    shard)


def _sharded_rs_axes(flat, axes, sizes, plans: GradSyncPlans, i,
                     codes=None):
    """The way down of the sharded sync (DESIGN.md §11): reduce-scatter
    bucket ``i`` over every DP axis, innermost first.  Returns the owned
    shard plus the pre-scatter lengths the all-gather needs to slice
    padding back off."""
    lengths = []
    for ax in axes:
        lengths.append(flat.shape[0])
        if codes is not None:
            flat = _dispatch_rs_dyn(flat, ax, sizes[ax], codes[f"rs:{ax}"][i])
        else:
            flat = _dispatch_rs(flat, ax, sizes[ax], plans.rs_plans[ax][i])
    return flat, lengths


def _sharded_ag_axes(flat, lengths, axes, sizes, plans: GradSyncPlans, i,
                     codes=None):
    """The way back up: all-gather bucket ``i``'s shard over the DP axes in
    reverse, slicing each level back to the length it scattered (the ring
    bodies pad internally)."""
    for ax, length in zip(reversed(axes), reversed(lengths)):
        if codes is not None:
            flat = _dispatch_ag_dyn(flat, ax, sizes[ax], codes[f"ag:{ax}"][i])
        else:
            flat = _dispatch_ag(flat, ax, sizes[ax], plans.ag_plans[ax][i])
        flat = flat[:length]
    return flat


def _sharded_sync_axes(flat, axes, sizes, plans: GradSyncPlans, i,
                       codes=None):
    """RS down the DP axes, AG back up: between the phases every device
    holds only its owned shard of the bucket (ZeRO-style, DESIGN.md §11).

    ``codes`` (the :meth:`SyncController.arrays` pytree) switches bucket
    dispatch to the traced strategy codes — the no-retrace re-plan path.

    ``"planned_pipelined"`` runs the same two halves but staggered across
    buckets (:func:`bucketing.bucketed_apply_pipelined`), so per-bucket
    numerics are identical between the two modes."""
    flat, lengths = _sharded_rs_axes(flat, axes, sizes, plans, i, codes=codes)
    return _sharded_ag_axes(flat, lengths, axes, sizes, plans, i, codes=codes)


class SyncController:
    """Online re-planner for the ``planned_sharded`` / ``planned_pipelined``
    gradient sync (DESIGN.md §12).

    Owns the current :class:`GradSyncPlans` and publishes it as a pytree of
    replicated int32 *strategy-code* arrays (one per DP axis and phase,
    indexed by bucket).  The jitted train step takes that pytree as a traced
    argument, so :meth:`replan` — invoked by the trainer when the watchdog
    or injector reports a :class:`~repro.core.topology.FailureMask` — swaps
    every (axis, bucket) schedule by re-running the planner under the mask
    and feeding the new arrays into the *already-compiled* step.  No
    retrace: the arrays' shapes and dtypes never change.

    ``last_replan_s`` records the wall-clock planner latency of the most
    recent re-plan (what ``benchmarks/bench_degraded.py`` reports).

    Plans are memoized per mask fingerprint (a small LRU over
    :class:`GradSyncPlans`), so a *recovery* replan — the fault-management
    loop shrinking the mask back toward healthy (DESIGN.md §14) — reuses
    the already-computed plan instead of re-running the planner:
    ``last_replan_cached`` reports whether the most recent :meth:`replan`
    was such a hit.
    """

    MEMO_CAP = 8

    def __init__(self, abstract_grads, tc: TrainConfig, mesh,
                 cost: planner.CostParams | None = None,
                 backend: str = "analytic") -> None:
        self._grads = abstract_grads
        self._tc = tc
        self._mesh = mesh
        self._cost = cost
        self._backend = backend
        # planned_pipelined plans each bucket against its composed RS+AG
        # interleaving (DESIGN.md §13); planned_sharded costs serially
        self.depth = (tc.pipeline_depth
                      if tc.sync_algorithm == "planned_pipelined" else 1)
        # compressed mode: sweep per-bucket wire widths once here; every
        # re-plan below re-picks strategies at these *frozen* widths so the
        # compiled step's quantization graph is untouched (DESIGN.md §15)
        self.compress = tc.sync_algorithm in COMPRESSED_ALGOS
        self.failures = None
        self.last_replan_s: float | None = None
        self.last_replan_cached = False
        self.replan_count = 0
        self.plans = plan_gradient_sync(abstract_grads, tc, mesh, cost,
                                        backend, sharded=True,
                                        depth=self.depth,
                                        compress=self.compress)
        # seed the memo with the healthy plan: recovery back to the empty
        # mask is always a hit (DESIGN.md §14)
        self._plan_memo = OrderedDict({self._memo_key(None): self.plans})

    @staticmethod
    def _memo_key(failure_mask) -> str:
        return "healthy" if failure_mask is None else failure_mask.fingerprint()

    def arrays(self) -> dict:
        """The current plan as traced jit inputs: ``{"rs:<axis>"|"ag:<axis>"
        -> int32[n_buckets]}`` strategy codes, replicated across devices."""
        enc = {}
        for phase, plans in (("rs", self.plans.rs_plans),
                             ("ag", self.plans.ag_plans)):
            for ax in dp_axes_of(self._mesh):
                enc[f"{phase}:{ax}"] = jnp.asarray(
                    [_plan_code(p) for p in plans[ax]], jnp.int32)
        return enc

    def replan(self, failure_mask=None) -> dict:
        """Re-plan every (DP axis, bucket) schedule under ``failure_mask``
        (``None`` or an empty mask restores the healthy plan) and return the
        new strategy-code arrays.  Raises
        :class:`~repro.core.wrht.DegradedInfeasibleError` when the mask
        leaves no feasible schedule — the previous plan stays installed."""
        if failure_mask is not None and failure_mask.empty:
            failure_mask = None
        key = self._memo_key(failure_mask)
        t0 = time.perf_counter()
        if key in self._plan_memo:
            plans = self._plan_memo[key]
            self._plan_memo.move_to_end(key)
            self.last_replan_cached = True
        else:
            plans = plan_gradient_sync(self._grads, self._tc, self._mesh,
                                       self._cost, self._backend,
                                       sharded=True, failures=failure_mask,
                                       depth=self.depth,
                                       compress=self.compress,
                                       bits_overrides=(self.plans.bits
                                                       if self.compress
                                                       else None))
            self._plan_memo[key] = plans
            while len(self._plan_memo) > self.MEMO_CAP:
                self._plan_memo.popitem(last=False)
            self.last_replan_cached = False
        self.last_replan_s = time.perf_counter() - t0
        self.plans = plans
        self.failures = failure_mask
        self.replan_count += 1
        return self.arrays()


def _sync_one_axis(flat, axis, size, alg, m):
    if alg == "psum":
        return lax.psum(flat, axis)
    if alg == "ring":
        return C.allreduce_ring(flat, axis, size)
    if alg == "rd":
        return C.allreduce_rd(flat, axis, size)
    if alg == "bt":
        return C.allreduce_bt(flat, axis, size)
    if alg == "wrht":
        return C.allreduce_wrht_tree(flat, axis, size, m=m,
                                     alltoall_max=max(2, m // 2))
    raise ValueError(alg)


def sync_gradients(grads, tc: TrainConfig, mesh, ef_state=None,
                   sync_plans: GradSyncPlans | None = None,
                   plan_codes=None):
    """Explicit gradient sync over the manual DP axes.  Returns (mean grads,
    new_ef_state | None).  Must run inside shard_map (manual DP axes).

    ``sync_plans`` carries the setup-time bucket partition and per-bucket
    schedule choices for the ``"planned"`` mode; when absent they are
    derived on the spot (plan-cache-warm, but re-done per trace).

    ``plan_codes`` (the sharded modes, :data:`SHARDED_ALGOS`) is the traced
    strategy-code
    pytree of :meth:`SyncController.arrays`: bucket dispatch switches to
    ``lax.cond`` on the codes so a re-plan swaps schedules without a
    retrace (DESIGN.md §12)."""
    axes = dp_axes_of(mesh)
    sizes = {a: mesh.shape[a] for a in axes}
    total = math.prod(sizes.values())
    alg = tc.sync_algorithm
    new_ef = None

    if tc.compress_pod_axis and "pod" in axes and ef_state is not None:
        # inner axes with the configured algorithm, pod axis compressed
        inner = tuple(a for a in axes if a != "pod")

        def bucket_fn_inner(flat, nbytes):
            for ax in inner:
                flat = _sync_one_axis(flat, ax, sizes[ax],
                                      alg if alg in ("psum", "ring", "rd", "bt", "wrht") else "psum",
                                      tc.sync_m)
            return flat

        grads = bucketing.bucketed_allreduce(grads, bucket_fn_inner,
                                             tc.bucket_bytes)
        grads, new_ef = compression.ef_allreduce_tree(
            grads, ef_state, "pod", sizes["pod"])
        # ef path returns pod-mean; finish the mean over inner axes
        scale = 1.0 / math.prod(sizes[a] for a in inner) if inner else 1.0
        grads = jax.tree.map(lambda g: g * scale, grads)
        return grads, new_ef

    if alg in ("hier_faithful", "hier_scatter"):
        mode = "faithful" if alg == "hier_faithful" else "scatter"

        def bucket_fn(flat, nbytes):
            return C.hierarchical_allreduce(
                flat, axes, tuple(sizes[a] for a in axes), mode=mode)

    elif alg == "planned":
        plans = sync_plans or plan_gradient_sync(grads, tc, mesh)

        def bucket_fn(flat, nbytes, i):
            for ax in axes:
                flat = _dispatch_planned(flat, ax, sizes[ax],
                                         plans.plans[ax][i])
            return flat

        grads = bucketing.bucketed_apply_indexed(
            grads, bucket_fn, plans.spec, sync_dtype=_dtype(tc.sync_dtype))
        grads = jax.tree.map(lambda g: g / total, grads)
        return grads, new_ef

    elif alg == "planned_compressed":
        plans = sync_plans or plan_gradient_sync(grads, tc, mesh,
                                                 compress=True)
        if ef_state is None:
            ef_state = jax.tree.map(jnp.zeros_like, grads)

        def bucket_fn(flat, nbytes, i):
            for ax in axes:
                flat = _dispatch_planned(flat, ax, sizes[ax],
                                         plans.plans[ax][i])
            return flat

        grads, new_ef = bucketing.bucketed_apply_compressed(
            grads, ef_state, bucket_fn, plans.spec, bits=plans.bits,
            block=tc.compress_block, fused=tc.compress_fused_kernel,
            sync_dtype=_dtype(tc.sync_dtype))
        grads = jax.tree.map(lambda g: g / total, grads)
        return grads, new_ef

    elif alg == "planned_sharded_compressed":
        plans = sync_plans or plan_gradient_sync(grads, tc, mesh,
                                                 sharded=True, compress=True)
        if ef_state is None:
            ef_state = jax.tree.map(jnp.zeros_like, grads)

        def bucket_fn(flat, nbytes, i):
            return _sharded_sync_axes(flat, axes, sizes, plans, i,
                                      codes=plan_codes)

        grads, new_ef = bucketing.bucketed_apply_compressed(
            grads, ef_state, bucket_fn, plans.spec, bits=plans.bits,
            block=tc.compress_block, fused=tc.compress_fused_kernel,
            sync_dtype=_dtype(tc.sync_dtype))
        grads = jax.tree.map(lambda g: g / total, grads)
        return grads, new_ef

    elif alg == "planned_sharded":
        plans = sync_plans or plan_gradient_sync(grads, tc, mesh,
                                                 sharded=True)

        def bucket_fn(flat, nbytes, i):
            return _sharded_sync_axes(flat, axes, sizes, plans, i,
                                      codes=plan_codes)

        grads = bucketing.bucketed_apply_indexed(
            grads, bucket_fn, plans.spec, sync_dtype=_dtype(tc.sync_dtype))
        grads = jax.tree.map(lambda g: g / total, grads)
        return grads, new_ef

    elif alg == "planned_pipelined":
        plans = sync_plans or plan_gradient_sync(
            grads, tc, mesh, sharded=True, depth=tc.pipeline_depth)

        def rs_fn(flat, nbytes, i):
            return _sharded_rs_axes(flat, axes, sizes, plans, i,
                                    codes=plan_codes)

        def ag_fn(shard, lengths, nbytes, i):
            return _sharded_ag_axes(shard, lengths, axes, sizes, plans, i,
                                    codes=plan_codes)

        grads = bucketing.bucketed_apply_pipelined(
            grads, rs_fn, ag_fn, plans.spec, depth=tc.pipeline_depth,
            sync_dtype=_dtype(tc.sync_dtype))
        grads = jax.tree.map(lambda g: g / total, grads)
        return grads, new_ef

    else:
        def bucket_fn(flat, nbytes):
            for ax in axes:
                flat = _sync_one_axis(flat, ax, sizes[ax], alg, tc.sync_m)
            return flat

    grads = bucketing.bucketed_allreduce(grads, bucket_fn, tc.bucket_bytes,
                                         sync_dtype=_dtype(tc.sync_dtype))
    grads = jax.tree.map(lambda g: g / total, grads)
    return grads, new_ef


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

def _microbatched_grads(loss_fn, params, batch, n_micro: int,
                        accum_dtype=jnp.float32):
    """Gradient accumulation over n_micro splits of the batch leading dim."""
    if n_micro <= 1:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def split(x):
        b = x.shape[0]
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    mb = jax.tree.map(split, batch)

    def body(carry, mbatch):
        loss_acc, grads_acc = carry
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mbatch)
        grads_acc = jax.tree.map(
            lambda a, g: a + g.astype(accum_dtype), grads_acc, grads)
        return (loss_acc + loss, grads_acc), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
    (loss_sum, grads), _ = lax.scan(body, (jnp.zeros(()), zeros), mb)
    scale = 1.0 / n_micro
    grads = jax.tree.map(lambda g: g * scale, grads)
    return loss_sum * scale, {}, grads


def make_train_step(cfg: ModelConfig, tc: TrainConfig, mesh=None):
    """Returns a function (state, batch) -> (state, metrics).

    auto mode: call under jit with sharded args.  Manual modes: the returned
    function already wraps shard_map over the DP axes; jit it directly.

    For the sharded modes (``"planned_sharded"`` / ``"planned_pipelined"``)
    the returned function additionally accepts an
    optional third argument ``plan_codes`` — the traced strategy-code pytree
    of :meth:`SyncController.arrays` — and carries the controller as a
    ``.controller`` attribute.  Feeding ``controller.replan(mask)``'s arrays
    into the jitted step swaps every (axis, bucket) schedule without a
    retrace (DESIGN.md §12); omitting the argument keeps the static
    setup-time plan, so existing callers are unchanged.
    """
    api = mapi.get_api(cfg, compute_dtype=_dtype(tc.compute_dtype), remat=tc.remat)
    lr_fn = make_lr_schedule(tc)

    # amortized planning: partition the (abstract) gradients into buckets
    # and plan every bucket's schedule ONCE here — each traced step then
    # just dispatches bucket i to its precomputed plan (DESIGN.md §10)
    sync_plans = None
    controller = None
    if (tc.sync_algorithm in ("planned", "planned_compressed") + SHARDED_ALGOS
            and mesh is not None and dp_axes_of(mesh)):
        g_dtype = _dtype(tc.grad_accum_dtype if tc.microbatches > 1
                         else tc.param_dtype)
        abstract_params = abstract_train_state(cfg, tc)["params"]
        abstract_grads = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, g_dtype), abstract_params)
        if tc.sync_algorithm in SHARDED_ALGOS:
            controller = SyncController(abstract_grads, tc, mesh)
            sync_plans = controller.plans
        else:
            sync_plans = plan_gradient_sync(
                abstract_grads, tc, mesh,
                compress=tc.sync_algorithm == "planned_compressed")

    def loss_fn(params, batch):
        return api.loss(params, batch)

    def step_body(state, batch, plan_codes=None):
        loss, metrics, grads = _microbatched_grads(
            loss_fn, state["params"], batch, tc.microbatches,
            accum_dtype=_dtype(tc.grad_accum_dtype))
        # the expert layer's counters of a held share (layers.moe_apply)
        counters = {k: v for k, v in metrics.items() if k.startswith("moe_")}
        new_ef = None
        if tc.sync_algorithm in MANUAL_ALGOS:
            with jax.named_scope("grad_sync"):
                grads, new_ef = sync_gradients(grads, tc, mesh, state.get("ef"),
                                               sync_plans=sync_plans,
                                               plan_codes=plan_codes)
                loss = lax.pmean(loss, dp_axes_of(mesh))
                if counters:
                    counters = lax.pmean(counters, dp_axes_of(mesh))
        with jax.named_scope("optimizer"):
            lr = lr_fn(state["step"])
            params, opt, om = adamw_update(grads, state["opt"], state["params"],
                                           lr, tc)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        if "ef" in state:
            new_state["ef"] = new_ef if new_ef is not None else state["ef"]
        return new_state, {"loss": loss, "lr": lr, **om, **counters}

    if tc.sync_algorithm not in MANUAL_ALGOS:
        return step_body

    assert mesh is not None, "manual sync modes need the mesh"
    dp = dp_axes_of(mesh)

    def _shard_map(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names=set(dp),
                             check_vma=False)

    # state replicated over DP axes, sharded over 'model' per param rules is
    # delegated to GSPMD ('model' stays an auto axis inside shard_map).
    state_specs = P()   # replicated across manual axes
    batch_spec = P(dp)  # batch leading dim split across manual DP axes

    def batch_specs_tree(batch):
        return jax.tree.map(lambda _: batch_spec, batch)

    def wrapped(state, batch, plan_codes=None):
        if plan_codes is None:
            f = _shard_map(
                step_body,
                in_specs=(state_specs,
                          jax.tree.map(lambda _: batch_spec, batch)),
                out_specs=(state_specs, P()),
            )
            return f(state, batch)
        # the strategy codes ride in replicated (P()) so every device takes
        # the same lax.cond branch — a requirement for the collectives inside
        f = _shard_map(
            step_body,
            in_specs=(state_specs,
                      jax.tree.map(lambda _: batch_spec, batch),
                      jax.tree.map(lambda _: P(), plan_codes)),
            out_specs=(state_specs, P()),
        )
        return f(state, batch, plan_codes)

    wrapped.controller = controller
    return wrapped
