"""DistributedOptimizer: gradient averaging as an optax transformation.

Reference: ``horovod/torch/optimizer.py`` (``_DistributedOptimizer``:
per-parameter backward hooks firing ``allreduce_async_``, a handle table,
``synchronize()`` before ``step()``, ``backward_passes_per_step`` local
aggregation) and ``horovod/tensorflow/__init__.py``
(``DistributedOptimizer`` wrapping ``compute_gradients``) — paths per
SURVEY.md §2.4, mount empty, unverified.

TPU-native redesign
-------------------
The reference needs hooks + async handles because framework autograd
produces gradients one tensor at a time on an eager stream, and overlap
comes from racing communication against the rest of backward.  Under
XLA, the whole step is one compiled program: gradients are a pytree
produced by ``jax.grad``, the fused allreduce is HLO inside that program,
and **overlap is the XLA scheduler's job** (it hoists collectives to
overlap with independent compute — the latency-hiding the reference
hand-builds with streams).  So the natural form is an *optax gradient
transformation*: ``update()`` allreduces (fused, compressed, Adasum-able)
then defers to the wrapped optimizer.  ``backward_passes_per_step`` —
local accumulation with a collective only on the boundary step — becomes
a ``lax.cond`` in the same program.

Use inside any SPMD region (``make_train_step`` builds one for you)::

    tx  = hvd.DistributedOptimizer(optax.adamw(3e-4), op=hvd.Average)
    step = hvd.make_train_step(loss_fn, tx)     # jit'ed, mesh-aware
    params, opt_state, loss = step(params, opt_state, batch)
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from .._compat import shard_map
from ..config import DEFAULT_COST_ALPHA_US, DEFAULT_COST_BETA_GBPS
from ..ops import collectives as C
from ..ops import fusion
from ..ops import spmd
from ..ops.adasum import adasum_pytree
from ..ops.compression import Compression
from ..ops.fusion import fused_allreduce_pytree
from ..obs import instrument as _obs
from ..obs import trace as _trace
from ..utils.logging import get_logger

logger = get_logger(__name__)


class DistributedOptimizerState(NamedTuple):
    inner_state: Any
    accumulator: Any          # grad pytree (zeros when backward_passes == 1)
    step_count: jax.Array     # int32 scalar
    # Error-feedback residual: the lossy wire's accumulated local
    # quantization error, re-injected into the next reduced gradient
    # (EQuARX recipe).  Per-leaf zeros pytree when error feedback is on,
    # 0-d placeholders otherwise (same convention as ``accumulator``).
    residual: Any = ()


def _check_reduce_args(op: str, compression) -> None:
    if op not in (C.Average, C.Sum, C.Adasum):
        raise ValueError(
            f"Gradient reduction supports Average/Sum/Adasum, got {op!r}")
    if op == C.Adasum and compression not in (None, Compression.none):
        raise ValueError(
            "compression is not supported with op=Adasum (the pairwise "
            "projections need full-precision dot products); drop the "
            "compression argument or use op=Average/Sum")


def _resolve_compression(compression):
    """Trace-time compression tier: an explicit call-site argument wins;
    otherwise the live config's ``HVD_TPU_COMPRESSION`` — the autotuner's
    compressor application point, read at trace time so proposals land at
    re-jit boundaries — selects the tier; default exact."""
    if compression is not None:
        return compression
    from .. import basics

    if basics.is_initialized():
        name = basics.config().compression
        if name:
            tier = getattr(Compression, name, None)
            if tier is None:
                raise ValueError(
                    f"unknown compression tier {name!r}; expected one of "
                    "none/fp16/bf16/int8")
            return tier
    return Compression.none


_snap_warned: set = set()


def snap_microbatches(requested: int, rows: int) -> int:
    """Largest divisor of ``rows`` that is <= ``requested`` — THE
    snapping policy for config/autotune-driven microbatch counts, shared
    with the benches so a reported count always matches what the step
    ran."""
    mb = min(max(1, int(requested)), max(1, int(rows)))
    while rows % mb:
        mb -= 1
    return mb


def _resolve_microbatches(requested: Optional[int], batch) -> int:
    """Microbatch count for this trace: the explicit argument, else the
    live config (``HVD_TPU_MICROBATCHES`` — the autotune application
    point).  The count must divide the per-call batch rows: an explicit
    non-divisor raises (a loud user error), while a config/autotune-
    driven value snaps DOWN to the largest divisor with a once-per-shape
    warning — a tuner proposal must never crash the run."""
    leaves = jax.tree.leaves(batch)
    if not leaves:
        return 1
    shape = getattr(leaves[0], "shape", ())
    b = int(shape[0]) if shape else 1
    mb = requested
    if mb is None:
        from .. import basics

        if basics.is_initialized():
            cfg = basics.config()
            mb = cfg.microbatches
        else:
            mb = 1
    mb = int(mb)
    if mb <= 1:
        return 1
    # The explicit-argument contract raises BEFORE the b<=1 early
    # return: microbatches=4 over a 1-row per-slot batch is a loud user
    # error, not a silent no-accumulation run.
    if requested is not None and (mb > b or b % mb):
        raise ValueError(
            f"microbatches={mb} does not divide the per-slot batch of "
            f"{b} rows; pick a divisor (or pad the batch)")
    if b <= 1:
        return 1
    snapped = snap_microbatches(mb, b)
    if snapped != mb:
        key = (mb, snapped, b)
        if key not in _snap_warned:
            _snap_warned.add(key)
            logger.warning(
                "HVD_TPU_MICROBATCHES=%d does not divide the per-slot "
                "batch of %d rows; snapping to %d", mb, b, snapped)
    return snapped


def _microbatch_grads(grad_fn, params, batch, mb, *, has_aux=False,
                      overlap=False, spmd_op="average", axis=None,
                      groups=None, compression=None, threshold=0,
                      alpha_us=DEFAULT_COST_ALPHA_US,
                      beta_gbps=DEFAULT_COST_BETA_GBPS):
    """Gradient accumulation over ``mb`` microbatches as ONE traced scan
    (bounded recompiles: the body traces once regardless of ``mb``).

    With ``overlap`` inside an SPMD region: microbatch *i−1*'s bucketed
    reduce-scatter is emitted in the same scan body as microbatch *i*'s
    forward/backward — the two are dataflow-independent, so XLA's async
    collective scheduler runs the wire under the compute (the fused
    computation-collective overlap of arXiv:2305.06942), double-buffered
    per bucket via the scan carry.  The all-gather phase is deferred to
    the optimizer-update boundary: one AG total, not one per microbatch.

    Returns ``(loss, grads, aux, reduced)`` — loss/grads averaged over
    microbatches, ``aux`` stacked ``[mb, ...]``, ``reduced`` True when
    the overlap wire already applied the cross-slot reduction."""
    from .. import faults as _faults

    if _faults._active is not None:
        # Fault site "accumulate": trace time, one event per microbatch
        # boundary — the failure surfaces while the accumulation program
        # is being built, the moment a planner/shape bug would.
        for i in range(mb):
            _faults.on_accumulate(i)

    mbatch = jax.tree.map(
        lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]), batch)
    first = jax.tree.map(lambda x: x[0], mbatch)
    rest = jax.tree.map(lambda x: x[1:], mbatch)
    if has_aux:
        (loss0, aux0), g0 = grad_fn(params, first)
    else:
        loss0, g0 = grad_fn(params, first)
        aux0 = None

    use_overlap = False
    n = None
    if overlap and axis is not None:
        n = fusion._uniform_group_width(axis, groups)
        use_overlap = n is not None and n > 1

    if _obs.enabled():
        _obs.record_microbatch_plan(mb, overlap=bool(use_overlap))

    if use_overlap:
        leaves0, treedef = jax.tree.flatten(g0)
        plan = fusion.plan_overlap_buckets(
            leaves0, threshold, world_size=n, alpha_us=alpha_us,
            beta_gbps=beta_gbps)
        comp = compression or Compression.none
        # Topology-aware lowering of the overlap wire: buckets the
        # two-tier compiler marks hierarchical reduce-scatter within
        # the pod and cross pods on the fragment (docs/topology.md);
        # None = flat wire, the single-tier default.
        from ..topo import schedule as _topo_sched_mod

        topo_compiler = _topo_sched_mod.maybe_compiler(n, groups=groups)
        if topo_compiler is not None:
            # Record ONLY the buckets the wire will actually lower
            # hierarchically (the _overlap_bucket_schedule gate below):
            # flat/two-phase buckets ride the plain whole-axis RS+AG
            # and are already covered by the overlap plan record.
            executed = [
                s for s in (fusion._overlap_bucket_schedule(
                    plan, bi, topo_compiler)
                    for bi in range(len(plan.members)))
                if s is not None]
            if executed:
                _topo_sched_mod.record_plans(
                    executed, comp,
                    np.dtype(plan.dtypes[0]).itemsize
                    if plan.dtypes else 4,
                    params=topo_compiler.params)
        if _obs.enabled() and plan.members:
            # Trace-time plan record for the overlap wire: mb RS passes
            # plus ONE deferred AG ride this plan per step.
            exact = sum(p * np.dtype(d).itemsize
                        for p, d in zip(plan.payload, plan.dtypes))
            ratio = fusion.wire_ratio(
                comp, max(np.dtype(plan.dtypes[0]).itemsize, 1))
            _obs.on_fusion_plan(
                "overlap",
                bytes_on_wire=int(exact * ratio * (mb + 1)),
                buckets=len(plan.members), compression_ratio=ratio)

        def rs(leaves):
            return fusion.overlap_reduce_scatter(
                leaves, plan, axis=axis, op=spmd_op, groups=groups,
                compression=comp, topo=topo_compiler)

        def body(carry, mb_i):
            pending, shard_acc, loss_acc = carry
            if has_aux:
                (loss_i, aux_i), g_i = grad_fn(params, mb_i)
            else:
                loss_i, g_i = grad_fn(params, mb_i)
                aux_i = None
            # The RS consumes the PREVIOUS microbatch's gradients —
            # independent of this body's backward, so XLA overlaps them.
            shard_acc = tuple(a + s
                              for a, s in zip(shard_acc, rs(pending)))
            new_pending = tuple(jax.tree.flatten(g_i)[0])
            return (new_pending, shard_acc, loss_acc + loss_i), aux_i

        init = (tuple(leaves0), fusion.zero_overlap_shards(plan), loss0)
        (pending, shard_acc, loss_sum), aux_rest = lax.scan(body, init, rest)
        # Last microbatch's RS (nothing left to hide it under), then the
        # single deferred AG at the optimizer boundary.
        shard_acc = tuple(a + s for a, s in zip(shard_acc, rs(pending)))
        full = fusion.overlap_all_gather(
            shard_acc, plan, leaves0, axis=axis, groups=groups,
            compression=comp, topo=topo_compiler)
        grads = jax.tree.unflatten(treedef, [l / mb for l in full])
    else:
        def body(carry, mb_i):
            acc, loss_acc = carry
            if has_aux:
                (loss_i, aux_i), g_i = grad_fn(params, mb_i)
            else:
                loss_i, g_i = grad_fn(params, mb_i)
                aux_i = None
            return (jax.tree.map(jnp.add, acc, g_i),
                    loss_acc + loss_i), aux_i

        (acc, loss_sum), aux_rest = lax.scan(body, (g0, loss0), rest)
        grads = jax.tree.map(lambda g: g / mb, acc)

    loss = loss_sum / mb
    aux = None
    if has_aux:
        aux = jax.tree.map(
            lambda a0, ar: jnp.concatenate(
                [jnp.asarray(a0)[None], ar], axis=0), aux0, aux_rest)
    return loss, grads, aux, use_overlap


_adasum_comp_warned = False
_lossy_no_ef_warned = False


def _allreduce_grads(grads, *, op, axis, groups, compression, threshold,
                     two_phase=None, pipeline_depth=None):
    if op == C.Adasum:
        # An EXPLICIT compression argument with Adasum is rejected at
        # construction; a config-resolved tier (HVD_TPU_COMPRESSION /
        # the autotuner's compressor knob) can still reach here — say
        # loudly that it is ignored rather than silently run a
        # different wire than the user configured.
        global _adasum_comp_warned
        if (compression not in (None, Compression.none)
                and not _adasum_comp_warned):
            _adasum_comp_warned = True
            logger.warning(
                "HVD_TPU_COMPRESSION is ignored for op=Adasum (the "
                "pairwise projections need full-precision dot "
                "products); this optimizer runs the exact wire")
        return adasum_pytree(grads, axis=axis, groups=groups)
    spmd_op = "average" if op == C.Average else "sum"
    return fused_allreduce_pytree(
        grads, axis=axis, op=spmd_op, threshold=threshold, groups=groups,
        compression=compression, two_phase=two_phase,
        pipeline_depth=pipeline_depth,
    )


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: str = C.Average,
    compression=None,
    backward_passes_per_step: int = 1,
    average_aggregated_gradients: bool = True,
    process_set=None,
    axis_name: Optional[str] = None,
    fusion_threshold: Optional[int] = None,
    two_phase: Optional[bool] = None,
    pipeline_depth: Optional[int] = None,
    error_feedback: Optional[bool] = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer with distributed gradient aggregation
    (reference: ``hvd.DistributedOptimizer``).

    Must be used inside an SPMD region over ``axis_name`` (default: the
    framework mesh axis) — ``make_train_step`` provides one.

    Args mirror the reference: ``op`` (Average/Sum/Adasum),
    ``compression`` (``hvd.Compression.fp16``/``bf16``),
    ``backward_passes_per_step`` (aggregate locally for k calls, allreduce
    + apply on the k-th; in between, parameters receive zero updates),
    ``average_aggregated_gradients`` (divide the accumulated sum by k).

    ``two_phase``/``pipeline_depth`` opt the gradient allreduce into the
    bucket-pipelined reduce-scatter + all-gather schedule
    (``ops.fusion.fused_two_phase_apply``); None defers to the live
    config (``HVD_TPU_TWO_PHASE_ALLREDUCE`` / ``HVD_TPU_PIPELINE_DEPTH``)
    at trace time, so autotune proposals land at re-jit boundaries.

    ``compression=None`` defers to ``HVD_TPU_COMPRESSION`` at trace time
    (same autotune contract).  ``error_feedback`` (None = the live
    config's ``HVD_TPU_ERROR_FEEDBACK``) carries the lossy wire's local
    quantization error in ``DistributedOptimizerState.residual`` and
    re-injects it into the next step's gradient — the EQuARX recipe that
    keeps ``Compression.int8``/``fp16`` unbiased over long runs (a
    component persistently quantized to zero accumulates in the residual
    until it crosses the wire's resolution).  No-op on exact wires and
    under ``op=Adasum`` (whose transport is exact).
    """
    _check_reduce_args(op, compression)
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    k = int(backward_passes_per_step)

    def _error_feedback_on() -> bool:
        if error_feedback is not None:
            return bool(error_feedback)
        from .. import basics

        if basics.is_initialized():
            cfg = basics.config()
            return cfg.error_feedback
        return False

    def _axis():
        if axis_name is not None:
            return axis_name
        from .. import basics

        plan = basics.peek("mesh_plan")
        if plan is not None:
            # The session plan's derived reduce wire: the bare legacy name
            # for 1-D plans (bit-identical), a name tuple for multi-axis
            # layouts.  Resolved at trace time so a layout flip re-jit
            # picks up the new wire.
            return plan.reduce_axis()
        return (basics.config().mesh_axis_name
                if basics.is_initialized() else "hvd")

    def _threshold() -> int:
        if fusion_threshold is not None:
            return fusion_threshold
        from .. import basics

        return (basics.config().fusion_threshold
                if basics.is_initialized() else 64 * 1024 * 1024)

    def _groups():
        if process_set is None:
            return None, None
        from .. import plan as _plan_mod

        groups = _plan_mod.collective_groups(process_set)
        member_groups = [list(process_set.ranks)] if groups else None
        return groups, member_groups

    def init_fn(params):
        acc = (jax.tree.map(jnp.zeros_like, params) if k > 1
               else jax.tree.map(lambda x: jnp.zeros((), x.dtype), params))
        if _error_feedback_on():
            residual = jax.tree.map(
                lambda x: (jnp.zeros_like(x)
                           if jnp.issubdtype(jnp.asarray(x).dtype,
                                             jnp.floating)
                           else jnp.zeros((), jnp.asarray(x).dtype)),
                params)
        else:
            residual = jax.tree.map(
                lambda x: jnp.zeros((), jnp.asarray(x).dtype), params)
        return DistributedOptimizerState(
            inner_state=optimizer.init(params),
            accumulator=acc,
            step_count=jnp.zeros((), jnp.int32),
            residual=residual,
        )

    def _reduce_and_update(grads, state, params):
        axis = _axis()
        groups, member_groups = _groups()
        comp = _resolve_compression(compression)
        ef = (_error_feedback_on() and comp is not Compression.none
              and op != C.Adasum)
        new_residual = state.residual
        if ef:
            # EF: correct the gradient with last step's transport error
            # BEFORE the lossy wire, then record what this wire loses.
            # A 0-d residual placeholder (EF was off at init) passes
            # through untouched.  The residual tracks the wire's
            # quantization granularity — block = elems/n, not the 1024
            # ceiling (wire_block_size) — per LEAF: blocks inside a
            # fused multi-leaf bucket can span leaf boundaries, so this
            # is an approximation of the exact bucket-level error, but
            # one that keeps the EF contraction property (sub-resolution
            # components still accumulate until they fire; pinned by the
            # drift test in tests/test_microbatch.py).
            from ..ops.quantization import wire_block_size

            n = fusion._uniform_group_width(axis, groups)
            grads = jax.tree.map(
                lambda g, r: g + r if r.shape == g.shape else g,
                grads, state.residual)
            new_residual = jax.tree.map(
                lambda g, r: (comp.local_error(
                    g, block_size=wire_block_size(g.size, n or 1))
                    if r.shape == g.shape else r),
                grads, state.residual)
        g = _allreduce_grads(
            grads,
            op=op,
            axis=axis,
            groups=member_groups if op == C.Adasum else groups,
            compression=comp,
            threshold=_threshold(),
            two_phase=two_phase,
            pipeline_depth=pipeline_depth,
        )
        updates, inner_state = optimizer.update(g, state.inner_state, params)
        return updates, inner_state, new_residual

    def update_fn(grads, state: DistributedOptimizerState, params=None):
        if k == 1:
            updates, inner_state, residual = _reduce_and_update(
                grads, state, params)
            return updates, DistributedOptimizerState(
                inner_state=inner_state,
                accumulator=state.accumulator,
                step_count=state.step_count + 1,
                residual=residual,
            )

        acc = jax.tree.map(jnp.add, state.accumulator, grads)
        count = state.step_count + 1
        is_boundary = (count % k) == 0

        def boundary(_):
            g = (jax.tree.map(lambda a: a / k, acc)
                 if average_aggregated_gradients else acc)
            updates, inner_state, residual = _reduce_and_update(
                g, state, params)
            zeros = jax.tree.map(jnp.zeros_like, acc)
            return updates, inner_state, zeros, residual

        def interior(_):
            zero_updates = jax.tree.map(jnp.zeros_like, grads)
            return zero_updates, state.inner_state, acc, state.residual

        updates, inner_state, acc, residual = lax.cond(
            is_boundary, boundary, interior, operand=None)
        return updates, DistributedOptimizerState(
            inner_state=inner_state, accumulator=acc, step_count=count,
            residual=residual,
        )

    return optax.GradientTransformation(init_fn, update_fn)


def resolve_mesh_axis(mesh, axis_name: Optional[str]):
    """(mesh_obj, axis) for a train-step builder: the session
    :class:`~horovod_tpu.plan.MeshPlan` by default (its mesh and its
    derived gradient-reduce axis — the bare legacy name for 1-D plans, a
    name tuple for multi-axis layouts), or an explicit
    ``jax.sharding.Mesh`` with its first axis.  An explicit ``axis_name``
    always wins."""
    from .. import basics

    if mesh is None:
        plan = basics.peek("mesh_plan")
        if plan is not None:
            if axis_name is None:
                return plan.mesh, plan.reduce_axis()
            if plan.has_axis(axis_name):
                return plan.mesh, axis_name
        gm = basics.global_mesh()
        return gm.mesh, (axis_name or gm.axis_name)
    return mesh, (axis_name or list(mesh.axis_names)[0])


def axis_width(mesh_obj, axis) -> int:
    """Participant count of one reduce wire: the axis size, or the
    product over a multi-axis plan's name tuple."""
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= int(mesh_obj.shape[a])
        return n
    return int(mesh_obj.shape[axis])


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    mesh=None,
    axis_name: Optional[str] = None,
    has_aux: bool = False,
    donate: bool = True,
    distributed: Optional[bool] = None,
    op: str = C.Average,
    compression=None,
    process_set=None,
    two_phase: Optional[bool] = None,
    pipeline_depth: Optional[int] = None,
    microbatches: Optional[int] = None,
    overlap: Optional[bool] = None,
):
    """Build the jit'ed SPMD training step — the hot loop the reference
    assembles from hooks + background thread + NCCL (§3.2 of SURVEY.md),
    here a single compiled program.  ``two_phase``/``pipeline_depth``
    select the bucket-pipelined RS+AG gradient wire (None = live config
    at trace time — the autotune application point).

    ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux``).  The returned ``step(params, opt_state, batch)`` shards
    ``batch`` along its leading axis over the mesh, computes per-slot
    gradients, allreduces them (unless ``optimizer`` is already a
    ``DistributedOptimizer`` — pass ``distributed=False`` to force off),
    applies updates, and returns ``(params, opt_state, loss[, aux])``
    with loss averaged across slots.  Parameters and optimizer state stay
    replicated.

    ``microbatches`` (None = ``HVD_TPU_MICROBATCHES``) accumulates
    gradients over that many microbatches of the per-slot batch inside
    ONE compiled scan.  With ``overlap`` (None =
    ``HVD_TPU_OVERLAP_REDUCE``; applies when this step owns the
    reduction and ``op`` is Average/Sum over uniform groups), microbatch
    *i−1*'s bucketed reduce-scatter is issued while microbatch *i*'s
    forward/backward computes and the all-gather is deferred to the
    optimizer-update boundary — hiding the collective time under
    backward compute instead of exposing it after the last gradient.
    ``aux`` comes back stacked ``[microbatches, ...]`` per slot.
    """
    from .. import basics
    from .. import plan as _plan_mod

    _check_reduce_args(op, compression)

    # Does the optimizer itself allreduce?  Decided at trace time by
    # inspecting the *actual* optimizer state for a
    # DistributedOptimizerState node (robust to optax.chain/masked
    # wrapping — no probe init on fake params, which structure-sensitive
    # optimizers would reject).  ``distributed=True/False`` overrides.
    def _contains_dist_state(opt_state) -> bool:
        found = False

        def visit(node):
            nonlocal found
            if isinstance(node, DistributedOptimizerState):
                found = True
            return node

        jax.tree.map(visit, opt_state,
                     is_leaf=lambda n: isinstance(n, DistributedOptimizerState))
        return found

    def _threshold():
        return (basics.config().fusion_threshold
                if basics.is_initialized() else 64 * 1024 * 1024)

    def _overlap_on() -> bool:
        if overlap is not None:
            return bool(overlap)
        if basics.is_initialized():
            cfg = basics.config()
            return cfg.overlap_reduce
        return True

    def _cost_knobs():
        if basics.is_initialized():
            cfg = basics.config()
            return cfg.cost_alpha_us, cfg.cost_beta_gbps
        return DEFAULT_COST_ALPHA_US, DEFAULT_COST_BETA_GBPS

    def _build_body():
        # Resolved INSIDE the builder (not at make time): the autotuner's
        # layout knob swaps the session MeshPlan at a re-jit boundary,
        # and rebuild() must pick up the new mesh + reduce axis + groups
        # — the same trace-time contract as every other tuned knob.
        mesh_obj, axis = resolve_mesh_axis(mesh, axis_name)
        groups = _plan_mod.collective_groups(process_set)
        member_groups = ([list(process_set.ranks)]
                         if process_set is not None and groups else None)

        def per_slot_step(params, opt_state, batch):
            reduce_here = (distributed if distributed is not None
                           else not _contains_dist_state(opt_state))
            comp = _resolve_compression(compression)
            if (reduce_here and compression is None
                    and comp is not Compression.none):
                # Config/autotune-driven lossy tier on a path with no EF
                # residual (EF state lives in DistributedOptimizer /
                # make_zero_train_step): legitimate, but the bias
                # accumulates unchecked over long runs — say so once.
                global _lossy_no_ef_warned
                if not _lossy_no_ef_warned:
                    _lossy_no_ef_warned = True
                    logger.warning(
                        "HVD_TPU_COMPRESSION drives a lossy gradient wire "
                        "on a step without error-feedback state; wrap the "
                        "optimizer in DistributedOptimizer("
                        "error_feedback=True) to carry the residual on "
                        "long runs")
            value_and_grad = jax.value_and_grad(loss_fn, has_aux=has_aux)

            def grad_fn(p, b):
                # Every forward/backward of the step, the microbatch
                # scan's included, under one scope of the program.
                with _trace.scope("hvd_tpu_fwd_bwd"):
                    return value_and_grad(p, b)

            mb = _resolve_microbatches(microbatches, batch)
            reduced = False
            if mb > 1:
                alpha_us, beta_gbps = _cost_knobs()
                loss, grads, aux, reduced = _microbatch_grads(
                    grad_fn, params, batch, mb, has_aux=has_aux,
                    overlap=(_overlap_on() and reduce_here
                             and op != C.Adasum),
                    spmd_op="average" if op == C.Average else "sum",
                    axis=axis, groups=groups, compression=comp,
                    threshold=_threshold(), alpha_us=alpha_us,
                    beta_gbps=beta_gbps)
            elif has_aux:
                (loss, aux), grads = grad_fn(params, batch)
            else:
                loss, grads = grad_fn(params, batch)
                aux = None
            if reduce_here and not reduced:
                grads = _allreduce_grads(
                    grads, op=op, axis=axis,
                    groups=member_groups if op == C.Adasum else groups,
                    compression=comp, threshold=_threshold(),
                    two_phase=two_phase, pipeline_depth=pipeline_depth,
                )
            # A DistributedOptimizer's own wire scopes nest inside this
            # one; a reader charges an operation to its innermost scope.
            with _trace.scope("hvd_tpu_optimizer"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
            loss = spmd.allreduce(loss, op="average", axis=axis,
                                  groups=groups)
            if has_aux:
                # Per-slot aux values come back stacked [size, ...]; add
                # the slot axis so scalars survive out_specs=P(axis).
                aux = jax.tree.map(lambda a: jnp.asarray(a)[None], aux)
                return params, opt_state, loss, aux
            return params, opt_state, loss

        return shard_map(
            per_slot_step,
            mesh=mesh_obj,
            in_specs=(P(), P(), P(axis)),
            out_specs=(P(), P(), P()) + ((P(axis),) if has_aux else ()),
            check=False,
        )

    donate_argnums = (0, 1) if donate else ()

    def build():
        # A fresh jit wrapper re-traces, so trace-time reads of
        # config().fusion_threshold (here and inside a wrapped
        # DistributedOptimizer) pick up autotune proposals; the body
        # itself is also rebuilt so a layout flip re-derives mesh +
        # axis + groups from the new session plan.  The obs wrapper
        # records step wall time / tokens per dispatch (no-op when
        # HVD_TPU_METRICS=0 — it returns the jitted step itself).
        return _obs.wrap_step(
            jax.jit(_build_body(), donate_argnums=donate_argnums),
            kind="train")

    pm = basics.peek("parameter_manager")   # fail-soft: None pre-init
    if pm is not None and not pm.frozen:
        if pm.claimed:
            # A second concurrent train step feeding the same manager
            # would cross-pollute scores and never see re-jits; only
            # the first step tunes.
            from ..utils.logging import get_logger

            get_logger(__name__).warning(
                "autotune is already driving another train step; this "
                "step runs untuned (one tuner per process)")
            return build()
        from .autotune import AutotunedTrainStep

        pm.claimed = True
        return AutotunedTrainStep(build, pm)
    return build()
