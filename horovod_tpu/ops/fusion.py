"""Tensor fusion: bucketing many small tensors into few large collectives.

Reference: the fusion buffer + coordinator fusion logic
(``horovod/common/fusion_buffer_manager.cc`` and the fusion pass inside
``Controller::ComputeResponseList`` — SURVEY.md §2.1, mount empty,
unverified).  There, a 64 MB scratch buffer (``HOROVOD_FUSION_THRESHOLD``)
is filled with ready tensors via batched device memcpys, one NCCL call
covers the buffer, and results are scattered back.

TPU-native redesign: fusion happens at *trace time*.  ``plan_buckets``
partitions a pytree's leaves into byte-bounded buckets (the planner is
pure bookkeeping, so it can also run in native code — see
``horovod_tpu/native``); ``fused_apply`` concatenates each bucket's leaves
into one flat vector, applies one collective per bucket, and splits back.
XLA fuses the concat/split into the collective's pre/post memcpys — the
same batched-memcpy trick as the reference's fusion-buffer kernels, but
compiler-generated, with no persistent scratch buffer to manage.

Two-phase bucket pipelining (beyond the reference; the phase-decomposed,
schedule-aware collectives of "Collective Communication for 100k+ GPUs",
PAPERS.md): a bandwidth-bound bucket's single allreduce decomposes into
**reduce-scatter → all-gather**, and consecutive buckets' phases are
emitted software-pipelined — bucket *i*'s all-gather interleaved with
bucket *i+pipeline_depth-1*'s reduce-scatter inside one traced program —
so XLA's async collective scheduler can keep both phases on the wire at
once.  Which buckets decompose is decided by an **α–β cost model**
(per-collective launch latency α, per-hop bandwidth β): a bucket whose
per-hop wire time ``bytes/(n·β)`` clears the extra phase launch α is
bandwidth-bound and splits; latency-bound stragglers stay single-phase.
``plan_bucket_schedule`` emits the whole plan (bucket membership +
per-bucket phase decision + interleaved emission order) deterministically
from static sizes, so every rank agrees without negotiation.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import DEFAULT_COST_ALPHA_US, DEFAULT_COST_BETA_GBPS
from ..obs import instrument as _obs
from ..obs import trace as _trace


def wire_ratio(compression, data_itemsize: int) -> float:
    """Wire bytes / exact bytes for a compression tier, from the
    compressor's own declaration (``wire_dtype`` on the cast tiers,
    ``wire_itemsize`` on the quantized tier — int8's per-block scale
    overhead is <1% at realistic block sizes and ignored here; this
    feeds telemetry and the cost model's byte counts, not an
    allocator)."""
    if compression is None:
        return 1.0
    wd = getattr(compression, "wire_dtype", None)
    if wd is not None:
        return np.dtype(wd).itemsize / max(1, data_itemsize)
    wi = getattr(compression, "wire_itemsize", None)
    if wi is not None:
        return float(wi) / max(1, data_itemsize)
    return 1.0


def plan_buckets(sizes_bytes: Sequence[int], threshold: int) -> List[List[int]]:
    """Greedy in-order bin packing of tensor byte sizes into buckets of at
    most ``threshold`` bytes (oversized tensors get singleton buckets).

    Order-preserving, like the reference's fusion scan — deterministic
    bucket membership is what lets every rank agree without negotiation.
    Delegates to the native C++ planner when built and not disabled via
    ``HVD_TPU_USE_NATIVE_PLANNER=0`` (same contract either way).
    """
    use_native = True
    from .. import basics

    if basics.is_initialized():
        use_native = basics.config().use_native_planner
    if use_native:
        try:
            from ..native import planner as _native

            if _native.available():
                return _native.plan_buckets(list(sizes_bytes), threshold)
        except ImportError:
            pass
    return plan_buckets_py(sizes_bytes, threshold)


def plan_buckets_py(sizes_bytes: Sequence[int], threshold: int) -> List[List[int]]:
    buckets: List[List[int]] = []
    current: List[int] = []
    current_bytes = 0
    for i, sz in enumerate(sizes_bytes):
        if current and current_bytes + sz > threshold:
            buckets.append(current)
            current, current_bytes = [], 0
        current.append(i)
        current_bytes += sz
    if current:
        buckets.append(current)
    return buckets


# --- α–β cost model + schedule planning --------------------------------------

def phase_cost_us(nbytes: int, n: int, alpha_us: float,
                  beta_gbps: float) -> float:
    """Modeled wall time of ONE phase (reduce-scatter or all-gather) of a
    ring collective over ``n`` participants: ``(n-1)`` hops of launch
    latency α plus shard transfer at bandwidth β."""
    if n <= 1:
        return 0.0
    beta_bytes_per_us = beta_gbps * 1e3  # GB/s == 10^9 B/s == 10^3 B/µs
    return (n - 1) * (alpha_us + (nbytes / n) / beta_bytes_per_us)


def allreduce_cost_us(nbytes: int, n: int, alpha_us: float,
                      beta_gbps: float) -> float:
    """Modeled wall time of a monolithic ring allreduce (the RS+AG wire
    cost fused into one launch): ``2(n-1)`` hops."""
    return 2.0 * phase_cost_us(nbytes, n, alpha_us, beta_gbps)


def two_phase_crossover_bytes(n: int, alpha_us: float,
                              beta_gbps: float) -> int:
    """Bucket payload above which phase decomposition pays: splitting
    costs one extra launch (α per hop), which the pipeline earns back
    only when the per-hop shard transfer time ``bytes/(n·β)`` is at
    least α — i.e. the bucket is bandwidth-bound."""
    if n <= 1:
        return 1 << 62  # nothing to decompose in a world of one
    return int(alpha_us * beta_gbps * 1e3 * n)


def plan_two_phase_flags(bucket_bytes: Sequence[int], n: int,
                         alpha_us: float, beta_gbps: float) -> List[bool]:
    """Per-bucket phase decision from the α–β model (True = decompose
    into reduce-scatter + all-gather)."""
    crossover = two_phase_crossover_bytes(n, alpha_us, beta_gbps)
    return [b >= crossover for b in bucket_bytes]


def _dispatch_two_phase_flags(payloads: Sequence[int], world_size: int,
                              alpha_us: float,
                              beta_gbps: float) -> List[bool]:
    """Same contract as :func:`plan_two_phase_flags`; delegates to the
    native planner when built and not disabled (mirroring
    :func:`plan_buckets`' dispatch)."""
    use_native = True
    from .. import basics

    if basics.is_initialized():
        use_native = basics.config().use_native_planner
    if use_native:
        try:
            from ..native import planner as _native

            if _native.available():
                return _native.plan_two_phase_flags(
                    list(payloads), world_size, alpha_us, beta_gbps)
        except ImportError:
            pass
    return plan_two_phase_flags(payloads, world_size, alpha_us, beta_gbps)


def plan_overlap_priority(bucket_bytes: Sequence[int], world_size: int,
                          alpha_us: float, beta_gbps: float) -> List[int]:
    """Bucket emission order that maximizes hidden communication:
    descending modeled wire cost (stable on ties).  The earliest-issued
    collective has the most concurrent compute left to hide under, so
    the most expensive bucket goes first — the overlap extension of the
    α–β model (fused computation-collective scheduling, PAPERS.md)."""
    costs = [phase_cost_us(b, world_size, alpha_us, beta_gbps)
             for b in bucket_bytes]
    return sorted(range(len(bucket_bytes)), key=lambda i: (-costs[i], i))


def plan_pipeline_order(two_phase_flags: Sequence[bool],
                        pipeline_depth: int,
                        priority: Optional[Sequence[float]] = None,
                        ) -> List[Tuple[str, int]]:
    """Software-pipelined emission order over buckets: ``("rs", i)`` /
    ``("ag", i)`` for decomposed buckets, ``("ar", i)`` for single-phase
    ones.  At most ``pipeline_depth`` reduce-scatters are in flight
    before the oldest bucket's all-gather is emitted; depth 1 degenerates
    to strictly sequential rs/ag pairs.  ``priority`` (e.g. per-bucket
    modeled wire cost) reorders emission descending-priority —
    most-expensive collectives first, so they have the most compute to
    hide under — while keeping the rs-before-ag and in-flight-bound
    invariants.  Deterministic in its inputs — every rank traces the
    identical collective order (the SPMD dispatch-order contract)."""
    depth = max(1, int(pipeline_depth))
    idxs: Sequence[int] = range(len(two_phase_flags))
    if priority is not None:
        if len(priority) != len(two_phase_flags):
            raise ValueError(
                f"priority has {len(priority)} entries for "
                f"{len(two_phase_flags)} buckets")
        idxs = sorted(idxs, key=lambda i: (-priority[i], i))
    order: List[Tuple[str, int]] = []
    inflight: List[int] = []
    for i in idxs:
        if two_phase_flags[i]:
            order.append(("rs", i))
            inflight.append(i)
            if len(inflight) >= depth:
                order.append(("ag", inflight.pop(0)))
        else:
            order.append(("ar", i))
    while inflight:
        order.append(("ag", inflight.pop(0)))
    return order


@dataclasses.dataclass(frozen=True)
class BucketSchedule:
    """A complete fusion plan: bucket membership, per-bucket phase
    decision, interleaved emission order, and the modeled makespan.
    ``est_hidden_us`` is the wire time the overlap term expects to hide
    under concurrent compute (0.0 when no compute estimate was given)."""

    buckets: Tuple[Tuple[int, ...], ...]
    two_phase: Tuple[bool, ...]
    order: Tuple[Tuple[str, int], ...]
    est_cost_us: float
    est_hidden_us: float = 0.0


def estimate_schedule_cost_us(bucket_bytes: Sequence[int],
                              two_phase_flags: Sequence[bool], n: int,
                              alpha_us: float, beta_gbps: float) -> float:
    """Modeled makespan of a pipelined schedule: single-phase buckets
    serialize; decomposed buckets overlap bucket *i*'s all-gather with
    bucket *i+1*'s reduce-scatter (steady state runs at the slower of
    the two phases per stage)."""
    total = 0.0
    prev_ag = 0.0
    for nbytes, tp in zip(bucket_bytes, two_phase_flags):
        if not tp:
            total += prev_ag + allreduce_cost_us(nbytes, n, alpha_us,
                                                 beta_gbps)
            prev_ag = 0.0
            continue
        rs = phase_cost_us(nbytes, n, alpha_us, beta_gbps)
        total += max(rs, prev_ag)   # this RS hides behind the prior AG
        prev_ag = rs                # AG cost == RS cost in the α–β model
    return total + prev_ag


def plan_bucket_schedule(sizes_bytes: Sequence[int], threshold: int, *,
                         world_size: int,
                         alpha_us: float = DEFAULT_COST_ALPHA_US,
                         beta_gbps: float = DEFAULT_COST_BETA_GBPS,
                         two_phase: bool = True,
                         pipeline_depth: int = 2,
                         compute_us: Optional[float] = None,
                         ) -> BucketSchedule:
    """Full schedule-aware plan for one dtype class: greedy byte-bounded
    buckets (``plan_buckets`` — native-capable), α–β phase decisions and
    the pipelined emission order.  Pure bookkeeping on static sizes, so
    every rank computes the identical schedule.  Delegates the
    flag computation to the native planner when built (same contract;
    equivalence property-tested in tests/test_native.py style in
    tests/test_fusion.py).

    ``compute_us`` is the overlap term: the modeled concurrent-compute
    time (e.g. one microbatch's backward, from ``utils.mfu``) the
    collectives can hide under.  When given, buckets are emitted in
    descending wire-cost order (``plan_overlap_priority``) so the most
    expensive collectives start earliest, and ``est_hidden_us`` reports
    how much of the modeled makespan the overlap is expected to hide."""
    buckets = plan_buckets(sizes_bytes, threshold)
    payloads = [sum(sizes_bytes[i] for i in b) for b in buckets]
    if two_phase and world_size > 1:
        flags = _dispatch_two_phase_flags(payloads, world_size, alpha_us,
                                          beta_gbps)
    else:
        flags = [False] * len(buckets)
    priority = None
    hidden = 0.0
    cost = estimate_schedule_cost_us(payloads, flags, world_size, alpha_us,
                                     beta_gbps)
    if compute_us is not None and world_size > 1:
        # ONE source of truth for the emission order: rank-encode
        # plan_overlap_priority's index order as priority values.
        order_idx = plan_overlap_priority(payloads, world_size, alpha_us,
                                          beta_gbps)
        priority = [0.0] * len(payloads)
        for rank, bi in enumerate(order_idx):
            priority[bi] = float(len(payloads) - rank)
        hidden = min(float(compute_us), cost)
    order = plan_pipeline_order(flags, pipeline_depth, priority)
    if _obs.enabled() and compute_us is not None:
        # The overlap-aware plan is the source of the hidden-comm
        # estimate operators scrape (`hvd_tpu_est_hidden_us`).
        _obs.on_fusion_plan(
            "schedule", bytes_on_wire=sum(payloads), buckets=len(buckets),
            est_cost_us=cost, est_hidden_us=hidden)
    return BucketSchedule(
        buckets=tuple(tuple(b) for b in buckets),
        two_phase=tuple(flags),
        order=tuple(order),
        est_cost_us=cost,
        est_hidden_us=hidden,
    )


def estimate_overlap_hidden_fraction(
        sizes_bytes: Sequence[int], threshold: int, *, world_size: int,
        microbatches: int, compute_us_per_microbatch: float,
        alpha_us: float = DEFAULT_COST_ALPHA_US,
        beta_gbps: float = DEFAULT_COST_BETA_GBPS) -> dict:
    """Modeled hidden-communication fraction of the overlap-scheduled
    microbatch wire: each of the ``microbatches`` microbatches pays one
    bucketed reduce-scatter pass, with microbatch *i−1*'s pass issued
    under microbatch *i*'s backward compute — so ``microbatches − 1``
    passes can hide up to ``compute_us_per_microbatch`` each; the last
    pass and the single deferred all-gather stay exposed.  Returns
    ``{"wire_us", "hidden_us", "hidden_frac"}`` (all 0 in a world of
    one, where there is no wire)."""
    mb = max(1, int(microbatches))
    buckets = plan_buckets(sizes_bytes, threshold)
    payloads = [sum(sizes_bytes[i] for i in b) for b in buckets]
    rs_us = sum(phase_cost_us(p, world_size, alpha_us, beta_gbps)
                for p in payloads)
    ag_us = rs_us  # AG cost == RS cost in the α–β model
    wire_us = mb * rs_us + ag_us
    hidden_us = (mb - 1) * min(max(0.0, float(compute_us_per_microbatch)),
                               rs_us)
    return {
        "wire_us": wire_us,
        "hidden_us": hidden_us,
        "hidden_frac": (hidden_us / wire_us) if wire_us > 0 else 0.0,
    }


def _native_ffi_ok() -> bool:
    """Route the bucket scatter/gather through the native XLA-FFI
    handlers?  Only on the CPU backend (on TPU, XLA's own fusion of
    concat/slice into the collective's memcpys is the native path —
    XLA:TPU runs no user custom calls on-device) and only inside a
    *manual* SPMD region (shard_map): under the auto partitioner an
    opaque custom call makes XLA all-gather slot-sharded operands, an
    8x comms regression vs the partial-sum + all-reduce it finds for
    the plain concat path."""
    try:
        if jax.default_backend() != "cpu":
            return False
        if not jax.sharding.get_abstract_mesh().manual_axes:
            return False
        from ..native import ffi

        return ffi.available()
    except Exception:
        return False


def fused_apply(
    leaves: Sequence[jax.Array],
    collective_1d: Callable[[jax.Array], jax.Array],
    threshold: int,
    lead_ndim: int = 0,
) -> List[jax.Array]:
    """Apply a collective to ``leaves`` with fusion.

    Leaves are grouped per dtype then bucketed by ``threshold`` *payload*
    bytes (the bytes one slot puts on the wire — leading ``lead_ndim``
    axes, e.g. the host-tier ``[size, ...]`` slot axis, don't count);
    each bucket is flattened+concatenated along its last axis, passed
    through ``collective_1d`` once, and split/reshaped back.  The
    collective may consume the leading axes (host-tier reduction does);
    splitting happens on the output's last axis.  Runs under jit.

    On the CPU backend the pack/split legs ride the native typed-FFI
    handlers (``native/src/ffi_ops.cc``) — one strided-memcpy pass, the
    fusion buffer's scatter/gather as compiled custom calls.
    """
    out: List[jax.Array] = [None] * len(leaves)  # type: ignore[list-item]
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)

    use_ffi = _native_ffi_ok()
    if use_ffi:
        from ..native import ffi as native_ffi

    bucket_ids = itertools.count()
    for dtype, idxs in by_dtype.items():
        sizes = [int(np.prod(leaves[i].shape[lead_ndim:])) * dtype.itemsize
                 for i in idxs]
        for bucket in plan_buckets(sizes, threshold):
            members = [idxs[j] for j in bucket]
            with _trace.scope("hvd_tpu_wire_pack"):
                flats = [leaves[i].reshape(
                    leaves[i].shape[:lead_ndim] + (-1,)) for i in members]
                if len(flats) > 1 and use_ffi:
                    # [rows, n_i] normal form (rows=1 when there is no
                    # slot axis); the handler does one row-strided
                    # memcpy pass.
                    rows2 = [f.reshape((-1, f.shape[-1])) for f in flats]
                    fused = native_ffi.bucket_pack(rows2).reshape(
                        flats[0].shape[:-1] + (-1,))
                elif len(flats) > 1:
                    fused = jnp.concatenate(flats, axis=lead_ndim)
                else:
                    fused = flats[0]
            with _trace.scope(f"hvd_tpu_wire_bucket_{next(bucket_ids)}"):
                reduced = collective_1d(fused)
            cols = [int(np.prod(leaves[i].shape[lead_ndim:]))
                    if leaves[i].shape[lead_ndim:] else 1
                    for i in members]
            with _trace.scope("hvd_tpu_wire_unpack"):
                if len(members) > 1 and use_ffi:
                    pieces = native_ffi.bucket_unpack(
                        reduced.reshape((-1, reduced.shape[-1])), cols)
                    for i, piece in zip(members, pieces):
                        out[i] = piece.reshape(
                            reduced.shape[:-1]
                            + leaves[i].shape[lead_ndim:])
                    continue
                offset = 0
                for i, n in zip(members, cols):
                    tail_shape = leaves[i].shape[lead_ndim:]
                    piece = jax.lax.dynamic_slice_in_dim(
                        reduced, offset, n, axis=reduced.ndim - 1
                    )
                    out[i] = piece.reshape(reduced.shape[:-1] + tail_shape)
                    offset += n
    return out


def _uniform_group_width(axis: str, groups) -> Optional[int]:
    """Participant count per reduction group, or None when the groups
    are ragged (XLA's ReduceScatter/AllGather need uniform replica
    groups — e.g. a process set's ``[members, complement]`` partition
    with unequal halves must stay single-phase)."""
    from .._compat import axis_size

    if not groups:
        return axis_size(axis)
    widths = {len(g) for g in groups}
    if len(widths) != 1:
        return None
    return len(groups[0])


def fused_two_phase_apply(
    leaves: Sequence[jax.Array],
    *,
    axis: str,
    op: str,
    groups,
    compression,
    threshold: int,
    pipeline_depth: int,
    alpha_us: float,
    beta_gbps: float,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    schedule=None,
) -> List[jax.Array]:
    """Schedule-aware fused allreduce: buckets whose payload clears the
    α–β crossover decompose into reduce-scatter → all-gather, emitted in
    the pipelined order of :func:`plan_pipeline_order` so bucket *i*'s
    all-gather interleaves with bucket *i+1*'s reduce-scatter in the
    traced program (XLA's async collective scheduler overlaps them on
    the wire).  Latency-bound buckets stay single-launch allreduces.
    Must run inside an SPMD region over ``axis``; numerically equivalent
    to the single-phase path (same reduction, same compression wire).

    ``schedule`` (a ``topo.schedule.ScheduleCompiler``) replaces the
    flat α–β phase decision with the two-tier compiler's per-bucket
    choice: ``two_phase`` buckets keep the pipelined RS/AG emission,
    ``hierarchical`` buckets ride the compiled RS-intra → cross-pod →
    AG-intra lowering as single composite entries in the emission
    order, and ``flat`` buckets stay monolithic allreduces.
    """
    # Fault site "fusion": fires at trace time — the failure surfaces
    # while the fused two-phase program is being built, the moment a
    # planner/compile bug would.
    from .. import faults as _faults

    if _faults._active is not None:
        _faults.on_fusion("two_phase_apply")
    n = _uniform_group_width(axis, groups)

    out: List[jax.Array] = [None] * len(leaves)  # type: ignore[list-item]
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)

    # One global bucket list across dtype classes: pipelining is about
    # wire occupancy, which doesn't care about element type.
    packed: List[dict] = []
    for dtype, idxs in by_dtype.items():
        sizes = [int(np.prod(leaves[i].shape)) * dtype.itemsize
                 for i in idxs]
        for bucket in plan_buckets(sizes, threshold):
            members = [idxs[j] for j in bucket]
            with _trace.scope("hvd_tpu_wire_pack"):
                flats = [leaves[i].reshape(-1) for i in members]
                fused = (jnp.concatenate(flats) if len(flats) > 1
                         else flats[0])
                if prescale_factor != 1.0:
                    fused = fused * prescale_factor
            packed.append({
                "members": members,
                "fused": fused,
                "cols": [int(np.prod(leaves[i].shape)) for i in members],
                "bytes": sum(sizes[j] for j in bucket),
            })

    scheds: dict = {}
    if schedule is not None and groups is None and n is not None \
            and n > 1 and schedule.topo.size == n:
        # Topo schedules are defined on the global axis: a process-set
        # sub-reduction (groups) or a compiler built for a different
        # mesh width must fall back to the flat planner — executing a
        # whole-axis schedule there would sum across group boundaries.
        for bi, b in enumerate(packed):
            scheds[bi] = schedule.compile(b["bytes"])
        # Hierarchical buckets are single composite entries in the
        # emission order (kind "ar"); only the compiler's two_phase
        # buckets join the pipelined RS/AG interleave.
        flags = [scheds[bi].algo == "two_phase"
                 for bi in range(len(packed))]
    elif n is None or n <= 1:
        flags = [False] * len(packed)
    else:
        flags = _dispatch_two_phase_flags([b["bytes"] for b in packed], n,
                                          alpha_us, beta_gbps)
    order = plan_pipeline_order(flags, pipeline_depth)

    if _obs.enabled() and packed:
        # Trace-time plan record: the compiled program replays exactly
        # these collectives every step.
        exact = sum(b["bytes"] for b in packed)
        ratio = wire_ratio(compression,
                           max(jnp.asarray(leaves[0]).dtype.itemsize, 1))
        _obs.on_fusion_plan(
            "two_phase", bytes_on_wire=int(exact * ratio),
            buckets=len(packed), compression_ratio=ratio,
            est_cost_us=estimate_schedule_cost_us(
                [b["bytes"] for b in packed], flags, n or 1, alpha_us,
                beta_gbps))
    if scheds:
        from ..topo import schedule as _topo_sched_mod

        _topo_sched_mod.record_plans(
            scheds.values(), compression,
            jnp.asarray(leaves[0]).dtype.itemsize if leaves else 4,
            params=schedule.params)

    shards: dict = {}
    reduced: dict = {}
    for kind, bi in order:
        b = packed[bi]
        with _trace.scope(f"hvd_tpu_wire_bucket_{bi}"):
            if kind == "ar":
                sched = scheds.get(bi)
                if sched is not None:
                    from ..topo import schedule as _topo_sched

                    reduced[bi] = _topo_sched.execute_schedule(
                        b["fused"], sched, axis=axis, op=op,
                        compression=compression)
                else:
                    reduced[bi] = compression.spmd_allreduce(
                        b["fused"], op=op, axis=axis, groups=groups)
            elif kind == "rs":
                x = b["fused"]
                pad = (-x.size) % n
                if pad:
                    x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
                shards[bi] = compression.spmd_reducescatter(
                    x, op=op, axis=axis, groups=groups)
            else:  # "ag"
                full = compression.spmd_allgather(
                    shards.pop(bi), axis=axis, groups=groups)
                reduced[bi] = full[: b["fused"].size]

    with _trace.scope("hvd_tpu_wire_unpack"):
        for bi, b in enumerate(packed):
            r = reduced[bi]
            if postscale_factor != 1.0:
                r = r * postscale_factor
            offset = 0
            for i, ncols in zip(b["members"], b["cols"]):
                piece = jax.lax.dynamic_slice_in_dim(r, offset, ncols,
                                                     axis=0)
                out[i] = piece.reshape(leaves[i].shape)
                offset += ncols
    return out


# --- overlap-scheduled microbatch wire ---------------------------------------
# The gradient wire of the microbatch training path (optim.make_train_step
# with HVD_TPU_MICROBATCHES > 1): each microbatch's gradients ride one
# bucketed reduce-scatter pass (emitted while the NEXT microbatch's
# backward computes — the fused computation-collective overlap), shards
# accumulate across microbatches, and ONE deferred all-gather at the
# optimizer-update boundary rebuilds the full averaged gradient.

@dataclasses.dataclass(frozen=True)
class OverlapBucketPlan:
    """Static plan for the microbatch overlap wire, computed once at
    trace time from leaf shapes so the per-microbatch reduce-scatter and
    the boundary all-gather agree on layout.  ``order`` is the RS
    emission order (descending modeled wire cost —
    :func:`plan_overlap_priority`)."""

    members: Tuple[Tuple[int, ...], ...]    # leaf indices per bucket
    cols: Tuple[Tuple[int, ...], ...]       # flat elems per member
    payload: Tuple[int, ...]                # bucket elems before padding
    pad: Tuple[int, ...]                    # zero elems appended per bucket
    shard_elems: Tuple[int, ...]            # (payload+pad)/n per bucket
    dtypes: Tuple[Any, ...]                 # bucket dtype
    order: Tuple[int, ...]                  # RS emission order
    n: int                                  # reduction-group width


def plan_overlap_buckets(leaves: Sequence[jax.Array], threshold: int, *,
                         world_size: int,
                         alpha_us: float = DEFAULT_COST_ALPHA_US,
                         beta_gbps: float = DEFAULT_COST_BETA_GBPS,
                         ) -> OverlapBucketPlan:
    """Bucket a gradient pytree's leaves for the overlap wire: greedy
    byte-bounded buckets per dtype class (``plan_buckets``), padded to
    the group width, emitted in descending wire-cost order.  Pure
    bookkeeping on static shapes — every rank computes the identical
    plan."""
    n = max(1, int(world_size))
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)
    members: List[Tuple[int, ...]] = []
    cols: List[Tuple[int, ...]] = []
    payload: List[int] = []
    pad: List[int] = []
    dtypes: List[Any] = []
    bucket_bytes: List[int] = []
    for dtype, idxs in by_dtype.items():
        sizes = [int(np.prod(leaves[i].shape)) * dtype.itemsize
                 for i in idxs]
        for bucket in plan_buckets(sizes, threshold):
            mem = tuple(idxs[j] for j in bucket)
            c = tuple(int(np.prod(leaves[i].shape)) for i in mem)
            elems = sum(c)
            members.append(mem)
            cols.append(c)
            payload.append(elems)
            pad.append((-elems) % n)
            dtypes.append(dtype)
            bucket_bytes.append(sum(sizes[j] for j in bucket))
    order = plan_overlap_priority(bucket_bytes, n, alpha_us, beta_gbps)
    return OverlapBucketPlan(
        members=tuple(members), cols=tuple(cols), payload=tuple(payload),
        pad=tuple(pad),
        shard_elems=tuple((p + q) // n for p, q in zip(payload, pad)),
        dtypes=tuple(dtypes), order=tuple(order), n=n,
    )


def zero_overlap_shards(plan: OverlapBucketPlan) -> Tuple[jax.Array, ...]:
    """Zero-initialized per-bucket shard accumulators (the scan carry of
    the microbatch loop)."""
    return tuple(jnp.zeros((e,), dt)
                 for e, dt in zip(plan.shard_elems, plan.dtypes))


def _overlap_bucket_schedule(plan: OverlapBucketPlan, bi: int, topo):
    """Compiled schedule for one overlap bucket, or None for the flat
    wire.  The compile keys off the bucket's exact payload bytes — the
    same coordinate the fused paths use — so the per-bucket choice is
    identical everywhere a bucket's bytes appear."""
    if topo is None:
        return None
    if topo.topo.size != plan.n:
        return None   # topology describes a different mesh than this wire
    nbytes = plan.payload[bi] * np.dtype(plan.dtypes[bi]).itemsize
    sched = topo.compile(int(nbytes))
    return sched if sched.algo == "hierarchical" else None


def overlap_reduce_scatter(leaves: Sequence[jax.Array],
                           plan: OverlapBucketPlan, *, axis: str, op: str,
                           groups, compression,
                           topo=None) -> Tuple[jax.Array, ...]:
    """One bucketed reduce-scatter pass over ``leaves`` (one
    microbatch's gradients): each bucket is flattened, padded to the
    group width and reduce-scattered on the compressor's wire, emitted
    in ``plan.order`` so the most expensive collectives are issued
    first.  Returns per-bucket shards in bucket-index order.  Must run
    inside an SPMD region over ``axis``.

    ``topo`` (a ``topo.schedule.ScheduleCompiler``) lowers buckets the
    two-tier compiler marks hierarchical through RS-intra (ICI) →
    cross-pod RS (DCN): shards come back pod-major-permuted but the
    same size, and :func:`overlap_all_gather` with the same compiler
    inverts the permutation — flat-equivalent end to end."""
    shards: List[jax.Array] = [None] * len(plan.members)  # type: ignore
    for bi in plan.order:
        with _trace.scope("hvd_tpu_wire_pack"):
            flats = [leaves[i].reshape(-1) for i in plan.members[bi]]
            fused = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
            if plan.pad[bi]:
                fused = jnp.concatenate(
                    [fused, jnp.zeros((plan.pad[bi],), fused.dtype)])
        sched = _overlap_bucket_schedule(plan, bi, topo)
        with _trace.scope(f"hvd_tpu_wire_bucket_{bi}"):
            if sched is not None:
                from ..topo import schedule as _topo_sched_mod

                shards[bi] = _topo_sched_mod.hierarchical_reduce_scatter(
                    fused, sched, axis=axis, op=op,
                    compression=compression)
            else:
                shards[bi] = compression.spmd_reducescatter(
                    fused, op=op, axis=axis, groups=groups)
    return tuple(shards)


def overlap_all_gather(shards: Sequence[jax.Array],
                       plan: OverlapBucketPlan,
                       leaves_like: Sequence[jax.Array], *, axis: str,
                       groups, compression, topo=None) -> List[jax.Array]:
    """The deferred all-gather phase at the optimizer-update boundary:
    gather each bucket's accumulated shard on the compressor's wire,
    drop the padding and unpack to the leaf shapes of ``leaves_like``.
    Must run inside an SPMD region over ``axis``.  ``topo`` must match
    the :func:`overlap_reduce_scatter` call that produced the shards —
    hierarchical buckets gather cross-pod then intra-pod, inverting the
    RS permutation."""
    out: List[jax.Array] = [None] * len(leaves_like)  # type: ignore
    for bi, shard in enumerate(shards):
        sched = _overlap_bucket_schedule(plan, bi, topo)
        with _trace.scope(f"hvd_tpu_wire_bucket_{bi}"):
            if sched is not None:
                from ..topo import schedule as _topo_sched_mod

                full = _topo_sched_mod.hierarchical_all_gather(
                    shard, sched, axis=axis, compression=compression)
            else:
                full = compression.spmd_allgather(shard, axis=axis,
                                                  groups=groups)
        with _trace.scope("hvd_tpu_wire_unpack"):
            full = full[: plan.payload[bi]]
            offset = 0
            for i, ncols in zip(plan.members[bi], plan.cols[bi]):
                piece = jax.lax.dynamic_slice_in_dim(full, offset, ncols,
                                                     axis=0)
                out[i] = piece.reshape(leaves_like[i].shape).astype(
                    leaves_like[i].dtype)
                offset += ncols
    return out


def fused_allreduce_pytree(
    tree: Any,
    *,
    axis: str = "hvd",
    op: str = "average",
    threshold: int = 64 * 1024 * 1024,
    groups=None,
    compression=None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    two_phase: Optional[bool] = None,
    pipeline_depth: Optional[int] = None,
    topo_schedule=None,
) -> Any:
    """Fused allreduce of every leaf of a pytree — the gradient hot path
    (reference: fused ``ncclAllReduce`` over the fusion buffer).

    Must run inside an SPMD region (``shard_map``) over ``axis``.

    ``two_phase``/``pipeline_depth`` default to the live config
    (``HVD_TPU_TWO_PHASE_ALLREDUCE`` / ``HVD_TPU_PIPELINE_DEPTH``) at
    trace time, so the autotuner can flip them at a re-jit boundary.
    When on, bandwidth-bound buckets ride the pipelined reduce-scatter +
    all-gather schedule of :func:`fused_two_phase_apply`.

    ``topo_schedule`` (a ``topo.schedule.ScheduleCompiler``, or None to
    resolve ``HVD_TPU_TOPO_SCHEDULE`` at trace time — the autotuner's
    topo application point) lowers each bucket through the two-tier
    schedule compiler instead of the flat α–β planner: per bucket, flat
    allreduce, global RS+AG, or hierarchical RS-intra → cross-pod
    exchange → AG-intra, chosen by the per-tier cost model
    (docs/topology.md).
    """
    from .compression import Compression

    compression = compression or Compression.none
    leaves, treedef = jax.tree.flatten(tree)

    alpha_us, beta_gbps = DEFAULT_COST_ALPHA_US, DEFAULT_COST_BETA_GBPS
    from .. import basics

    if basics.is_initialized():
        cfg = basics.config()
        if two_phase is None:
            two_phase = cfg.two_phase_allreduce
        if pipeline_depth is None:
            pipeline_depth = cfg.pipeline_depth
        alpha_us, beta_gbps = cfg.cost_alpha_us, cfg.cost_beta_gbps
    two_phase = bool(two_phase) if two_phase is not None else False
    pipeline_depth = int(pipeline_depth) if pipeline_depth else 2

    compiler = topo_schedule
    if compiler is None and op in ("sum", "average") and leaves:
        from ..topo import schedule as _topo_sched_mod

        n = _uniform_group_width(axis, groups)
        if n is not None:
            compiler = _topo_sched_mod.maybe_compiler(n, groups=groups)

    if two_phase or compiler is not None:
        reduced = fused_two_phase_apply(
            leaves, axis=axis, op=op, groups=groups,
            compression=compression, threshold=threshold,
            pipeline_depth=pipeline_depth, alpha_us=alpha_us,
            beta_gbps=beta_gbps, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, schedule=compiler)
        return jax.tree.unflatten(treedef, reduced)

    if _obs.enabled() and leaves:
        by_dtype: dict = {}
        for leaf in leaves:
            dt = jnp.asarray(leaf).dtype
            by_dtype.setdefault(dt, []).append(
                int(np.prod(leaf.shape)) * dt.itemsize)
        exact = sum(sum(sizes) for sizes in by_dtype.values())
        ratio = wire_ratio(compression,
                           max(jnp.asarray(leaves[0]).dtype.itemsize, 1))
        _obs.on_fusion_plan(
            "spmd", bytes_on_wire=int(exact * ratio),
            buckets=sum(len(plan_buckets(sizes, threshold))
                        for sizes in by_dtype.values()),
            compression_ratio=ratio)

    def collective(flat: jax.Array) -> jax.Array:
        x = flat
        if prescale_factor != 1.0:
            x = x * prescale_factor
        # The compressor owns the transport (Compressor.spmd_allreduce:
        # compress -> HLO -> decompress by default; int8 overrides with
        # its quantized alltoall/allgather decomposition).
        x = compression.spmd_allreduce(x, op=op, axis=axis, groups=groups)
        if postscale_factor != 1.0:
            x = x * postscale_factor
        return x

    reduced = fused_apply(leaves, collective, threshold)
    return jax.tree.unflatten(treedef, reduced)
