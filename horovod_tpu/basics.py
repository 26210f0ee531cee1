"""Process model: init / shutdown / rank / size / local_rank / cross_rank.

Mirrors the reference's ``HorovodBasics`` Python façade over the C core
(``horovod/common/basics.py`` + ``horovod_init`` in
``horovod/common/operations.cc`` — paths per SURVEY.md §2.1/§2.4, reference
mount empty, unverified).

TPU-native redesign
-------------------
The reference starts a C++ background coordinator thread per process and
bootstraps an MPI/Gloo controller.  On TPU none of that machinery is needed:

* **Process bootstrap** is ``jax.distributed.initialize()`` (coordination
  service over DCN) — replacing mpirun/Gloo-HTTP rendezvous.
* **Slot model:** the reference runs one *process per accelerator*; a JAX
  controller process may own many chips.  We therefore distinguish

  - ``size()``      — number of *slots* (= global device count).  This is
    the world size every collective reduces over, matching the reference's
    one-GPU-per-rank worldview.
  - ``rank()``      — the calling process's *first* slot index.  Inside an
    SPMD region each slot observes its own rank via
    :func:`horovod_tpu.ops.rank` (``lax.axis_index``).
  - ``local_size()``/``local_rank()`` — slots on this host / first local slot.
  - ``cross_size()``/``cross_rank()`` — number of controller processes /
    this process's index (the reference defines cross_* per-host; on TPU
    host == controller process).

* **The coordinator thread is gone.**  XLA's SPMD compilation already
  guarantees what the reference's rank-0 consensus protocol establishes at
  runtime — that every rank executes the same collectives in the same
  order.  The response cache is subsumed by jit tracing (same graph every
  step); the background cycle loop by XLA's static schedule.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional

import jax
import numpy as np

from .config import Config
from .utils.logging import get_logger

logger = get_logger(__name__)


class NotInitializedError(RuntimeError):
    """Raised when the API is used before :func:`init` (reference raises
    ``ValueError('Horovod has not been initialized; use hvd.init()')``)."""

    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first."
        )


class _GlobalState:
    """Singleton runtime state (reference: ``HorovodGlobalState`` in
    ``horovod/common/global_state.h``, unverified)."""

    def __init__(self) -> None:
        self.initialized: bool = False   # guarded-by: lock
        self.config: Optional[Config] = None   # guarded-by: lock
        self.mesh = None            # guarded-by: lock (horovod_tpu.mesh.GlobalMesh)
        self.mesh_plan = None       # guarded-by: lock (plan.MeshPlan — the session parallelism plan)
        self.layout_lattice = None  # guarded-by: lock (autotune layout specs; index 1 = the live plan)
        self.process_sets = None    # guarded-by: lock (process_sets.ProcessSetTable)
        self.timeline = None        # guarded-by: lock (utils.timeline.Timeline)
        self.stall_inspector = None  # guarded-by: lock
        self.cross_monitor = None   # guarded-by: lock (utils.cross_stall, multi-process)
        self.parameter_manager = None   # guarded-by: lock
        self.metrics_port = None    # guarded-by: lock (bound HVD_TPU_METRICS_PORT)
        # RLock: the locked read accessors below (_require/peek) are
        # reachable from helpers that init()/autotune apply paths call
        # while already holding the lock.
        self.lock = threading.RLock()


_state = _GlobalState()


def _maybe_init_distributed() -> None:
    """Bring up the multi-process coordination service when launched by
    ``horovodrun``-style tooling (env contract) or a cloud TPU pod.

    Replaces the reference's MPI_Init / Gloo HTTP-KV rendezvous
    (``horovod/common/gloo/gloo_context.cc``, unverified).
    """
    coordinator = os.environ.get("HVD_TPU_COORDINATOR_ADDR")
    num_processes = os.environ.get("HVD_TPU_NUM_PROCESSES")
    process_id = os.environ.get("HVD_TPU_PROCESS_ID")
    if process_id is None:
        # Scheduler launches (jsrun/srun — runner/lsf.py) don't stamp a
        # per-task id; the job-step manager's own rank env carries it.
        for var in ("PMIX_RANK", "OMPI_COMM_WORLD_RANK", "SLURM_PROCID"):
            if var in os.environ:
                process_id = os.environ[var]
                break
    if not (coordinator and num_processes and int(num_processes) > 1):
        return
    if process_id is None:
        # N tasks all claiming rank 0 would hang in rendezvous with no
        # clue; fail loudly naming the contract instead.
        raise RuntimeError(
            f"HVD_TPU_NUM_PROCESSES={num_processes} but no per-task rank "
            "was found: set HVD_TPU_PROCESS_ID, or launch through a "
            "job-step manager that exports PMIX_RANK / "
            "OMPI_COMM_WORLD_RANK / SLURM_PROCID")
    # NOTE: jax.distributed.initialize must run before anything touches a
    # backend (jax.devices()/process_count() would initialize XLA and make
    # it fail), so detect "already initialized" via the distributed client
    # state, not via backend queries.
    from jax._src import distributed as _jd

    if getattr(_jd.global_state, "client", None) is not None:
        return  # already initialized by the platform or the user
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    logger.info(
        "jax.distributed initialized: process %d/%s via %s",
        int(process_id), num_processes, coordinator,
    )


def _per_process_path(path: Optional[str]) -> Optional[str]:
    """One observability writer per file: every controller process opens
    its configured path with mode "w", so a shared path in a
    multi-process world would truncate/interleave.  Suffixing here — in
    the library, not in any launcher — covers every launch path (local
    spawn, remote agents, LSF, a plain exported env var).  Process 0
    keeps the exact path: the reference's one-file contract, and in
    SPMD every controller dispatches the same programs, so process 0 is
    representative."""
    if path and jax.process_index() > 0:
        return f"{path}.rank{jax.process_index()}"
    return path


def init(config: Optional[Config] = None) -> None:
    """Initialize the framework (reference: ``hvd.init()``).

    Idempotent, like the reference.  Accepts an explicit :class:`Config`
    for tests; otherwise reads the environment.
    """
    from . import process_sets as _ps
    from .mesh import GlobalMesh
    from .utils.timeline import Timeline
    from .utils.stall import StallInspector

    with _state.lock:
        if _state.initialized:
            return
        _maybe_init_distributed()
        cfg = config or Config.from_env()
        from .config import warn_noop_knobs

        warn_noop_knobs(logger)
        from .utils.logging import set_level

        set_level(cfg.log_level)
        if cfg.fault_spec:
            from . import faults

            # Arm the fault plan once per spec: an elastic re-init
            # (shutdown+init mid-recovery) must NOT restart the armed
            # plan's counters/history — the failure sequence spans the
            # process, or a step fault could re-fire on every reset.
            if faults.active_spec() != cfg.fault_spec:
                faults.configure(cfg.fault_spec)
        _apply_cache_capacity(cfg.cache_capacity)
        _state.config = cfg
        _state.mesh = GlobalMesh.build(axis_name=cfg.mesh_axis_name)
        _state.process_sets = _ps.ProcessSetTable(_state.mesh)
        # The session parallelism plan (docs/mesh_plan.md): unset knob →
        # the 1-D default plan wrapping the global mesh (bit-identical
        # legacy wiring); a declared HVD_TPU_MESH_PLAN builds the named
        # layout and registers one process set per axis group.
        from . import plan as _plan

        _state.mesh_plan = _plan.compile_plan(cfg.mesh_plan)
        _state.mesh_plan.register_process_sets(_state.process_sets)
        _state.timeline = Timeline(_per_process_path(cfg.timeline),
                                   mark_cycles=cfg.timeline_mark_cycles)
        _state.stall_inspector = StallInspector(
            enabled=not cfg.stall_check_disable,
            warn_after_s=cfg.stall_check_time_seconds,
            shutdown_after_s=cfg.stall_shutdown_time_seconds,
        )
        # Telemetry gate + optional local scrape port.  The registry is
        # NOT reset here: like the fault plan above, counters span the
        # process across elastic re-inits so rates stay meaningful.
        from .obs import flight as _obs_flight
        from .obs import metrics as _obs_metrics
        from .obs import trace as _obs_trace

        _obs_metrics.configure(enabled=cfg.metrics,
                               window=cfg.metrics_window)
        # Tracing + flight recorder: pin the lazy env gates to the
        # resolved Config; like the metrics registry, the span/event
        # rings are NOT cleared across elastic re-inits.
        _obs_trace.configure(enabled=cfg.trace, ring=cfg.trace_ring,
                             rank=jax.process_index(),
                             timeline=_state.timeline)
        _obs_flight.configure(enabled=cfg.flight,
                              directory=cfg.flight_dir,
                              ring=cfg.flight_ring)
        if cfg.metrics and cfg.metrics_port > 0:
            from .obs import export as _obs_export

            # One exporter per controller process; peers offset the
            # configured port by their process index so a multi-process
            # host exposes every rank.
            _state.metrics_port = _obs_export.start_http_exporter(
                cfg.metrics_port + jax.process_index())
        _state.parameter_manager = _maybe_build_parameter_manager(cfg)
        _state.initialized = True
        _state.cross_monitor = _maybe_start_cross_monitor(cfg)
        logger.info(
            "horovod_tpu initialized: %d slot(s) on %d process(es), platform=%s",
            _state.mesh.size, jax.process_count(), jax.default_backend(),
        )


_default_cache_sizes: dict = {}


def _apply_cache_capacity(capacity: Optional[int]) -> None:
    """``HOROVOD_CACHE_CAPACITY`` bounds the compiled-collective
    dispatch caches — the role the reference's response cache capacity
    plays for its negotiated-response LRU (``response_cache.cc``,
    SURVEY.md §2.1, mount empty).  Unset (None): each dispatch cache
    keeps its per-op tuned size (restored across re-inits); any explicit
    value rebinds them all to the requested capacity."""
    import functools

    from .ops import collectives as _c

    if capacity is not None and capacity <= 0:
        # The reference's CACHE_CAPACITY=0 disables its negotiation
        # response cache; here the "cache" holds compiled XLA programs,
        # and maxsize<=0 would re-trace+recompile every collective call.
        logger.warning(
            "HOROVOD_CACHE_CAPACITY=%d would recompile every collective "
            "on TPU (the cache holds compiled XLA programs, not "
            "negotiation responses); keeping the default capacities",
            capacity)
        capacity = None
    for name in ("_allreduce_fn", "_grouped_allreduce_fn", "_allgather_fn",
                 "_broadcast_fn", "_alltoall_fn", "_reducescatter_fn",
                 "_grouped_reducescatter_fn"):
        fn = getattr(_c, name)
        wrapped = getattr(fn, "__wrapped__", None)
        if wrapped is None:
            continue
        current = fn.cache_info().maxsize
        default = _default_cache_sizes.setdefault(name, current)
        target = default if capacity is None else capacity
        if target != current:
            setattr(_c, name,
                    functools.lru_cache(maxsize=target)(wrapped))


def _maybe_build_parameter_manager(cfg):
    """``HOROVOD_AUTOTUNE=1`` → construct the online knob tuner
    (reference: ``ParameterManager`` in the background thread,
    ``parameter_manager.cc`` per SURVEY.md §2.1, mount empty).

    The reference tunes (fusion threshold, cycle time) JOINTLY via
    Bayesian optimization.  The TPU surface has no cycle time, but it
    has a second trace-time wire knob with the same shape: the
    hierarchical-allreduce inner width (ICI-block size of the two-level
    reduction).  With ``HOROVOD_HIERARCHICAL_ALLREDUCE=1`` in a world
    of >= 4 slots the GP therefore searches 2-D
    (fusion_threshold x hierarchical_inner_size); otherwise it tunes
    the threshold alone.  With ``HVD_TPU_TWO_PHASE_ALLREDUCE=1`` the
    search additionally spans the two-phase wire knobs: ``two_phase``
    (a 1/2-valued on/off axis — the GP is free to discover that the
    monolithic allreduce wins) and ``pipeline_depth`` (buckets in
    flight, snapped to an integer in [1, 8]).  With
    ``HVD_TPU_MICROBATCHES>1`` the search spans the overlap-scheduled
    microbatch knobs jointly: ``microbatches`` (snapped to a power of
    two; the train step further snaps to a divisor of the per-slot
    batch at trace time) and ``overlap`` (1/2 on/off — exposing the
    wire after the last gradient can win for latency-bound models).
    With ``HVD_TPU_ERROR_FEEDBACK=1`` the ``compressor`` axis joins
    (1..4 → none/fp16/bf16/int8): on the EF-carrying paths
    (DistributedOptimizer / make_zero_train_step) the residual keeps
    lossy tiers unbiased, so the tuner may trade quantization noise for
    wire time; a plain make_train_step reduce has no residual state and
    warns once when a config-driven lossy tier lands on it.
    With ``HVD_TPU_TOPO_SCHEDULE`` on (any value but ``off``) over a
    genuinely two-tier mesh, the ``topo_schedule`` axis joins (1..3 =
    flat/two_phase/hierarchical — docs/topology.md): the per-tier cost
    model proposes, the GP disposes.  Whenever topo scheduling is on
    (any mesh) the ``topo_kernel`` axis joins too (1..2 = spmd/pallas
    — docs/fused_collectives.md): fused vs unfused lowering per bucket
    set.
    All knobs are applied at the re-jit boundary (the next-cycle
    application point of the reference); see ``optim/autotune.py`` and
    ``_apply_autotuned_knobs``."""
    if not cfg.autotune:
        return None
    import dataclasses

    from .optim.parameter_manager import ParameterManager

    lo, hi = 1 << 20, 1 << 28
    knobs = {"fusion_threshold": (lo, hi)}
    initial = {}
    size = _state.mesh.size if _state.mesh is not None else 1
    joint = cfg.hierarchical_allreduce and size >= 4
    joint_two_phase = cfg.two_phase_allreduce and size > 1
    if joint_two_phase:
        # On/off rides the same log2 machinery as every other knob:
        # points round to 1 (off) or 2 (on); proposals snap at the
        # apply boundary like the hierarchical inner width does.
        knobs["two_phase"] = (1, 2)
        initial["two_phase"] = 2
        knobs["pipeline_depth"] = (1, _MAX_PIPELINE_DEPTH)
        initial["pipeline_depth"] = min(max(1, cfg.pipeline_depth),
                                        _MAX_PIPELINE_DEPTH)
    joint_microbatch = cfg.microbatches > 1 and size > 1
    if joint_microbatch:
        # Power-of-two lattice up to _MAX_MICROBATCHES; the user's
        # configured count seeds the start point (clamped onto the
        # lattice — scores must attribute to what the job runs).
        knobs["microbatches"] = (1, _MAX_MICROBATCHES)
        initial["microbatches"] = _nearest_pow2(
            min(max(1, cfg.microbatches), _MAX_MICROBATCHES))
        knobs["overlap"] = (1, 2)
        initial["overlap"] = 2 if cfg.overlap_reduce else 1
    if cfg.error_feedback and size > 1:
        # Lossy tiers are safe under the EF residual, so the wire dtype
        # becomes a legitimate search axis (1..4 = none/fp16/bf16/int8).
        knobs["compressor"] = (1, len(_COMPRESSOR_LATTICE))
        live_comp = cfg.compression or "none"
        initial["compressor"] = _COMPRESSOR_LATTICE.index(live_comp) + 1
    if cfg.topo_schedule != "off" and size > 1:
        # Topology-aware schedule axis (1..3 = flat/two_phase/
        # hierarchical): the cost model's choice ("auto") seeds the
        # search, and the GP is free to discover the model's priors are
        # wrong for this job — its winner pins the schedule explicitly.
        # Resolve from the cfg in hand, not config_topology(): the
        # manager builds before _state.initialized flips, so trace-time
        # helpers can't see the declared spec yet.
        from .topo.topology import MeshTopology, resolve_topology

        try:
            topo = resolve_topology(size, cfg.topo_spec)
        except ValueError:
            topo = MeshTopology(pods=1, chips_per_pod=size)
        if topo.two_tier:
            knobs["topo_schedule"] = (1, len(_TOPO_LATTICE))
            live_topo = cfg.topo_schedule
            initial["topo_schedule"] = (
                _TOPO_LATTICE.index(live_topo) + 1
                if live_topo in _TOPO_LATTICE
                else len(_TOPO_LATTICE))   # auto seeds at hierarchical
        # Lowering-backend axis (1..2 = spmd/pallas): fused vs unfused
        # per bucket set is a legitimate GP discovery — the fused
        # kernels win on HBM-bound buckets and tie elsewhere (bit-
        # identical wire either way).  Not gated on two_tier: flat and
        # two-phase schedules on a one-pod mesh ride the ICI tier, and
        # those steps fuse too (docs/fused_collectives.md).
        knobs["topo_kernel"] = (1, len(_KERNEL_LATTICE))
        initial["topo_kernel"] = (
            _KERNEL_LATTICE.index(cfg.topo_kernel) + 1
            if cfg.topo_kernel in _KERNEL_LATTICE else 1)
    if cfg.mesh_plan is not None and size > 1:
        # Layout search (docs/mesh_plan.md): with a declared plan the
        # GP also searches 2-D DP×FSDP splits of the same world — index
        # 1 is the LIVE layout (scores attribute to what the job runs),
        # later indices the progressively deeper fsdp splits from
        # plan.layout_lattice.  Applied at the re-jit boundary like
        # every other trace-time knob: the plan (and its mesh) rebuild,
        # and the step factory re-resolves them on the next trace.
        from . import plan as _plan

        layouts = _plan.layout_lattice(size)
        if cfg.mesh_plan in layouts:
            layouts.remove(cfg.mesh_plan)
        layouts = [cfg.mesh_plan] + layouts
        if len(layouts) > 1:
            knobs["layout"] = (1, len(layouts))
            initial["layout"] = 1
            _state.layout_lattice = layouts  # hvdlint: disable=unguarded-mutation -- runs under init()'s `with _state.lock:` (sole caller)
    if joint:
        # log2 search over [1, size]; proposals snap to the nearest
        # divisor of the slot count (1 and size both mean "flat"
        # — turning hierarchy OFF is a legitimate point to discover).
        knobs["hierarchical_inner_size"] = (1, size)
        live_inner = cfg.hierarchical_inner_size
        if not 1 <= live_inner <= size:
            live_inner = max(1, size // 2)
        # Snap BEFORE seeding: scores are attributed to the manager's
        # start point, so it must be the width the job actually runs
        # (a non-divisor like INNER=3 on 8 slots would otherwise seed
        # the GP at a point that never executes).
        initial["hierarchical_inner_size"] = _nearest_divisor(
            live_inner, size)
    # Scores are attributed to the manager's current point — seed it
    # with the threshold the first windows will actually run.  A live
    # value outside the search space (e.g. HOROVOD_FUSION_THRESHOLD=0,
    # the reference's fusion-off setting) can't seed it; the tuner's
    # start point becomes the live value instead — autotune overriding
    # a manual threshold is its purpose.
    seedable = lo <= cfg.fusion_threshold <= hi
    if seedable:
        initial["fusion_threshold"] = cfg.fusion_threshold
    pm = ParameterManager(
        knobs=knobs,
        warmup_samples=cfg.autotune_warmup_samples,
        steps_per_sample=cfg.autotune_steps_per_sample,
        max_samples=cfg.autotune_max_samples,
        # Only the decision rank writes samples (proposals are rank-0
        # broadcast); a non-zero rank opening the shared path with
        # mode "w" would truncate the real log.
        log_path=cfg.autotune_log if jax.process_index() == 0 else None,
        initial=initial or None,
    )
    start_vals = pm.current_values()
    if not seedable:
        start = int(start_vals["fusion_threshold"])
        logger.warning(
            "HOROVOD_AUTOTUNE=1 overrides fusion_threshold=%d (outside "
            "the tunable range [%d, %d]): starting from %d",
            cfg.fusion_threshold, lo, hi, start)
        _state.config = dataclasses.replace(  # hvdlint: disable=unguarded-mutation -- runs under init()'s `with _state.lock:` (sole caller)
            _state.config, fusion_threshold=start)
    if joint:
        # The manager's start point must equal the live config (scores
        # are attributed to it): snap and store.
        start_inner = _nearest_divisor(
            int(round(start_vals["hierarchical_inner_size"])), size)
        _state.config = dataclasses.replace(  # hvdlint: disable=unguarded-mutation -- runs under init()'s `with _state.lock:` (sole caller)
            _state.config, hierarchical_inner_size=start_inner)
    if joint_two_phase:
        # Same invariant for the two-phase knobs: the live config must
        # equal the clamped start point the first windows run.
        _state.config = dataclasses.replace(  # hvdlint: disable=unguarded-mutation -- runs under init()'s `with _state.lock:` (sole caller)
            _state.config,
            pipeline_depth=int(round(start_vals["pipeline_depth"])))
    if joint_microbatch:
        _state.config = dataclasses.replace(  # hvdlint: disable=unguarded-mutation -- runs under init()'s `with _state.lock:` (sole caller)
            _state.config,
            microbatches=_nearest_pow2(int(round(
                start_vals["microbatches"]))),
            overlap_reduce=start_vals["overlap"] >= 1.5)
    if "compressor" in knobs:
        idx = min(max(1, int(round(start_vals["compressor"]))),
                  len(_COMPRESSOR_LATTICE))
        _state.config = dataclasses.replace(  # hvdlint: disable=unguarded-mutation -- runs under init()'s `with _state.lock:` (sole caller)
            _state.config, compression=_COMPRESSOR_LATTICE[idx - 1])
    logger.info(
        "autotune enabled: tuning %s, %d warmup + %d scored windows "
        "of %d steps%s",
        " x ".join(pm.knob_names),
        cfg.autotune_warmup_samples, cfg.autotune_max_samples,
        cfg.autotune_steps_per_sample,
        f", log={cfg.autotune_log}" if cfg.autotune_log else "")
    return pm


# Pipeline-depth search ceiling: past ~8 buckets in flight the transient
# shard buffers outweigh any remaining overlap.
_MAX_PIPELINE_DEPTH = 8

# Microbatch search ceiling: past 32-way accumulation the per-microbatch
# batch is too small to keep the MXU busy on any realistic config.
_MAX_MICROBATCHES = 32

# Compressor search lattice (index 1..4 on the GP's log2 machinery);
# names are Compression namespace attributes AND legal
# HVD_TPU_COMPRESSION values, so the applied point round-trips.
_COMPRESSOR_LATTICE = ("none", "fp16", "bf16", "int8")

# Topo-schedule search lattice (1..3; "auto" is the cost model deciding
# and is what the knob replaces, so it is not itself a search point).
_TOPO_LATTICE = ("flat", "two_phase", "hierarchical")

# Schedule-lowering backend lattice (1..2): the plain SPMD/HLO wire vs
# the fused Pallas quantize-collective kernels (config.TOPO_KERNELS
# order, so the applied point round-trips through HVD_TPU_TOPO_KERNEL).
_KERNEL_LATTICE = ("spmd", "pallas")


def _nearest_pow2(value: int) -> int:
    """Nearest power of two in log space (microbatch proposals must land
    on a lattice the per-slot batch has a chance of dividing)."""
    import math

    v = max(1, int(value))
    lo = 1 << (v.bit_length() - 1)
    hi = lo * 2
    return lo if abs(math.log2(v) - math.log2(lo)) <= \
        abs(math.log2(hi) - math.log2(v)) else hi


def _nearest_divisor(value: int, size: int) -> int:
    """The divisor of ``size`` nearest ``value`` in log space (the
    hierarchical inner width must tile the slot axis exactly)."""
    import math

    divisors = [d for d in range(1, size + 1) if size % d == 0]
    return min(divisors,
               key=lambda d: abs(math.log2(d) - math.log2(max(1, value))))


def parameter_manager():
    """The active autotuner, or None unless ``HOROVOD_AUTOTUNE=1``."""
    return _require("parameter_manager")


def _apply_autotuned_fusion_threshold(value: float) -> None:
    """Single-knob form of :func:`_apply_autotuned_knobs` (kept for
    compatibility with external callers/tests)."""
    _apply_autotuned_knobs({"fusion_threshold": value})


def _apply_autotuned_knobs(values) -> dict:
    """Apply an autotune proposal: swap the frozen Config for one with
    the new knob values.  Callers must rebuild (re-jit) their train
    step afterwards — trace-time reads of ``config()`` pick the new
    values up on the next trace.  Returns the values as actually
    applied, keyed by KNOB name (the hierarchical inner width snaps to
    the nearest divisor of the slot count; ``pipeline_depth`` snaps to
    an int in [1, 8]; ``two_phase``/``overlap`` snap to their 1=off /
    2=on lattices; ``microbatches`` snaps to a power of two;
    ``compressor`` snaps to the none/fp16/bf16/int8 lattice;
    ``topo_kernel`` snaps to the spmd/pallas lattice) —
    the caller re-points the manager at these, so keys must match
    ``pm.knob_names`` even where the Config field is spelled
    differently (``two_phase`` → ``two_phase_allreduce``)."""
    import dataclasses

    st = _require_init()
    updates = {}   # Config field names
    applied = {}   # knob names (ParameterManager space)
    if "fusion_threshold" in values:
        v = int(values["fusion_threshold"])
        updates["fusion_threshold"] = applied["fusion_threshold"] = v
    if "hierarchical_inner_size" in values:
        v = _nearest_divisor(
            int(round(values["hierarchical_inner_size"])), st.mesh.size)
        updates["hierarchical_inner_size"] = v
        applied["hierarchical_inner_size"] = v
    if "two_phase" in values:
        snapped = 2 if values["two_phase"] >= 1.5 else 1
        updates["two_phase_allreduce"] = snapped == 2
        applied["two_phase"] = snapped
    if "pipeline_depth" in values:
        v = min(max(1, int(round(values["pipeline_depth"]))),
                _MAX_PIPELINE_DEPTH)
        updates["pipeline_depth"] = applied["pipeline_depth"] = v
    if "microbatches" in values:
        v = min(_nearest_pow2(int(round(values["microbatches"]))),
                _MAX_MICROBATCHES)
        updates["microbatches"] = applied["microbatches"] = v
    if "overlap" in values:
        snapped = 2 if values["overlap"] >= 1.5 else 1
        updates["overlap_reduce"] = snapped == 2
        applied["overlap"] = snapped
    if "compressor" in values:
        idx = min(max(1, int(round(values["compressor"]))),
                  len(_COMPRESSOR_LATTICE))
        updates["compression"] = _COMPRESSOR_LATTICE[idx - 1]
        applied["compressor"] = idx
    if "topo_schedule" in values:
        idx = min(max(1, int(round(values["topo_schedule"]))),
                  len(_TOPO_LATTICE))
        updates["topo_schedule"] = _TOPO_LATTICE[idx - 1]
        applied["topo_schedule"] = idx
    if "topo_kernel" in values:
        idx = min(max(1, int(round(values["topo_kernel"]))),
                  len(_KERNEL_LATTICE))
        updates["topo_kernel"] = _KERNEL_LATTICE[idx - 1]
        applied["topo_kernel"] = idx
    if "layout" in values:
        with st.lock:
            layouts = st.layout_lattice
        if layouts:
            idx = min(max(1, int(round(values["layout"]))), len(layouts))
            updates["mesh_plan"] = layouts[idx - 1]
            applied["layout"] = idx
    # The swap races with concurrent trace-time config() readers
    # (serving threads, a re-jitting train step) — publish under the
    # state lock like every other _state mutation.
    with st.lock:
        relayout = "mesh_plan" in updates \
            and updates["mesh_plan"] != st.config.mesh_plan
        st.config = dataclasses.replace(st.config, **updates)
        if relayout:
            # A layout flip rebuilds the session plan (new mesh, new
            # axis process sets) — the caller's re-jit then re-resolves
            # mesh/axis/shardings from the fresh plan on its next trace.
            from . import plan as _plan
            from .obs import instrument as _obs

            st.mesh_plan = _plan.compile_plan(st.config.mesh_plan)
            st.mesh_plan.register_process_sets(st.process_sets)
            _obs.on_plan_relayout()
    return applied


def _maybe_start_cross_monitor(cfg):
    """Start the native-Coordinator stall/failure monitor in
    multi-controller worlds (reference: the rank-0 controller's
    cross-rank stall attribution; see utils/cross_stall.py).

    Fail-soft, with one hard rule: the ``broadcast_object`` port exchange
    is a *collective*, so every rank must reach it exactly once no matter
    what fails locally — a rank that skipped it would leave its peers
    blocked inside ``hvd.init``.  Local bootstrap failures therefore ship
    ``port = -1`` (rank 0) or ignore the received port (others); the only
    remaining asymmetric case — a peer whose Coordinator connect fails
    after a successful exchange — degrades via negotiate timeout, which
    self-disables every monitor without touching the data plane."""
    if jax.process_count() <= 1 or cfg.stall_check_disable \
            or not cfg.native_coordinator:
        return None
    from .functions import broadcast_object

    rank, nproc = jax.process_index(), jax.process_count()
    coord_addr = os.environ.get("HVD_TPU_COORDINATOR_ADDR", "")
    host = coord_addr.rsplit(":", 1)[0] if ":" in coord_addr else "127.0.0.1"
    coord = None
    port = -1
    if rank == 0:
        try:
            from .native import runtime as native

            if native.available():
                coord = native.Coordinator(
                    0, nproc, host=host, port=0,
                    fusion_threshold=cfg.fusion_threshold, timeout_s=30.0)
                port = coord.bound_port
        except Exception as e:
            logger.info("cross-process stall monitor unavailable: %s", e)
            coord = None
            port = -1
    try:
        port = int(broadcast_object(port if rank == 0 else None, root_rank=0))
    except Exception as e:
        logger.info("cross-process monitor port exchange failed: %s", e)
        port = -1
    if port < 0:
        if coord is not None:   # exchange failed after a successful bind
            try:
                coord.close()
            except Exception:
                pass
        return None
    if rank != 0:
        try:
            from .native import runtime as native

            if native.available():
                coord = native.Coordinator(
                    rank, nproc, host=host, port=port,
                    fusion_threshold=cfg.fusion_threshold, timeout_s=30.0)
        except Exception as e:
            logger.info("cross-process stall monitor unavailable: %s", e)
            coord = None
    if coord is None:
        return None
    from .utils.cross_stall import CrossProcessMonitor

    return CrossProcessMonitor(coord,
                               warn_after_s=cfg.stall_check_time_seconds)


def shutdown() -> None:
    """Tear down (reference: ``hvd.shutdown()`` → joins the background
    thread; here: flush the timeline, drop state)."""
    with _state.lock:
        if not _state.initialized:
            return
        if _state.timeline is not None:
            _state.timeline.close()
        if _state.stall_inspector is not None:
            _state.stall_inspector.stop()
        if _state.cross_monitor is not None:
            _state.cross_monitor.stop()
            _state.cross_monitor = None
        if _state.metrics_port is not None:
            from .obs import export as _obs_export

            _obs_export.stop_http_exporter()
            _state.metrics_port = None
        _state.initialized = False
        # Compiled-collective caches hold the old mesh; drop them so a
        # re-init (elastic restart, tests) rebuilds against the new mesh.
        from .ops import collectives as _c

        for fn in (_c._allreduce_fn, _c._grouped_allreduce_fn, _c._allgather_fn,
                   _c._broadcast_fn, _c._alltoall_fn, _c._reducescatter_fn,
                   _c._grouped_reducescatter_fn):
            fn.cache_clear()
        if _state.parameter_manager is not None:
            _state.parameter_manager.close()
        _state.mesh = None
        _state.mesh_plan = None
        _state.layout_lattice = None
        _state.process_sets = None
        _state.timeline = None
        _state.stall_inspector = None
        _state.parameter_manager = None
        from .obs import trace as _obs_trace

        _obs_trace.configure(rank=None, timeline=None)


atexit.register(shutdown)


def is_initialized() -> bool:
    """Reference: ``hvd.is_initialized()``.  Locked read: the flag is
    consulted from RPC handler and batcher threads while init/shutdown
    may be flipping it (hvdsan caught the lock-free version)."""
    with _state.lock:
        return _state.initialized


def _require_init() -> _GlobalState:
    with _state.lock:
        if not _state.initialized:
            raise NotInitializedError()
    return _state


def _require(attr: str):
    """Locked read of one initialized-state field — THE accessor the
    public API reads globals through, so every cross-thread read honors
    the `# guarded-by: lock` contract the sanitizer enforces."""
    with _state.lock:
        if not _state.initialized:
            raise NotInitializedError()
        return getattr(_state, attr)


def peek(attr: str):
    """Locked read of one global-state field, or None pre-init — the
    fail-soft accessor for observability paths (trace/instrument/
    engine timeline mirrors) that must work before and after init."""
    with _state.lock:
        return getattr(_state, attr, None)


def size() -> int:
    """World size in *slots* (accelerator chips) — the reduction width of
    every collective.  Reference: ``hvd.size()`` (one process per GPU)."""
    return _require("mesh").size


def rank() -> int:
    """This controller process's first slot index.  Reference:
    ``hvd.rank()``.  Per-slot rank inside SPMD code: ``ops.rank(axis)``."""
    return _require("mesh").process_first_slot


def local_size() -> int:
    """Slots attached to this process.  Reference: ``hvd.local_size()``."""
    return _require("mesh").local_size


def local_rank() -> int:
    """Index of this process's first slot among local slots — 0 unless
    several controller processes share a host.  Reference:
    ``hvd.local_rank()``."""
    return _require("mesh").local_rank


def cross_size() -> int:
    """Number of controller processes.  Reference: ``hvd.cross_size()``
    (number of hosts)."""
    _require_init()
    return jax.process_count()


def cross_rank() -> int:
    """This controller process's index.  Reference: ``hvd.cross_rank()``."""
    _require_init()
    return jax.process_index()


def is_homogeneous() -> bool:
    """True when every process drives the same number of slots.
    Reference: ``hvd.is_homogeneous()``."""
    st = _require_init()
    counts = st.mesh.slots_per_process
    return len(set(counts)) <= 1


# --- feature matrix (reference: hvd.mpi_built()/nccl_built()/… and
#     `horovodrun --check-build`) -------------------------------------------

def mpi_built() -> bool:
    """Always False: there is no MPI in the TPU stack."""
    return False


def nccl_built() -> int:
    """Always 0: collectives run as XLA HLO over ICI, not NCCL."""
    return 0


def gloo_built() -> bool:
    """Always False (see :func:`mpi_built`)."""
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def ddl_built() -> bool:
    """Always False (IBM DDL is a legacy GPU backend)."""
    return False


def xla_built() -> bool:
    """True: XLA *is* the collective backend here."""
    return True


def mpi_enabled() -> bool:
    """Reference: built-AND-enabled-at-runtime check; always False here."""
    return False


def gloo_enabled() -> bool:
    """Always False — honest matrix: enabled implies built, and no Gloo
    is built here.  The controller role belongs to `jax.distributed`;
    see :func:`xla_enabled`."""
    return False


def xla_enabled() -> bool:
    """The reference's 'some controller is enabled' invariant lands
    here: XLA collectives + `jax.distributed` rendezvous are always
    available."""
    return True


def mpi_threads_supported() -> bool:
    """Reference API parity; meaningless without MPI."""
    return False


def config() -> Config:
    """The resolved :class:`Config` (no reference analogue as an object;
    the reference exposes knobs only as env vars)."""
    return _require("config")


def global_mesh():
    """The framework-owned global 1-D device mesh (TPU-native concept;
    replaces the reference's global MPI/Gloo communicator)."""
    return _require("mesh")


def mesh_plan():
    """The session :class:`~horovod_tpu.plan.MeshPlan` — the single
    source of truth every parallelism entry point derives its axes,
    shardings, process sets and topo tiers from (docs/mesh_plan.md).
    Unset ``HVD_TPU_MESH_PLAN`` → the 1-D default plan over
    :func:`global_mesh`."""
    return _require("mesh_plan")


def apply_mesh_plan(spec):
    """Rebuild the session plan from an axis spec (``"data=4,fsdp=2"``;
    ``None`` restores the 1-D default) — the public relayout entry the
    benchmark layout sweep uses.  Steps built BEFORE the swap keep
    their traced wiring; rebuild them (or let the autotuner's re-jit do
    it) to pick up the new plan.  Returns the new plan."""
    import dataclasses

    from . import plan as _plan
    from .obs import instrument as _obs

    st = _require_init()
    plan = _plan.compile_plan(spec)
    with st.lock:
        st.config = dataclasses.replace(st.config, mesh_plan=spec)
        st.mesh_plan = plan
        plan.register_process_sets(st.process_sets)
    _obs.on_plan_relayout()
    return plan


def timeline():
    return _require("timeline")


def stall_inspector():
    return _require("stall_inspector")


def _retarget_span_mirror(timeline) -> None:
    """Finished spans are mirrored into the Timeline that obs/trace.py
    was last told of (it looks nothing up per span)."""
    from .obs import trace as _obs_trace

    _obs_trace.configure(timeline=timeline)


def start_timeline(path: str, mark_cycles: bool = False) -> None:
    """Reference: ``hvd.start_timeline()`` (dynamic timeline activation)."""
    from .utils.timeline import Timeline

    st = _require_init()
    with st.lock:
        if st.timeline is not None:
            st.timeline.close()
        st.timeline = Timeline(_per_process_path(path),
                               mark_cycles=mark_cycles)
        _retarget_span_mirror(st.timeline)


def stop_timeline() -> None:
    """Reference: ``hvd.stop_timeline()``."""
    st = _require_init()
    from .utils.timeline import Timeline

    with st.lock:
        if st.timeline is not None:
            st.timeline.close()
        st.timeline = Timeline(None)
        _retarget_span_mirror(st.timeline)
