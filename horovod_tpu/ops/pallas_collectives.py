"""Fused quantize-collective Pallas kernels: the int8/EF wire without
the HBM round-trip.

The int8 transport in :mod:`.quantization` is three separate HLO
regions around each collective: quantize (writes the int8 payload and
the f32 scale sidecar to HBM), the collective itself, and dequantize/
accumulate (reads the payload back, writes the f32 result).  On TPU
each region is its own HBM round-trip over the full bucket.  The fused
computation-collective line of work (arXiv:2305.06942) and EQuARX
(arXiv:2506.17615, PAPERS.md) both show that folding the quantize/
dequantize math into the kernels that feed and drain the wire recovers
most of the compression win that memory traffic eats.

This module is that tier, following the ``ops/pallas_attention.py``
pattern (grid + block specs + ``interpret=`` escape hatch via
:mod:`.pallas_common`):

* :func:`fused_quantize_reducescatter` — blocks the input, computes
  per-block int8 scales and packs **inside a Pallas kernel** whose
  outputs are the wire operands themselves, runs the quantized
  ``all_to_all``, and dequantize-accumulates the received shards in a
  second kernel — no standalone quantized intermediate in HBM.
* :func:`fused_quantize_allgather` — the AG half: quantize-pack kernel
  → quantized ``all_gather`` → fused dequantize kernel.
* :func:`fused_allgather_sgd_apply` / :func:`fused_allgather_adam_apply`
  — consume the gathered int8 shards and apply the SGD/Adam leaf update
  (the ``optim/distributed_optimizer.py`` optimizer semantics) in one
  pass: the full-precision gradient is never materialized.
* :func:`fused_matmul_allgather` — the FSDP unshard epilogue
  (``optim/fsdp.py``): matmul against the local weight shard with the
  all-gather moved AFTER the matmul, so the wire carries activations
  straight out of the kernel's epilogue instead of gathered weights.

Numerics contract (the tier-1 oracle, ``tests/test_pallas_collectives.py``):
in interpret mode every fused path is **bit-identical** to the
:mod:`.quantization` reference wire — same scales, same packed int8
payload, same error-feedback residuals — because the kernels perform
the exact op sequence of ``_quantize_blocks`` per block.  The
collectives themselves stay HLO (``spmd.alltoall``/``allgather``): XLA
cannot run a collective inside a user kernel, so the fusion win is the
*elimination of the quantize/dequantize HBM round-trips on either
side*, which the schedule tier accounts structurally
(``topo.schedule.CollectiveSchedule.hbm_materializations``).

Selected per schedule step by ``topo/schedule.py``'s ``kernel="pallas"``
backend (``HVD_TPU_TOPO_KERNEL``, autotunable — docs/fused_collectives.md).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import spmd
from .pallas_common import _SUBLANES, pad_dim, resolve_interpret, round_up
from .quantization import _EPS, _INV127, _group_size, wire_block_size

__all__ = [
    "quantize_blocks", "dequantize_blocks", "pallas_quant_dequant",
    "pallas_local_error", "fused_quantize_reducescatter",
    "fused_quantize_allgather", "fused_allreduce",
    "fused_allgather_sgd_apply", "fused_allgather_adam_apply",
    "fused_matmul_allgather",
]


# --- block quantize / dequantize kernels -------------------------------------

def _quant_kernel(x_ref, q_ref, s_ref):
    """One row-tile of blockwise symmetric int8 quantization — the
    exact op sequence of ``quantization._quantize_blocks`` so interpret
    mode is bit-identical to the reference wire."""
    blk = x_ref[...]                                     # [rt, b] f32
    scale = jnp.maximum(jnp.max(jnp.abs(blk), axis=-1) * _INV127, _EPS)
    q_ref[...] = jnp.clip(jnp.round(blk / scale[:, None]),
                          -127, 127).astype(jnp.int8)
    s_ref[...] = scale[:, None].astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref):
    """One row-tile of dequantization: ``q * scale`` in f32."""
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _dequant_accum_kernel(q_ref, s_ref, o_ref):
    """Dequantize-accumulate across the contributor axis: f32 sum of
    ``n`` int8 shards — same reduction as the reference's
    ``jnp.sum(rows * scales, axis=0)``, fused with the dequantize."""
    o_ref[...] = jnp.sum(
        q_ref[...].astype(jnp.float32) * s_ref[...], axis=0)


def _row_grid(rows: int, interpret: bool) -> Tuple[int, int]:
    """(padded_rows, row_tile) for a kernel gridded over independent
    block rows: tiles of ``_SUBLANES`` rows (zero-padded rows quantize
    to q=0 at the _EPS floor scale and are sliced off by the caller).
    Interpret mode (the CPU oracle/bench path) collapses the grid to a
    single whole-array tile: the interpreter costs per grid step, and
    every kernel here is row-wise (quantize, dequantize, leaf update),
    so the tile split is bitwise-invariant — the CPU wire pays one step
    while TPU keeps VMEM-sized tiles."""
    if interpret:
        rt = round_up(rows, _SUBLANES)
    else:
        rt = min(_SUBLANES, round_up(rows, _SUBLANES))
    return round_up(rows, rt), rt


def quantize_blocks(blocks, *, interpret: Optional[bool] = None):
    """Pallas twin of ``quantization._quantize_blocks`` for a 2-D
    ``[rows, b]`` block array: returns ``(int8 [rows, b], f32 scales
    [rows])``, bit-identical to the reference in interpret mode.  The
    packed payload and scale sidecar come straight out of the kernel —
    these ARE the wire operands, with no separate HBM materialization
    between quantize and collective."""
    interpret = resolve_interpret(interpret)
    rows, b = blocks.shape
    xp, _ = pad_dim(blocks.astype(jnp.float32), _SUBLANES, axis=0)
    rows_p, rt = _row_grid(rows, interpret)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rows_p // rt,),
        in_specs=[pl.BlockSpec((rt, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rt, b), lambda i: (i, 0)),
            # Trailing unit dim keeps the scale tile legal on TPU
            # (same trick as pallas_attention's lse output).
            pl.BlockSpec((rt, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, b), jnp.int8),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_tpu_quantize_blocks",
    )(xp)
    return q[:rows], s[:rows, 0]


def dequantize_blocks(q, scales, *, interpret: Optional[bool] = None):
    """Fused dequantize of ``[rows, b]`` int8 blocks with per-row
    scales: f32 ``q * scale``, the consumer-side half of the wire."""
    interpret = resolve_interpret(interpret)
    rows, b = q.shape
    qp, _ = pad_dim(q, _SUBLANES, axis=0)
    sp, _ = pad_dim(scales.reshape(-1, 1).astype(jnp.float32),
                    _SUBLANES, axis=0)
    rows_p, rt = _row_grid(rows, interpret)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows_p // rt,),
        in_specs=[
            pl.BlockSpec((rt, b), lambda i: (i, 0)),
            pl.BlockSpec((rt, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rt, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, b), jnp.float32),
        interpret=interpret,
        name="hvd_tpu_dequantize_blocks",
    )(qp, sp)
    return out[:rows]


def pallas_quant_dequant(x, block_size: int = 1024,
                         interpret: Optional[bool] = None):
    """Fused twin of ``quantization.quant_dequant`` — the local lossy-
    transport roundtrip whose complement is the error-feedback
    residual.  Bit-identical to the reference in interpret mode."""
    f32 = x.astype(jnp.float32).reshape(-1)
    b = max(1, min(block_size, f32.size)) if f32.size else 1
    pad = (-f32.size) % b
    if pad:
        f32 = jnp.concatenate([f32, jnp.zeros((pad,), jnp.float32)])
    q, scale = quantize_blocks(f32.reshape(-1, b), interpret=interpret)
    deq = dequantize_blocks(q, scale, interpret=interpret).reshape(-1)
    if pad:
        deq = deq[:-pad]
    return deq.reshape(x.shape).astype(x.dtype)


def pallas_local_error(x, block_size: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """Fused twin of ``Int8Compressor.local_error``: the EF residual
    ``x - quant_dequant(x)`` with the roundtrip on the Pallas kernels —
    bit-identical residuals, so a step that mixes backends keeps the
    EF contraction property."""
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return x - pallas_quant_dequant(x, block_size=block_size or 1024,
                                    interpret=interpret)


# --- fused quantize -> reduce-scatter ----------------------------------------

def fused_quantize_reducescatter(x, *, op: str = "sum", axis: str = "hvd",
                                 groups=None, block_size: int = 1024,
                                 interpret: Optional[bool] = None):
    """Fused twin of ``quantization.int8_reducescatter``: the quantize-
    pack Pallas kernel feeds the quantized ``all_to_all`` directly, and
    a dequantize-accumulate kernel drains it — phases 1–2 of the int8
    wire with no standalone quantized intermediate in HBM.  Same
    contract (flat vector, size divides the group width, returns this
    slot's reduced shard) and bit-identical results in interpret mode.
    """
    if op not in ("sum", "average"):
        raise ValueError(
            f"int8 transport supports op=sum/average, got {op!r} "
            "(min/max/product need exact comparisons; drop compression)")
    n = _group_size(axis, groups)
    flat = x.astype(jnp.float32).reshape(-1)
    if flat.size % n:
        raise ValueError(f"size {flat.size} not divisible by group {n}")
    if n == 1:
        return flat.astype(x.dtype)  # degenerate world
    k = flat.size // n
    b = max(1, min(block_size, k))
    pad = (-k) % b
    chunks = flat.reshape(n, k)
    if pad:  # pad each destination chunk's tail to whole blocks
        chunks = jnp.concatenate(
            [chunks, jnp.zeros((n, pad), jnp.float32)], axis=1)
    m = (k + pad) // b

    # Quantize-pack kernel: its outputs ARE the alltoall operands.
    q1, s1 = quantize_blocks(chunks.reshape(n * m, b), interpret=interpret)
    rows = spmd.alltoall(q1, axis=axis, groups=groups).reshape(n, m, b)
    s1_rows = spmd.alltoall(s1, axis=axis, groups=groups).reshape(n, m, 1)

    # Dequantize-accumulate kernel over the contributor axis, gridded
    # over my shard's blocks (zero-padded block columns contribute 0).
    interpret = resolve_interpret(interpret)
    m_p, mt = _row_grid(m, interpret)
    qp, _ = pad_dim(rows, mt, axis=1)
    sp, _ = pad_dim(s1_rows, mt, axis=1)
    partial = pl.pallas_call(
        _dequant_accum_kernel,
        grid=(m_p // mt,),
        in_specs=[
            pl.BlockSpec((n, mt, b), lambda i: (0, i, 0)),
            pl.BlockSpec((n, mt, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((mt, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_p, b), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="hvd_tpu_dequant_accumulate",
    )(qp, sp)
    partial = partial[:m].reshape(-1)
    if pad:
        partial = partial[:-pad]
    if op == "average":
        partial = partial / n
    return partial.astype(x.dtype)


# --- fused all-gather -> dequantize [-> optimizer apply] ---------------------

def _gather_quantized(shard, *, axis, groups, block_size, interpret):
    """Quantize my flat shard (Pallas) and all-gather payload + scale
    sidecar: ``(q [n, m, b], scales [n, m, 1], k, pad, n)``."""
    n = _group_size(axis, groups)
    flat = shard.astype(jnp.float32).reshape(-1)
    k = flat.size
    b = max(1, min(block_size, k))
    pad = (-k) % b
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    m = flat.size // b
    q, s = quantize_blocks(flat.reshape(m, b), interpret=interpret)
    gathered = spmd.allgather(q.reshape(-1), axis=axis,
                              groups=groups).reshape(n, m, b)
    s_all = spmd.allgather(s, axis=axis, groups=groups).reshape(n, m, 1)
    return gathered, s_all, k, pad, n


def fused_quantize_allgather(shard, *, axis: str = "hvd", groups=None,
                             block_size: int = 1024,
                             interpret: Optional[bool] = None):
    """Fused twin of ``quantization.int8_allgather`` (phase 3 of the
    wire): quantize-pack kernel → quantized ``all_gather`` → fused
    dequantize kernel.  Returns ``[n * size]`` flat, rank-major,
    bit-identical to the reference in interpret mode."""
    n = _group_size(axis, groups)
    if n == 1:
        return shard.astype(jnp.float32).reshape(-1).astype(shard.dtype)
    gathered, s_all, k, pad, n = _gather_quantized(
        shard, axis=axis, groups=groups, block_size=block_size,
        interpret=interpret)
    m, b = gathered.shape[1], gathered.shape[2]
    deq = dequantize_blocks(gathered.reshape(n * m, b),
                            s_all.reshape(n * m),
                            interpret=interpret)
    out = deq.reshape(n, -1)
    if pad:
        out = out[:, :-pad]
    return out.reshape(-1).astype(shard.dtype)


def fused_allreduce(x, *, op: str = "sum", axis: str = "hvd", groups=None,
                    block_size: int = 1024,
                    interpret: Optional[bool] = None):
    """Fused twin of ``quantization.int8_allreduce`` — the RS+AG
    composition on the fused kernels (the ``--kernel pallas`` bench
    vehicle).  Bit-identical to the reference in interpret mode."""
    if op not in ("sum", "average"):
        raise ValueError(
            f"int8 transport supports op=sum/average, got {op!r} "
            "(min/max/product need exact comparisons; drop compression)")
    n = _group_size(axis, groups)
    if n == 1:
        return x
    orig_dtype, orig_shape = x.dtype, x.shape
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    shard = fused_quantize_reducescatter(
        flat, op=op, axis=axis, groups=groups, block_size=block_size,
        interpret=interpret)
    out = fused_quantize_allgather(
        shard, axis=axis, groups=groups, block_size=block_size,
        interpret=interpret)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape).astype(orig_dtype)


def _sgd_kernel(q_ref, s_ref, p_ref, o_ref, *, lr: float):
    """Dequantize + SGD leaf update in one pass: ``p - lr * (q*s)``."""
    g = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (p_ref[...].astype(jnp.float32)
                  - lr * g).astype(o_ref.dtype)


def _adam_kernel(q_ref, s_ref, p_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, lr: float, b1: float,
                 b2: float, eps: float, bc1: float, bc2: float):
    """Dequantize + Adam leaf update in one pass (the
    ``optax.adam``-shaped moment/bias-correction math the
    DistributedOptimizer's inner transform applies)."""
    g = q_ref[...].astype(jnp.float32) * s_ref[...]
    m_new = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    v_new = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * (g * g)
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    po_ref[...] = (p_ref[...].astype(jnp.float32)
                   - lr * update).astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


def _blocked_layout(leaf_flat, n, k, pad, b):
    """Lay a flat ``[n*k]`` leaf out as the gathered wire's block rows
    ``[n*m, b]`` (per-contributor zero-padded tails), so the apply
    kernel walks parameter and gradient blocks in lockstep."""
    rows = leaf_flat.astype(jnp.float32).reshape(n, k)
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((n, pad), jnp.float32)], axis=1)
    return rows.reshape(-1, b)


def _unblocked(rows2d, n, k, pad, dtype):
    out = rows2d.reshape(n, -1)
    if pad:
        out = out[:, :-pad]
    return out.reshape(-1).astype(dtype)


def _apply_gridded(kernel, inputs, out_shapes, rows, b, interpret, name):
    """Run a leaf-update kernel over ``[rows, b]`` block rows: pads the
    row axis to the tile, grids, slices the pad back off.  ``name`` is
    the kernel's stable name in a device trace."""
    interpret = resolve_interpret(interpret)
    rows_p, rt = _row_grid(rows, interpret)
    padded = []
    for arr in inputs:
        ap, _ = pad_dim(arr, rt, axis=0)
        padded.append(ap)
    specs = [pl.BlockSpec((rt, arr.shape[1]), lambda i: (i, 0))
             for arr in padded]
    outs = pl.pallas_call(
        kernel,
        grid=(rows_p // rt,),
        in_specs=specs,
        out_specs=[pl.BlockSpec((rt, b), lambda i: (i, 0))
                   for _ in out_shapes],
        out_shape=[jax.ShapeDtypeStruct((rows_p, b), dt)
                   for dt in out_shapes],
        interpret=resolve_interpret(interpret),
        name=name,
    )(*padded)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    return [o[:rows] for o in outs]


def fused_allgather_sgd_apply(param, grad_shard, *, lr: float,
                              axis: str = "hvd", groups=None,
                              block_size: int = 1024,
                              interpret: Optional[bool] = None):
    """All-gather the reduced gradient shard on the int8 wire and apply
    the SGD leaf update ``p - lr*g`` in ONE fused pass: the gathered
    int8 payload is dequantized inside the update kernel, so the full-
    precision gradient never lands in HBM.  ``param`` is the flat
    ``[n * shard]`` leaf; returns the updated leaf.  The dequantized
    gradient matches ``int8_allgather`` bit-for-bit (same kernel math);
    the update arithmetic itself may differ from an unfused
    formulation by one FMA-contraction rounding (~1 ulp)."""
    n = _group_size(axis, groups)
    if n == 1:
        g = grad_shard.astype(jnp.float32).reshape(-1)
        return (param.reshape(-1).astype(jnp.float32)
                - lr * g).astype(param.dtype).reshape(param.shape)
    gathered, s_all, k, pad, n = _gather_quantized(
        grad_shard, axis=axis, groups=groups, block_size=block_size,
        interpret=interpret)
    m, b = gathered.shape[1], gathered.shape[2]
    rows = n * m
    p_rows = _blocked_layout(param.reshape(-1), n, k, pad, b)
    (new_p,) = _apply_gridded(
        functools.partial(_sgd_kernel, lr=float(lr)),
        [gathered.reshape(rows, b), s_all.reshape(rows, 1), p_rows],
        [jnp.float32], rows, b, interpret, "hvd_tpu_fused_sgd_apply")
    return _unblocked(new_p, n, k, pad, param.dtype).reshape(param.shape)


def fused_allgather_adam_apply(param, mu, nu, grad_shard, *, lr: float,
                               step: int, b1: float = 0.9,
                               b2: float = 0.999, eps: float = 1e-8,
                               axis: str = "hvd", groups=None,
                               block_size: int = 1024,
                               interpret: Optional[bool] = None):
    """All-gather the reduced gradient shard on the int8 wire and apply
    the Adam leaf update (first/second moments + bias correction, the
    ``optax.adam`` shape) in ONE fused pass.  ``step`` is the 1-based
    update count for bias correction (static: the caller's python step,
    matching a per-step re-traced or scanned update).  Returns
    ``(new_param, new_mu, new_nu)``, each flat leaves shaped like their
    inputs."""
    if step < 1:
        raise ValueError(f"step must be >= 1 for bias correction, "
                         f"got {step}")
    n = _group_size(axis, groups)
    bc1 = 1.0 - float(b1) ** int(step)
    bc2 = 1.0 - float(b2) ** int(step)
    if n == 1:
        g = grad_shard.astype(jnp.float32).reshape(param.shape)
        m_new = b1 * mu.astype(jnp.float32) + (1 - b1) * g
        v_new = b2 * nu.astype(jnp.float32) + (1 - b2) * (g * g)
        upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
        return ((param.astype(jnp.float32) - lr * upd).astype(param.dtype),
                m_new.astype(mu.dtype), v_new.astype(nu.dtype))
    gathered, s_all, k, pad, n = _gather_quantized(
        grad_shard, axis=axis, groups=groups, block_size=block_size,
        interpret=interpret)
    m, b = gathered.shape[1], gathered.shape[2]
    rows = n * m
    p_rows = _blocked_layout(param.reshape(-1), n, k, pad, b)
    m_rows = _blocked_layout(mu.reshape(-1), n, k, pad, b)
    v_rows = _blocked_layout(nu.reshape(-1), n, k, pad, b)
    new_p, new_m, new_v = _apply_gridded(
        functools.partial(_adam_kernel, lr=float(lr), b1=float(b1),
                          b2=float(b2), eps=float(eps), bc1=bc1, bc2=bc2),
        [gathered.reshape(rows, b), s_all.reshape(rows, 1),
         p_rows, m_rows, v_rows],
        [jnp.float32, jnp.float32, jnp.float32], rows, b, interpret,
        "hvd_tpu_fused_adam_apply")
    return (_unblocked(new_p, n, k, pad, param.dtype).reshape(param.shape),
            _unblocked(new_m, n, k, pad, mu.dtype).reshape(mu.shape),
            _unblocked(new_v, n, k, pad, nu.dtype).reshape(nu.shape))


# --- fused matmul -> all-gather (FSDP unshard epilogue) ----------------------

def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    """One (m, n, k) grid step of the blocked matmul: accumulate the
    K-panel product in f32 VMEM scratch; the epilogue on the last K
    step writes the output tile that feeds the all-gather directly."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def fused_matmul_allgather(x, w_shard, *, axis: str = "hvd", groups=None,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 512,
                           interpret: Optional[bool] = None):
    """The FSDP unshard epilogue: ``x [M, K] @ w_shard [K, N/n]`` as a
    blocked Pallas matmul whose epilogue tile feeds an activation
    all-gather — ``[M, N]`` with rank-major column order, equal to
    ``x @ all_gather(w_shard, axis=columns)``.

    Moving the gather AFTER the matmul replaces the unshard path's
    gathered-weight HBM materialization (``K × N`` bytes per layer)
    with an activation gather (``M × N``), and the output tile goes to
    the wire straight from the kernel epilogue.  Wins whenever
    ``M < K`` — the usual FSDP regime of long thin layers.
    """
    if x.ndim != 2 or w_shard.ndim != 2 or x.shape[1] != w_shard.shape[0]:
        raise ValueError(
            f"expected x [M, K] @ w_shard [K, N/n]; got {x.shape} @ "
            f"{getattr(w_shard, 'shape', None)}")
    mm, kk = x.shape
    nl = w_shard.shape[1]
    bm = min(block_m, round_up(mm, _SUBLANES))
    bn = min(block_n, round_up(nl, _SUBLANES))
    bk = min(block_k, kk)
    xp, _ = pad_dim(x, bm, axis=0)
    xp, _ = pad_dim(xp, bk, axis=1)
    wp, _ = pad_dim(w_shard, bk, axis=0)
    wp, _ = pad_dim(wp, bn, axis=1)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    y = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="hvd_tpu_matmul_allgather",
    )(xp, wp)[:mm, :nl]
    n = _group_size(axis, groups)
    if n == 1:
        return y
    gathered = spmd.allgather(y, axis=axis, groups=groups,
                              tiled=True)                 # [n*M, N/n]
    return gathered.reshape(n, mm, nl).transpose(1, 0, 2).reshape(mm, -1)
