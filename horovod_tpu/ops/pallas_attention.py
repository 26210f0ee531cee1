"""Flash attention as a Pallas TPU kernel.

No reference analogue — Horovod ships no kernels (SURVEY.md §2.9: no
attention/sequence machinery at all); this is part of the TPU rebuild's
first-class long-context support.

**The forward** is one Pallas kernel a call, and :func:`plan` says from
the shapes alone what that call does — the same function the call
builds its grid from.  A grid step holds one block of ``block_q`` query
rows against the *whole* key range of its heads: K and V of the head
group stay resident in VMEM while the group's query blocks go by (their
block index does not change, so they are copied once a group), and the
step walks the live keys itself, ``block_k`` at a time, in a
``fori_loop`` that carries the streaming-softmax state (running max,
denominator, numerator; float32).  With ``causal`` the walk ends at the
diagonal: a block above it costs no step, no copy and no arithmetic,
the chunks wholly below it skip the mask, and the last chunk — what is
left up to the diagonal, of a width that is static in the block's place
within its chunk — masks the block's own keys alone.  A sequence of one
chunk (GPT-2's 1,024 positions) has no walk and no state: each step is
a plain softmax over its live keys.  Both products take their operands
in the inputs' dtype (bfloat16 into the MXU for bfloat16 inputs,
float32 for float32) and accumulate in float32; the scale meets the
float32 scores, and max, exponent, sums and ``lse`` stay float32.

Where a head fills whole vectors of 128 lanes, or ``128 / d`` heads
side by side do (two of GPT-2's 64), the kernel reads ``[B, T, H * D]``
as it stands — no transpose on either side of the call — and a step
takes one such group of lanes.  The heads of a group are stacked along
the rows with the other heads' lanes zeroed (the block-diagonal query
of ``ops/paged_attention.py``): one product with the keys scores them
all, no head is sliced out of a vector, and the wasted products are
zeros the MXU would have idled through anyway at a contraction of 64.
Any other head size goes in as ``[B * H, T, D]``, one head a step.

**The backward** is one Pallas kernel a call too (``hvd_tpu_flash_bwd``
in a trace), the forward's picture turned over, and :func:`plan_bwd`
says what it does.  A grid step holds one block of ``block_k`` *keys*
of its heads, with their ``q``, ``dO``, ``lse`` and ``delta =
rowsum(dO · O) − dlse`` whole and resident (one XLA fusion makes
``delta`` in front of the call), and walks its live queries
``block_q`` at a time.  Everything is held keys along the rows and queries along
the lanes, so ``lse`` and ``delta`` meet the scores as rows and four of
the five products need no transpose: ``sᵀ = k · qᵀ``, ``pᵀ = exp2((sᵀ ·
scale − lse) · log2 e)``, ``dv += pᵀ · dO``, ``dpᵀ = v · dOᵀ``, ``dSᵀ =
pᵀ · (dpᵀ − delta)``, ``dk += dSᵀ · q``, and ``dq += (dSᵀ)ᵀ · k``, the
one product that contracts over the rows of both.  ``dk`` and ``dv``
sum in float32 within the step and are rounded once when written;
``dq`` sums in a float32 scratch across the group's key blocks (that
grid axis is last and ``arbitrary``) and is written once; the scale
meets ``dk`` and ``dq`` there.  With ``causal`` a block's walk starts at
its diagonal: its own queries under the mask, then what is left of
their chunk (a width that is static in the block's place within it),
then whole chunks with no mask; no query before the block's first key
is touched, and a sequence of one chunk has no loop.  The operands are
the inputs' dtype, ``pᵀ`` and ``dSᵀ`` rounded to it on their way into
the MXU; scores, exponent, ``delta`` and every sum are float32.  The
layouts are the forward's: ``[B, T, H * D]`` as it stands where heads
fill whole vectors, a head of a group taking its keys and values with
the other heads' lanes zeroed (once a step), else ``[B * H, T, D]``.

Used by ``models.transformer`` (``attention='flash'``, which pads odd
causal lengths up to a multiple of 128).  Off-TPU the same kernel runs
in the Pallas interpreter (tests); it does not silently fall back to
another implementation.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import _LANES, resolve_interpret, round_up

_NEG_INF = -1e30
# Rows a grid step scores at once (its heads stacked) and keys a chunk
# of its walk: the largest that divide the lengths, up to these.
_MAX_ROWS = 512
_MAX_BLOCK_K = 1024
_VMEM_FLOOR = 16 << 20        # v5e's default scoped limit
_VMEM_CEILING = 96 << 20      # of 128 MiB


class Plan(NamedTuple):
    """What one call does, forward (:func:`plan`) or backward
    (:func:`plan_bwd`); static in the shapes.  The forward holds
    ``block_q`` queries a grid step and walks keys in chunks of
    ``block_k``; the backward holds ``block_k`` keys a step and walks
    queries in chunks of ``block_q``."""
    block_q: int
    block_k: int
    heads: int              # heads a grid step, stacked along the rows
    lanes: int              # lanes a grid step: heads * d, or d
    lane_packed: bool       # [B, T, H * D] as it stands, or [B * H, T, D]
    grid: Tuple[int, int, int]
    steps: int              # grid steps a call
    dead_steps: int         # ... of which do no arithmetic
    chunks: int             # chunks walked a call, over all steps
    masked_chunks: int      # ... of which build the causal mask
    vmem_limit_bytes: int


def _chosen_block(n: int, unit: int, cap: int) -> int:
    """A block for a length of ``n``: ``n`` itself up to ``unit``;
    beyond, the largest multiple of ``unit`` up to ``cap`` that divides
    ``n``."""
    if n <= unit:
        return n
    if n % unit:
        raise ValueError(
            f"sequence lengths must be multiples of the block sizes: {n} "
            f"is over {unit} and no multiple of it; pad, or use "
            f"flash_attention_padded for causal self-attention")
    return max(b for b in range(unit, max(cap, unit) + 1, unit)
               if n % b == 0)


def _lane_groups(b: int, h: int, d: int) -> Tuple[int, bool, int, int]:
    """Heads a grid step takes; whether ``[B, T, H * D]`` is read as it
    stands (where a head, or ``128 / d`` heads side by side, fill whole
    vectors) or ``[B * H, T, D]``; the rows of that array and the lane
    groups of a row: the grid's first two axes."""
    if d % _LANES == 0:
        return 1, True, b, h
    if _LANES % d == 0 and (h * d) % _LANES == 0:
        return _LANES // d, True, b, h * d // _LANES
    return 1, False, b * h, 1


def plan(b: int, h: int, t: int, tk: int, d: int, dtype, causal: bool,
         block_q: Optional[int] = None,
         block_k: Optional[int] = None) -> Plan:
    """The forward call for ``q [b, t, h, d]`` against ``tk`` keys.
    ``block_q`` / ``block_k`` override the choice."""
    heads, lane_packed, rows_of_q, groups = _lane_groups(b, h, d)
    lanes = heads * d
    if block_q is None:
        block_q = _chosen_block(t, _LANES, _MAX_ROWS // heads)
    if block_k is None:
        # A causal walk ends on a block's edge: whole blocks of queries.
        block_k = _chosen_block(tk, block_q if causal else _LANES,
                                _MAX_BLOCK_K)
    block_q, block_k = min(block_q, t), min(block_k, tk)
    if t % block_q or tk % block_k:
        raise ValueError(
            f"sequence lengths ({t}, {tk}) must be multiples of the block "
            f"sizes ({block_q}, {block_k}); pad, or use "
            f"flash_attention_padded for causal self-attention")
    if causal and t != tk:
        raise ValueError("causal flash attention requires Tq == Tk")
    if causal and block_q % block_k and block_k % block_q:
        raise ValueError(
            f"causal flash attention needs one of block_q, block_k "
            f"({block_q}, {block_k}) to divide the other")
    n_q = t // block_q
    grid = (rows_of_q, groups, n_q)
    if causal:
        walked = sum(i * block_q // block_k + 1 for i in range(n_q))
        masked = n_q
    else:
        walked, masked = n_q * (tk // block_k), 0
    isz = jnp.dtype(dtype).itemsize
    rows = heads * block_q
    # Double-buffered blocks (q, o; K, V whole; lse a lane-padded row a
    # query) and the step's own values (scores, probabilities and their
    # rounded copy; stacked queries, numerator).
    blocks = 2 * (2 * block_q * lanes * isz + 2 * tk * lanes * isz
                  + rows * _LANES * 4)
    values = rows * block_k * (8 + isz) + rows * lanes * (8 + isz)
    vmem = min(max(blocks + 2 * values, _VMEM_FLOOR), _VMEM_CEILING)
    per_block = rows_of_q * groups
    return Plan(block_q, block_k, heads, lanes, lane_packed, grid,
                per_block * n_q, 0, per_block * walked, per_block * masked,
                vmem)


def plan_bwd(b: int, h: int, t: int, tk: int, d: int, dtype, causal: bool,
             block_q: Optional[int] = None,
             block_k: Optional[int] = None) -> Plan:
    """The backward call for ``q [b, t, h, d]`` against ``tk`` keys: the
    forward's picture turned over.  A grid step holds ``block_k`` keys
    and walks its live queries ``block_q`` at a time.  ``block_q`` /
    ``block_k`` override the choice as they do the forward's; under
    ``causal``, where a chunk has to be whole key blocks, the larger of
    the two is the chunk and the smaller the key block."""
    heads, lane_packed, rows_of_q, groups = _lane_groups(b, h, d)
    lanes = heads * d
    if causal and t != tk:
        raise ValueError("causal flash attention requires Tq == Tk")
    bk, cq = block_k, block_q
    if causal and bk is not None and cq is not None:
        bk, cq = min(bk, cq), max(bk, cq)
    if bk is None:
        bk = _chosen_block(tk, _LANES, _MAX_ROWS // heads)
    bk = min(bk, tk)
    if cq is None:
        cq = _chosen_block(t, bk if causal else _LANES, _MAX_BLOCK_K)
    cq = min(cq, t)
    if t % cq or tk % bk or (causal and cq % bk):
        raise ValueError(
            f"sequence lengths ({t}, {tk}) must be multiples of the block "
            f"sizes ({cq}, {bk}), and a causal chunk of whole key blocks; "
            f"pad, or use flash_attention_padded for causal self-attention")
    n_k = tk // bk
    grid = (rows_of_q, groups, n_k)
    if causal:
        # Block j: its own queries under the mask, what is left of
        # their chunk where that is anything, whole chunks to the end.
        per = cq // bk
        walked = sum(1 + ((j + 1) % per > 0) + (t // cq - j // per - 1)
                     for j in range(n_k))
        masked = n_k
    else:
        walked, masked = n_k * (t // cq), 0
    isz = jnp.dtype(dtype).itemsize
    # Double-buffered blocks (q, dO, dq whole; k, v, dk, dv; lse and
    # delta a sublane-padded row a head), the float32 sums, and a
    # head's values of a chunk (scores, p, dp, dS; p and dS rounded).
    blocks = 2 * (3 * t * lanes * isz + 4 * bk * lanes * isz
                  + 2 * heads * 8 * t * 4)
    sums = (t + 2 * heads * bk) * lanes * 4
    values = bk * cq * (16 + 2 * isz) + 3 * max(bk, cq) * lanes * 4
    vmem = min(max(blocks + sums + 2 * values, _VMEM_FLOOR), _VMEM_CEILING)
    per_block = rows_of_q * groups
    return Plan(cq, bk, heads, lanes, lane_packed, grid,
                per_block * n_k, 0, per_block * walked, per_block * masked,
                vmem)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                heads: int):
    """One grid step: ``block_q`` queries of ``heads`` heads against
    their live keys.  ``scale`` is positive."""
    bq, bk = block_q, block_k
    lanes = q_ref.shape[2]
    d = lanes // heads
    tk = k_ref.shape[1]
    q = q_ref[0]                                          # [bq, lanes]
    if heads > 1:
        # Stack the heads along the rows, each with the others' lanes
        # zeroed: one product with the keys then scores every head.
        lane = lax.broadcasted_iota(jnp.int32, q.shape, 1)
        q = jnp.concatenate(
            [jnp.where((lane >= g * d) & (lane < (g + 1) * d), q,
                       jnp.zeros_like(q)) for g in range(heads)], axis=0)
    rows = heads * bq
    # exp(scale * (s - m)) as one multiply and a power of two: the
    # scale meets the float32 scores inside the exponent, and the max
    # is taken of the raw scores (the same row for a positive scale).
    log2e_scale = scale * math.log2(math.e)

    def chunk(start, width, carry, own=None):
        """The keys ``[start, start + width)`` into the streaming
        softmax.  ``own``: the chunk ends with the block's own ``bq``
        keys, which take this mask; every key before them is live."""
        keys = pl.ds(start, width)
        k_blk = k_ref[0, keys, :]                         # [width, lanes]
        v_blk = v_ref[0, keys, :]
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [rows, width]
        if own is not None:
            last = jnp.where(own, s[:, width - bq:], _NEG_INF)
            s = last if width == bq else jnp.concatenate(
                [s[:, :width - bq], last], axis=1)
        m_new = jnp.max(s, axis=-1, keepdims=True)
        if carry is not None:
            m, den, num = carry
            m_new = jnp.maximum(m, m_new)
        p = jnp.exp2((s - m_new) * log2e_scale)
        den_c = jnp.sum(p, axis=-1, keepdims=True)
        num_c = lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [rows, lanes]
        if carry is None:
            return m_new, den_c, num_c
        corr = jnp.exp2((m - m_new) * log2e_scale)
        return m_new, den * corr + den_c, num * corr + num_c

    def finish(carry):
        m, den, num = carry
        out = num * (1.0 / den)
        lse = m * scale + jnp.log(den)
        o = out[:bq]
        if heads > 1:
            lane = lax.broadcasted_iota(jnp.int32, o.shape, 1)
            for g in range(1, heads):
                o = jnp.where(lane >= g * d, out[g * bq:(g + 1) * bq], o)
        o_ref[0] = o.astype(o_ref.dtype)
        for g in range(heads):
            lse_ref[0, g] = lse[g * bq:(g + 1) * bq]

    def walk(first, count, carry):
        return lax.fori_loop(
            first, count,
            lambda j, c: chunk(pl.multiple_of(j * bk, bk), bk, c), carry)

    if not causal:
        finish(walk(1, tk // bk, chunk(0, bk, None)))
        return
    # Causal.  The keys before the block's first query go by in chunks
    # of bk with no mask, and the walk ends with one chunk that holds
    # what is left up to the diagonal, the block's own keys last.  Its
    # width is static, one of bk / bq cases by the block's place in its
    # chunk; a sequence of one chunk is a plain softmax with no walk.
    i = pl.program_id(2)
    n_q = tk // bq
    cases = min(max(1, bk // bq), n_q)
    own = lax.broadcasted_iota(jnp.int32, (bq, bq), 0) \
        >= lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
    own = jnp.concatenate([own] * heads, axis=0)
    if n_q == cases:
        start, carry = 0, None
    else:
        below = (i * bq) // bk
        start = pl.multiple_of(below * bk, bk)
        carry = walk(0, below, (
            jnp.full((rows, 1), _NEG_INF, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, lanes), jnp.float32)))
    for c in range(cases):
        def last(c=c):
            finish(chunk(start, (c + 1) * bq, carry, own))
        if cases == 1:
            last()
        else:
            pl.when(i % cases == c)(last)


def _pack(x):
    """``[B, T, H, D]`` as ``[B * H, T, D]``."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unpack(x3, b):
    bh, t, d = x3.shape
    return x3.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, *, scale, causal, block_q, block_k, interpret):
    """``q, k, v [B, T, H, D]`` to ``(o [B, T, H, D], lse [B, H, T])``."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    p = plan(b, h, t, tk, d, q.dtype, causal, block_q, block_k)
    if p.lane_packed:
        q3, k3, v3 = (x.reshape(b, x.shape[1], h * d) for x in (q, k, v))
    else:
        q3, k3, v3 = _pack(q), _pack(k), _pack(v)
    n, _, width = q3.shape
    bq, lanes = p.block_q, p.lanes
    o3, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=p.block_k, heads=p.heads),
        grid=p.grid,
        in_specs=[
            pl.BlockSpec((1, bq, lanes), lambda n, g, i: (n, i, g)),
            # The group's keys and values whole: the index does not
            # move with the query block, so they are copied once.
            pl.BlockSpec((1, tk, lanes), lambda n, g, i: (n, 0, g)),
            pl.BlockSpec((1, tk, lanes), lambda n, g, i: (n, 0, g)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, lanes), lambda n, g, i: (n, i, g)),
            # lse rides a trailing unit dim: TPU lowering requires the
            # last two block dims be (multiple-of-8, multiple-of-128) or
            # equal to the array dims; (block_q, 1) satisfies that where
            # a (1, block_q) block would not.
            pl.BlockSpec((1, p.heads, bq, 1), lambda n, g, i: (n, g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t, width), q.dtype),
            jax.ShapeDtypeStruct((n, width // d, t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=p.vmem_limit_bytes),
        interpret=interpret,
    )(q3, k3, v3)
    o = o3.reshape(b, t, h, d) if p.lane_packed else _unpack(o3, b)
    return o, lse.reshape(b, h, t)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                heads: int):
    """One grid step: ``block_k`` keys of ``heads`` heads against their
    live queries.  Everything is held keys along the rows and queries
    along the lanes, so ``lse`` and ``delta`` meet the scores as rows
    and four of the five products need no transpose."""
    bk, cq = block_k, block_q
    t, lanes = q_ref.shape[1], q_ref.shape[2]
    d = lanes // heads
    j = pl.program_id(2)
    k, v = k_ref[0], v_ref[0]                             # [bk, lanes]
    lane = lax.broadcasted_iota(jnp.int32, k.shape, 1)
    if heads > 1:
        # A head's keys and values with the other heads' lanes zeroed:
        # a product over all the lanes is then that head's alone.
        mine = [(lane >= g * d) & (lane < (g + 1) * d) for g in range(heads)]
        ks = [jnp.where(m, k, jnp.zeros_like(k)) for m in mine]
        vs = [jnp.where(m, v, jnp.zeros_like(v)) for m in mine]
    else:
        ks, vs = [k], [v]
    log2e = math.log2(math.e)
    nt = (((1,), (1,)), ((), ()))        # a · bᵀ
    tn = (((0,), (0,)), ((), ()))        # aᵀ · b

    def chunk(start, width, first=False, mask=None):
        """The queries ``[start, start + width)`` into the step's sums.
        ``first`` opens the key block's accumulators; ``mask`` (keys by
        queries) is the diagonal block's."""
        rows = pl.ds(start, width)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]     # [width, lanes]
        dq = None
        for g in range(heads):
            lse = lse_ref[0, g, :, rows] * log2e          # [1, width]
            delta = delta_ref[0, g, :, rows]
            s = lax.dot_general(ks[g], q, nt,
                                preferred_element_type=jnp.float32)
            p = jnp.exp2(s * (scale * log2e) - lse)       # [bk, width]
            if mask is not None:
                p = jnp.where(mask, p, 0.0)
            dp = lax.dot_general(vs[g], do, nt,
                                 preferred_element_type=jnp.float32)
            # The scale meets dk and dq once, when they are written.
            ds = (p * (dp - delta)).astype(q.dtype)
            dv_g = jnp.dot(p.astype(do.dtype), do,
                           preferred_element_type=jnp.float32)
            dk_g = jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq_g = lax.dot_general(ds, ks[g], tn,
                                   preferred_element_type=jnp.float32)
            dq = dq_g if dq is None else dq + dq_g
            if first:
                dv_acc[g], dk_acc[g] = dv_g, dk_g
            else:
                dv_acc[g] += dv_g
                dk_acc[g] += dk_g
        dq_acc[rows, :] += dq                             # [width, lanes]

    def walk(first):
        """Whole chunks of ``cq`` queries from chunk ``first`` to the
        end, none of them masked."""
        if t > cq:
            lax.fori_loop(
                first, t // cq,
                lambda i, _: chunk(pl.multiple_of(i * cq, cq), cq), None)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if not causal:
        chunk(0, cq, first=True)
        walk(1)
    else:
        # The block's own queries first, under the mask; then what is
        # left of their chunk, of a width that is static in the block's
        # place within it (one of cq / bk cases); then whole chunks to
        # the end.  No query before the block's first key is touched.
        n_k = t // bk
        cases = cq // bk
        keys = lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
        queries = lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
        # (A sequence of one block may be no multiple of the lanes:
        # its start is static.)
        own = 0 if n_k == 1 else pl.multiple_of(j * bk, bk)
        chunk(own, bk, first=True, mask=queries >= keys)
        for c in range(cases - 1):
            pl.when(j % cases == c)(functools.partial(
                chunk, pl.multiple_of((j + 1) * bk, bk),
                (cases - 1 - c) * bk))
        walk(j // cases + 1)

    dk, dv = dk_acc[0], dv_acc[0]
    for g in range(1, heads):
        dk = jnp.where(lane >= g * d, dk_acc[g], dk)
        dv = jnp.where(lane >= g * d, dv_acc[g], dv)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


# Under jit, so that a model's layers share one tracing and one lowering
# of the kernel: traced a layer, 24 layers added 11 s to a warm start of
# the GPT-2 train step (PERF.md §6, PR 41).  The forward is not: its
# custom-call would take the jitted function's name in the device trace
# where the benchmark finds it by the calling module's (ROADMAP W6).
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret"))
def _flash_bwd(q, k, v, o, lse, do, dlse, *, scale, causal, block_q,
               block_k, interpret):
    """The gradients of :func:`_flash_fwd`'s ``(o, lse)`` by recompute:
    ``q, k, v, o, do [B, T, H, D]`` and ``lse, dlse [B, H, T]`` to
    ``dq, dk, dv``.

    ∂lse_i/∂s_ik = p_ik, so ``dlse`` folds into the same dS term as the
    softmax-jacobian diagonal: dS = P · (dP − Δ), Δ = rowsum(dO · O) −
    dlse."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    p = plan_bwd(b, h, t, tk, d, q.dtype, causal, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1) - dlse.astype(jnp.float32)
    if p.lane_packed:
        q3, k3, v3, do3 = (x.reshape(b, x.shape[1], h * d)
                           for x in (q, k, v, do))
    else:
        q3, k3, v3, do3 = _pack(q), _pack(k), _pack(v), _pack(do)
    n, _, width = q3.shape
    bk, lanes = p.block_k, p.lanes
    whole = pl.BlockSpec((1, t, lanes), lambda n, g, j: (n, 0, g))
    block = pl.BlockSpec((1, bk, lanes), lambda n, g, j: (n, j, g))
    # lse and delta ride the lanes, a row a head.
    row = pl.BlockSpec((1, p.heads, 1, t), lambda n, g, j: (n, g, 0, 0))
    dq3, dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=p.block_q, block_k=bk, heads=p.heads),
        grid=p.grid,
        # The queries' side of a group does not move with the key block:
        # it is copied once a group, and dq leaves once.
        in_specs=[whole, block, block, whole, row, row],
        out_specs=[whole, block, block],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q3, k3, v3)],
        scratch_shapes=[
            pltpu.VMEM((t, lanes), jnp.float32),
            pltpu.VMEM((p.heads, bk, lanes), jnp.float32),
            pltpu.VMEM((p.heads, bk, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=p.vmem_limit_bytes),
        interpret=interpret,
        name="hvd_tpu_flash_bwd",
    )(q3, k3, v3, do3, lse.reshape(n, width // d, 1, t),
      delta.reshape(n, width // d, 1, t))
    if p.lane_packed:
        return (dq3.reshape(q.shape), dk3.reshape(k.shape),
                dv3.reshape(v.shape))
    return _unpack(dq3, b), _unpack(dk3, b), _unpack(dv3, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


def _flash_lse_fwd(q, k, v, *static):
    o, lse = _flash_lse(q, k, v, *static)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _flash_bwd(q, k, v, o, lse, do, dlse, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention; same contract as
    :func:`horovod_tpu.parallel.ring_attention.full_attention`:
    q/k/v ``[B, T, H, D]`` → ``[B, T, H, D]``, differentiable.

    The block sizes are chosen from the shapes (:func:`plan`);
    ``block_q`` / ``block_k`` override the choice.  A sequence length
    is at most 128 or a multiple of 128 (of the blocks given); for
    causal self-attention :func:`flash_attention_padded` accepts any
    length.  ``interpret`` defaults to True off-TPU so the same kernel
    runs under the CPU test mesh.
    """
    # The kernel emits lse unconditionally; dropping it here gives it a
    # zero cotangent, which folds into the backward as a no-op.
    o, _ = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return o


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp ``[B, H, T]`` (float32) — the merge key that lets callers
    combine partial attention outputs exactly (ring attention's
    per-block engine).  Differentiable in both outputs."""
    if q.ndim != 4:
        raise ValueError(f"expected [B, T, H, D] inputs, got {q.shape}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if scale < 0:
        # The kernel takes the row's max of the raw scores.
        q, scale = -q, -scale
    return _flash_lse(q, k, v, float(scale), bool(causal), block_q, block_k,
                      bool(resolve_interpret(interpret)))


def padded_length(t: int, block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> int:
    """The length :func:`flash_attention_padded` runs a sequence of
    ``t`` at: the next multiple of 128 (of the larger block, where
    blocks are given), or of 8 below one block."""
    blk = max(block_q or _LANES, block_k or _LANES)
    return round_up(t, blk if t >= blk else 8)


def flash_attention_padded(q, k, v, *, scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Causal self-attention for arbitrary sequence length: pads T up to
    a multiple of 128 (of the larger block, where blocks are given; of 8
    below one block), runs the kernel, slices back.  The blocks are then
    chosen to divide the padded length, so no length does more work for
    the larger blocks.  Safe exactly because the attention is causal —
    padded key positions sit after every real query position, so the
    mask removes them."""
    b, t, h, d = q.shape
    if k.shape[1] != t:
        raise ValueError("flash_attention_padded is self-attention only")
    tp = padded_length(t, block_q, block_k)
    pad = tp - t
    cfg = dict(causal=True, scale=scale, block_q=block_q, block_k=block_k,
               interpret=interpret)
    if pad == 0:
        return flash_attention(q, k, v, **cfg)
    padded = [jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
              for x in (q, k, v)]
    return flash_attention(*padded, **cfg)[:, :t]
