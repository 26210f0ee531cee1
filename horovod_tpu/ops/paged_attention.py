"""Attention of one decode step over a paged KV cache.

The serving engine keeps a layer's keys and values in two pools of
blocks, ``[num_blocks, block, K * D_k]`` and ``[num_blocks, block, K *
D_v]`` (a token's ``K`` KV heads side by side in one row; the layer
states ``K``, the keys' width ``D_k`` and the values' ``D_v``, and two
layers of one model may state different ones), and a request's chain of
blocks in a row of the layer's block table.  A decode step has one
query token a row, at the row's own depth, and has to read each row's
keys ``start .. position``: ``start`` is 0 for a layer that attends to
everything before it, and ``position - window + 1`` for a layer with a
causal window, whose table is a *ring* — the block of positions ``[i *
block, (i + 1) * block)`` is column ``i mod ring`` of the row, ``ring``
being the table's width less its last column (the trash column).  A
full layer's table is as wide as the longest request, so ``i mod ring``
is ``i`` there and one arithmetic serves both.  A layer may also state
a *sink*: one learned logit a head that joins the softmax's denominator
and nothing else (its column is dropped).

The gathered *view* does that for any chunk: ``pool[table]`` laid out
as ``[B, n_cols * block, K, D]``, scores over all of it, a mask.  Its
cost is that of the table's width, whatever the rows hold, and for one
query token a row the gather, the re-layout of 64-wide heads and the
scores over dead positions were 23 of a 34 ms decode step of GPT-2 XL
(PERF.md, PR 27).  :func:`view_attention` is that arithmetic; prefill
chunks (``T > 1``) and the dense cache keep it.

:func:`paged_decode` is the single-token case.  On the TPU it is one
Pallas kernel a layer (``hvd_tpu_paged_decode``): the table and the
positions go in as scalar prefetch, the pools stay in HBM, and for each
row the kernel walks the row's table to ``position // block + 1``
blocks, fetching them by asynchronous copy, several blocks a wave, the
next wave in flight while the present one is scored (the pattern of
``jax.experimental.pallas.ops.tpu.paged_attention``, whose pool layout
and head sizes are not this repository's).  A row at position 0 (a slot
without a request rides along so, on trash blocks) costs one block.

**No head is sliced out of a pool row.**  The query goes in as a
block-diagonal matrix ``[heads, K * D]`` — row ``h`` holds ``q[h]`` in
the columns of its KV head and zero elsewhere, built once a row — so
the scores of every head against a wave ``[n, K * D]`` are one MXU
product, and the read-out is ``p [heads, n] . V [n, K * D]``, of which
the block diagonal is kept at the end.  The wasted products are zeros.
Grouped KV heads (``K < H``) are covered by the same matrix: the
``H / K`` query heads of a group are rows that share a column block.
bfloat16 inputs, float32 scores, softmax and sums, probabilities
rounded to the pool's dtype before the product with V.

**A block of queries a row.**  A model that generates by blocks
(``GPTConfig.block_length``) brings ``B`` queries a row to a decode
step, and all of them see one range: everything up to their block's
end.  :func:`fold_block` lays them out as ``B x H`` query heads over
the ``K`` KV heads — a grouped-query step with a group ``B`` times as
large — and the same kernel serves them with the row's length at the
block's end; :func:`unfold_block` takes the result apart again.
:func:`view_attention` takes the block mask as ``block`` (a query at
``i`` sees the key at ``j`` iff ``j // block <= i // block``) for the
no-cache forward and prefill chunks.

Off the TPU :func:`paged_decode` runs the view's arithmetic in
``jax.numpy``.  ``interpret`` is the tree-wide escape hatch of a kernel
(True: the kernel under the interpreter, which is how the tests reach
it; False: the kernel compiled wherever this runs, which is how it is
compiled for a described chip).

**What the kernel asks of the pool.**  Mosaic copies a block only if
its last dimension is whole vectors of 128 lanes and its rows whole
sublane tiles (16 rows of bfloat16, 8 of float32).  So the engine pads
a pool row to a multiple of 128 (GPT-2 XL's 25 x 64 = 1,600 to 1,664:
the bytes the chip's tiled layout gave the row anyway), a token's ``K *
D`` numbers first; a pool that is not so shaped takes the view's
arithmetic everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import _LANES, _SUBLANES, round_up

_NEG_INF = -1e30
# Tokens fetched and scored together, one wave of block copies: at most
# this many, and no more than fill _WAVE_BYTES.  Two waves of K and of
# V are resident (4 x 128 x 1,664 x 2 B = 1.7 MB at GPT-2 XL's row).  A
# wave is scored whole, so a short row pays for all of it: 128 against
# 256 is 0.5 us less for a row of one block and 4 % more for rows of
# 1,024 tokens (PERF.md, PR 27).
_WAVE_TOKENS = 128
_WAVE_BYTES = 1 << 20


def view_attention(q, k_all, v_all, positions, *, key_positions=None,
                   window: int = 0, sink=None, block: int = 0):
    """Attention of ``q [B, T, H, D_k]`` at absolute ``positions [B,
    T]`` over per-row keys ``[B, S, K, D_k]`` and values ``[B, S, K,
    D_v]`` (dense cache rows, a paged cache's gathered view, or the
    chunk's own): a query sees the keys at ``key_positions [B, S]``
    (None: key ``i`` is position ``i``) that are not after it, not
    negative and, with ``window``, fewer than ``window`` positions
    back.  With ``block`` the mask is causal over blocks of ``block``
    positions and full inside one: a query at ``i`` sees the key at
    ``j`` iff ``j // block <= i // block``.  ``sink [H]`` (float32)
    joins each head's denominator.
    Scores rounded to ``q``'s dtype by the product, softmax in float32,
    probabilities rounded to ``q``'s dtype.  Returns ``[B, T, H,
    D_v]``."""
    H, K, D = q.shape[2], k_all.shape[2], q.shape[3]
    if K != H:
        # Grouped KV heads: each is read by H / K query heads.
        k_all = jnp.repeat(k_all, H // K, axis=2)
        v_all = jnp.repeat(v_all, H // K, axis=2)
    S = k_all.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all)
    scores = scores.astype(jnp.float32) * (D ** -0.5)
    if key_positions is None:
        key_positions = jnp.arange(S, dtype=positions.dtype)[None]
    back = positions[:, :, None] - key_positions[:, None, :]
    if block:
        back = (positions[:, :, None] // block
                - key_positions[:, None, :] // block)
    visible = (back >= 0) & (key_positions[:, None, :] >= 0)
    if window:
        visible &= back < window
    scores = jnp.where(visible[:, None], scores, _NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        # The sink's column takes its share of the softmax and is
        # dropped: the probabilities of a row then add up to less than
        # one.
        logit = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            scores.shape[:3] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, logit], axis=-1), axis=-1)[..., :S]
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v_all)


def gathered_view(pool, table, kv_heads: int, head_dim: int):
    """``pool [num_blocks, block, row]`` through ``table [B, n_cols]``
    as per-row keys or values ``[B, n_cols * block, K, D]``: view row
    ``i`` is the token at position ``i`` of the row's chain.  A pool
    row holds the ``K * D`` numbers of a token first and may be padded
    beyond them (to whole vectors of 128 lanes)."""
    B = table.shape[0]
    return pool[table][..., :kv_heads * head_dim].reshape(
        B, -1, kv_heads, head_dim)


def ring_positions(table, positions, block: int):
    """The position whose token each row of the ring's view holds,
    ``[B, ring * block]`` for ``table [B, ring + 1]`` (the last column
    is the trash column and no part of the view) and a row whose newest
    token is at ``positions [B]``: column ``c`` holds the newest block
    index ``i <= position // block`` with ``i mod ring == c`` — negative
    where the row has not come so far, which no query sees."""
    ring = table.shape[1] - 1
    newest = positions[:, None] // block                        # [B, 1]
    index = newest - (newest - jnp.arange(ring)[None]) % ring   # [B, ring]
    return (index[:, :, None] * block
            + jnp.arange(block)[None, None]).reshape(table.shape[0], -1)


def _decode_view(q, k_pool, v_pool, table, positions, kv_heads,
                 v_head_dim=None, window=0, sink=None):
    D, Dv = q.shape[-1], v_head_dim or q.shape[-1]
    key_positions = None
    if window:
        # A ring: what a view row holds depends on how far the row is.
        key_positions = ring_positions(table, positions, k_pool.shape[1])
        table = table[:, :-1]
    return view_attention(
        q[:, None], gathered_view(k_pool, table, kv_heads, D),
        gathered_view(v_pool, table, kv_heads, Dv), positions[:, None],
        key_positions=key_positions, window=window, sink=sink)[:, 0]


def fold_block(q, kv_heads: int):
    """A block's queries as query heads of one decode row: ``q [B, T,
    H, D]`` to ``[B, T * H, D]``, the ``T * H / K`` heads that read KV
    head ``k`` side by side (head ``k * T * G + t * G + g`` is position
    ``t``'s head ``k * G + g``), so that the ``T`` queries of a row,
    which all see the same range of keys, ride :func:`paged_decode` as
    a grouped-query step with a group ``T`` times as large."""
    B, T, H, D = q.shape
    return q.reshape(B, T, kv_heads, H // kv_heads, D).swapaxes(
        1, 2).reshape(B, T * H, D)


def unfold_block(out, T: int, kv_heads: int):
    """:func:`fold_block`'s inverse on the attention's result:
    ``[B, T * H, D_v]`` to ``[B, T, H, D_v]``."""
    B, heads, Dv = out.shape
    return out.reshape(B, kv_heads, T, heads // (T * kv_heads), Dv).swapaxes(
        1, 2).reshape(B, T, heads // T, Dv)


def _sublane_tile(dtype) -> int:
    return _SUBLANES * 4 // jnp.dtype(dtype).itemsize


def _decode_kernel(table_ref, pos_ref, start_ref, q_ref, *refs, wave: int,
                   groups: int, kv_rows: int, head_dim: int, v_head_dim: int,
                   has_sink: bool):
    """One row of the batch: walk its table from its start to its
    length."""
    sink_ref = refs[0] if has_sink else None
    (k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, first_ref, m_ref, l_ref,
     acc_ref) = refs[has_sink:]
    b = pl.program_id(0)
    block = k_hbm.shape[1]
    ring = table_ref.shape[1] - 1
    n = wave * block
    D, Dv, Kp = head_dim, v_head_dim, kv_rows
    pos = pos_ref[b]
    start = start_ref[b]
    n_waves = (pos // block - start // block + wave) // wave

    def copies(row, i, slot, start: bool):
        # Wave ``i`` of ``row``'s blocks into buffer ``slot``; only the
        # row's live blocks move.  A wait needs the copy's size and
        # semaphore, not its source.
        first_idx = start_ref[row] // block + i * wave
        live = jnp.minimum(pos_ref[row] // block + 1 - first_idx, wave)

        def one(w, _):
            blk = table_ref[row, (first_idx + w) % ring] if start else 0
            rows = pl.ds(pl.multiple_of(w * block, block), block)
            for hbm, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                copy = pltpu.make_async_copy(
                    hbm.at[blk], buf.at[slot, rows], sem.at[s, slot])
                copy.start() if start else copy.wait()
            return 0

        jax.lax.fori_loop(0, live, one, 0)

    @pl.when(b == 0)
    def _():
        # A wave's dead rows are multiplied by probabilities of exactly
        # zero; what they hold must be a number.  After this they only
        # ever hold pool rows.
        v_buf[...] = jnp.zeros_like(v_buf)
        first_ref[0] = 0
        copies(0, 0, 0, True)

    # The buffer of this row's first wave: the row before started it
    # while it scored its own last wave.
    first = first_ref[0]

    # The block-diagonal query: row ``g * Kp + k`` is query head ``k * G
    # + g`` in the columns of KV head ``k``.
    def diagonal(width, row):
        head = jax.lax.broadcasted_iota(jnp.int32, (Kp, row), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Kp, row), 1)
        return (col >= head * width) & (col < (head + 1) * width)

    diagonal_k = diagonal(D, k_hbm.shape[2])
    diagonal_v = diagonal_k if (Dv, v_hbm.shape[2]) == (
        D, k_hbm.shape[2]) else diagonal(Dv, v_hbm.shape[2])
    q_rows = [jnp.where(diagonal_k, q_ref[0, g:g + 1, :].astype(jnp.float32),
                        0.0).astype(k_buf.dtype) for g in range(groups)]
    q_bd = q_rows[0] if groups == 1 else jnp.concatenate(q_rows, axis=0)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, _):
        slot = (first + i) % 2

        @pl.when(i + 1 < n_waves)
        def _():
            copies(b, i + 1, 1 - slot, True)

        @pl.when((i + 1 == n_waves) & (b + 1 < pl.num_programs(0)))
        def _():
            copies(b + 1, 0, 1 - slot, True)

        copies(b, i, slot, False)
        s = jax.lax.dot_general(
            q_bd, k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (D ** -0.5)   # [R, n]
        at = ((start // block) * block + i * n
              + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where((at >= start) & (at <= pos), s, _NEG_INF)
        m = m_ref[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        return 0

    jax.lax.fori_loop(0, n_waves, body, 0)
    first_ref[0] = (first + n_waves) % 2

    # Each head's own D_v columns of its row: the block diagonal, summed
    # down the rows of a group into one pool-shaped row.  A sink joins
    # the denominator alone (rows that are no head hold -1e30 there).
    den = l_ref[:, :1]
    if sink_ref is not None:
        den = den + jnp.exp(sink_ref[:, :1] - m_ref[:, :1])
    out = acc_ref[...] / den
    for g in range(groups):
        mine = jnp.where(diagonal_v, out[g * Kp:(g + 1) * Kp], 0.0)
        o_ref[0, g:g + 1, :] = jnp.sum(
            mine, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "v_head_dim",
                                             "interpret"))
def _decode_pallas(q, k_pool, v_pool, table, positions, starts, sink,
                   kv_heads, v_head_dim, *, interpret: bool):
    # Jitted so that a model's layers, which call this with the same
    # shapes, share one trace and one lowering of the kernel: lowering
    # it anew for each of GPT-2 XL's 48 layers took a process 25 s.
    B, H, D = q.shape
    _, block, row = k_pool.shape
    v_row = v_pool.shape[2]
    K, G, Dv = kv_heads, H // kv_heads, v_head_dim
    tokens = min(_WAVE_TOKENS,
                 _WAVE_BYTES // (row * jnp.dtype(k_pool.dtype).itemsize))
    wave = max(1, tokens // block)
    # A group's K heads take whole tiles of the query matrix's rows.
    Kp = round_up(K, _sublane_tile(k_pool.dtype))
    R = G * Kp
    # Query head k * G + g goes to group row g, laid out as a pool row.
    q_g = q.reshape(B, K, G, D).swapaxes(1, 2).reshape(B, G, K * D)
    q_g = jnp.pad(q_g.astype(k_pool.dtype),
                  ((0, 0), (0, 0), (0, row - K * D)))
    per_row = pl.BlockSpec((1, G, row), lambda b, *_: (b, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_decode_kernel, wave=wave, groups=G,
                               kv_rows=Kp, head_dim=D, v_head_dim=Dv,
                               has_sink=sink is not None)
    block_table = table.astype(jnp.int32)
    # The kernel reads the table as far as the position says.
    last = jnp.clip(positions.astype(jnp.int32), 0, 2 ** 30)
    if starts is None:
        last = jnp.minimum(last, (table.shape[1] - 1) * block - 1)
        first = jnp.zeros_like(last)
    else:
        first = jnp.clip(starts.astype(jnp.int32), 0, last)
    operands, in_specs = [q_g], [per_row]
    if sink is not None:
        # Laid out as the running maximum is: row g * Kp + k is head
        # k * G + g; rows that are no head never reach the output.
        s = jnp.full((G, Kp), _NEG_INF, jnp.float32).at[:, :K].set(
            sink.astype(jnp.float32).reshape(K, G).T)
        operands.append(jnp.broadcast_to(s.reshape(R, 1), (R, _LANES)))
        in_specs.append(pl.BlockSpec((R, _LANES), lambda b, *_: (0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=in_specs + [in_hbm, in_hbm],
            out_specs=pl.BlockSpec((1, G, v_row), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, wave * block, row), k_pool.dtype),
                pltpu.VMEM((2, wave * block, v_row), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),              # first buffer
                pltpu.VMEM((R, _LANES), jnp.float32),     # running max
                pltpu.VMEM((R, _LANES), jnp.float32),     # denominator
                pltpu.VMEM((R, v_row), jnp.float32),      # numerator
            ]),
        out_shape=jax.ShapeDtypeStruct((B, G, v_row), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Rows in order: each starts the next one's first copies.
            dimension_semantics=("arbitrary",)),
        name="hvd_tpu_paged_decode",
        interpret=interpret,
    )(block_table, last, first, *operands, k_pool, v_pool)
    out = out[..., :K * Dv].reshape(B, G, K, Dv)
    return out.swapaxes(1, 2).reshape(B, H, Dv)


def paged_decode(q, k_pool, v_pool, table, positions, kv_heads: int, *,
                 v_head_dim: Optional[int] = None, window: int = 0,
                 sink=None, interpret: Optional[bool] = None):
    """Attention of a single-token decode step over a paged cache.
    ``q [B, H, D_k]``; ``k_pool [num_blocks, block, k_row]``, ``v_pool
    [num_blocks, block, v_row]`` with this step's keys and values
    already written, a token's ``kv_heads * D_k`` (``kv_heads *
    v_head_dim``; None: ``D_k``) numbers first in its row; ``table [B,
    ring + 1]`` block ids, the last column the trash column;
    ``positions [B]``: row ``b`` sees the tokens ``0 .. positions[b]``
    of its chain — with ``window`` the last ``window`` of them — token
    ``i`` in row ``i % block`` of block ``table[b, (i // block) %
    ring]``.  ``sink [H]``: a logit a head in the denominator.  ``H`` is
    a multiple of ``kv_heads``: grouped KV heads go through the kernel
    too.  On the TPU the kernel, elsewhere the view's arithmetic;
    ``interpret``: see the module's text.  A pool the kernel cannot
    copy by block — a row that is not whole vectors of 128 lanes, a
    block that is not whole sublane tiles — takes the view's arithmetic
    everywhere.  Returns ``[B, H, D_v]`` in ``q``'s dtype."""
    _, block, row = k_pool.shape
    Dv = int(v_head_dim or q.shape[-1])
    plain = interpret is None and jax.default_backend() != "tpu"
    if (plain or row % _LANES or v_pool.shape[2] % _LANES
            or block % _sublane_tile(k_pool.dtype)):
        return _decode_view(q, k_pool, v_pool, table, positions, kv_heads,
                            Dv, window, sink)
    starts = (jnp.maximum(positions - (window - 1), 0) if window else None)
    return _decode_pallas(q, k_pool, v_pool, table, positions, starts, sink,
                          kv_heads=kv_heads, v_head_dim=Dv,
                          interpret=bool(interpret))
