"""Power retention (arXiv:2507.04239, "Scaling Context Requires
Rethinking Attention"): a linear-cost replacement for softmax attention
whose score is a *power* of the scaled dot product, gated by a learned
per-token decay, and whose whole past fits a fixed-size state.

For query head ``h`` reading KV head ``κ(h)``, degree ``p`` = 2 and head
size ``d``::

    a[t, j] = exp(sum_{s=j+1..t} log_g[s]) * (q_t . k_j / sqrt(d)) ** 2     (j <= t)
    o_t     = sum_j a[t, j] v_j / (sum_j a[t, j] + EPS)

Because ``(a . b) ** 2 = phi(a) . phi(b)`` for the symmetric degree-2
feature map ``phi``, the same numbers come out of a recurrence over a
state ``(S, z)`` per KV head::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi'(q_t)^T S_t / (phi'(q_t) . z_t + EPS)             phi'(q) = phi(q) / d

Three forms of the one operator live here and the tests hold them
equal: :func:`retention_quadratic` (the definition; short inputs and
the oracle), :func:`retention_chunked` (prefill and the training-shaped
forward: masked power scores inside a chunk, the state carried between
chunks) and :func:`retention_step` (decode: one read-modify-write of
the state per token).

**The feature map's layout.**  ``phi(x)`` holds the products ``x_i x_j``
of the unordered pairs, off-diagonal ones times sqrt(2).  They are laid
out by *offset*: row ``r`` (0 <= r <= d/2) holds ``w_r x_i x_{(i+r) mod
d}`` for every ``i``, with ``w_0 = 1`` (the squares), ``w_r = sqrt(2)``
for ``0 < r < d/2`` (each unordered pair once) and ``w_{d/2} = 1`` (each
pair ``{i, i + d/2}`` appears twice, and ``1 + 1 = sqrt(2) ** 2``).  That
is ``(d/2 + 1) * d`` numbers — 8,320 for ``d`` = 128 against the 8,256
of a packed triangle — every row a whole vector of ``d`` lanes, built
from ``d/2 + 1`` rotations of ``x`` with no gather.  The state is
``S: [.., d/2 + 1, d_v, d]`` (a value's index before the key pair's, so
that a feature row ``phi(k)[r]`` lies along the lanes of ``S[r]``) and
``z: [.., d/2 + 1, d]``, float32.

**The decode step on the chip** is one Pallas kernel
(``hvd_tpu_retention_step``): XLA makes three passes over a layer's
state (a fusion reads ``S`` and writes ``g S + v phi(k)^T``, a second
reads the new ``S`` for the read-out), the kernel one: each ``S[b, k]``
of a row that holds a request comes into VMEM once, is updated, read
out against the group's ``phi(q)`` on the MXU, and goes back in place;
a row without one is not visited, and no block of its state moves.
Off the TPU the same arithmetic runs as plain ``jax.numpy`` over every
row.

The degree (2: it is the feature map's) and ``EPS`` are the operator's
definition as this repository runs it, not knobs: the benchmark's
reference states the same two.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_common import _SUBLANES

EPS = 1e-6
CHUNK = 128     # tokens per chunk of the chunked form


def feature_rows(head_dim: int) -> int:
    """Rows of ``phi``: one per offset ``0..d/2``."""
    if head_dim % 2:
        raise ValueError(f"power retention's feature layout needs an even "
                         f"head size, got {head_dim}")
    return head_dim // 2 + 1


def state_shapes(batch: int, kv_heads: int, head_dim: int
                 ) -> Tuple[tuple, tuple]:
    """Shapes of one layer's ``(S, z)`` for ``batch`` rows."""
    rows = feature_rows(head_dim)
    return ((batch, kv_heads, rows, head_dim, head_dim),
            (batch, kv_heads, rows, head_dim))


def expand(x, scale: float = 1.0):
    """``phi(x)``: ``[..., d] -> [..., d/2 + 1, d]`` with
    ``phi(a) . phi(b) = (a . b) ** 2``.  ``scale`` multiplies every
    entry (the query side carries the ``1/d`` of both ``1/sqrt(d)``)."""
    d = x.shape[-1]
    rows = feature_rows(d)
    twice = jnp.concatenate([x, x], axis=-1)
    shifted = jnp.stack([twice[..., r:r + d] for r in range(rows)], axis=-2)
    w = [math.sqrt(2.0) * scale] * rows
    w[0] = w[-1] = scale
    w = jnp.asarray(w, x.dtype)[:, None]
    return x[..., None, :] * shifted * w


def _grouped(q, kv_heads: int):
    B, T, H, d = q.shape
    if H % kv_heads:
        raise ValueError(f"{H} query heads do not divide into {kv_heads} "
                         f"KV heads")
    return q.reshape(B, T, kv_heads, H // kv_heads, d)


def _mm(eq: str, a, b):
    """``einsum`` with both inputs in ``a``'s dtype (bfloat16 in the
    serving program, float32 in the tests) and a float32 result."""
    return jnp.einsum(eq, a, b.astype(a.dtype),
                      preferred_element_type=jnp.float32)


def _weights(q, k, cum):
    """``a[t, j]`` of the definition, ``[B, K, G, T, T]`` float32, for
    grouped ``q [B, T, K, G, d]``, ``k [B, T, K, d]`` and the running
    sum ``cum [B, T, K]`` of ``log_g``: the squared scaled score times
    the decay from ``j`` to ``t``, zero where ``j > t``."""
    T, d = q.shape[1], q.shape[-1]
    s = _mm("btkgd,bjkd->bkgtj", q, k)
    decay = (cum[:, :, None] - cum[:, None, :]).transpose(0, 3, 1, 2)
    seen = jnp.tril(jnp.ones((T, T), bool))
    return (s * s / d) * jnp.exp(jnp.where(seen, decay, -jnp.inf))[:, :, None]


def retention_quadratic(q, k, v, log_g):
    """The definition, ``O(T^2)``.  ``q [B, T, H, d]``, ``k, v [B, T, K,
    d]``, ``log_g [B, T, K]`` (float32, <= 0).  Returns ``o [B, T, H,
    d]`` in float32."""
    B, T, H, d = q.shape
    a = _weights(_grouped(q, k.shape[2]), k,
                 jnp.cumsum(log_g.astype(jnp.float32), axis=1))
    num = jnp.einsum("bkgtj,bjkd->btkgd", a, v.astype(jnp.float32))
    den = a.sum(-1).transpose(0, 3, 1, 2)[..., None]
    return (num / (den + EPS)).reshape(B, T, H, d)


def _chunk(q, k, v, log_g, S, z):
    """One chunk against the state before it.  ``q [B, C, K, G, d]``,
    ``k, v [B, C, K, d]``, ``log_g [B, C, K]``; ``S`` here with the
    value's index last (``[B, K, R, d, d_v]``: both contractions then
    run over leading or trailing axes and XLA transposes nothing).
    Returns ``(o [B, C, K, G, d] float32, S, z)``."""
    d = q.shape[-1]
    cum = jnp.cumsum(log_g, axis=1)                       # [B, C, K]
    a = _weights(q, k, cum)
    num = jnp.einsum("bkgtj,bjkd->btkgd", a.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    den = a.sum(-1).transpose(0, 3, 1, 2)                 # [B, C, K, G]
    # What the chunks before this one left in the state.
    phi_q = expand(q, 1.0 / d)                            # [B, C, K, G, R, d]
    into = jnp.exp(cum)[..., None]                        # [B, C, K, 1]
    num = num + into[..., None] * _mm("btkgri,bkriv->btkgv", phi_q, S)
    den = den + into * _mm("btkgri,bkri->btkg", phi_q, z)
    # And what this chunk leaves for the next.
    last = cum[:, -1]                                     # [B, K]
    left = jnp.exp(last[:, None] - cum)                   # [B, C, K]
    phi_k = expand(k.astype(jnp.float32)) * left[..., None, None]
    carry = jnp.exp(last)
    S = (carry[..., None, None, None] * S
         + _mm("bjkri,bjkv->bkriv", phi_k.astype(k.dtype), v))
    z = carry[..., None, None] * z + phi_k.sum(axis=1)
    return num / (den[..., None] + EPS), S, z


def retention_chunked(q, k, v, log_g, state: Optional[tuple] = None,
                      valid=None):
    """The same numbers in chunks of ``CHUNK`` tokens, ``O(T)`` in the
    state: inside a chunk the masked power scores, between chunks the
    carried ``(S, z)``.  ``state`` is the ``(S, z)`` before the first
    token (None = zeros); ``valid [B, T]`` marks real tokens — a token
    that is not valid neither decays nor enters the state (padding of a
    prefill bucket), and its output row means nothing.  ``T`` need not
    be a multiple of ``CHUNK``.  Returns ``(o [B, T, H, d] float32,
    (S, z))``."""
    B, T, H, d = q.shape
    K = k.shape[2]
    log_g = log_g.astype(jnp.float32)
    if valid is not None:
        log_g = jnp.where(valid[..., None], log_g, 0.0)
        k = jnp.where(valid[..., None, None], k, jnp.zeros((), k.dtype))
    if state is None:
        s_shape, z_shape = state_shapes(B, K, d)
        state = (jnp.zeros(s_shape, jnp.float32),
                 jnp.zeros(z_shape, jnp.float32))
    C = min(CHUNK, T)
    n = -(-T // C)
    pad = n * C - T

    def chunks(x):
        # Padding behaves like tokens that are not valid: k = 0, log_g = 0.
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((B, n, C) + x.shape[2:]), 1, 0)

    # A chunk with no valid token (the tail of a padded bucket) changes
    # nothing and is not computed.
    live = (jnp.ones((n,), bool) if valid is None
            else chunks(valid).any(axis=(1, 2)))

    def body(carry, xs):
        o, S, z = jax.lax.cond(
            xs[0], lambda: _chunk(*xs[1:], *carry),
            lambda: (jnp.zeros((B, C, K, H // K, d), jnp.float32), *carry))
        return (S, z), o

    # The scan carries S with the value's index last (see _chunk); one
    # transpose of a row's state on the way in and one on the way out.
    (S, z), o = jax.lax.scan(
        body, (jnp.swapaxes(state[0], -1, -2), state[1]),
        (live, chunks(_grouped(q, K)), chunks(k), chunks(v), chunks(log_g)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, n * C, H, d)[:, :T]
    return o, (jnp.swapaxes(S, -1, -2), z)


def _step_kernel(rows_ref, n_ref, g_ref, v_ref, pk_ref, pq_ref, s_ref,
                 s_out_ref, num_ref):
    """One ``(row, KV head)``: ``S[r] = g S[r] + v phi(k)[r]^T`` for
    every feature row ``r``, written back in place, and the group's
    read-out ``num[g] = sum_r phi(q)[r, g] . S[r]^T`` on the MXU with
    bfloat16 inputs (what XLA's default precision gives the same
    contraction) and a float32 sum.  A grid step past the rows to visit
    (``n_ref``) computes nothing; ``_step_pallas`` has why it copies
    nothing either."""
    del rows_ref                          # the index maps read it
    rows = s_ref.shape[2]

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        g_row = g_ref[0, 0]               # [1, d]: g, the same in every lane
        v_cols = v_ref[0, 0]              # [d_v, d]: v along the sublanes

        def body(r, acc):
            s = (s_ref[0, 0, r] * g_row
                 + v_cols * pk_ref[0, 0, pl.ds(r, 1), :])
            s_out_ref[0, 0, r] = s
            return acc + jax.lax.dot_general(
                pq_ref[0, 0, r].astype(jnp.bfloat16), s.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

        num_ref[0, 0] = jax.lax.fori_loop(
            0, rows, body, jnp.zeros(num_ref.shape[2:], jnp.float32))


def _step_plain(g, v, phi_k, phi_q, S):
    """``(S_new, num)`` of one decode step in ``jax.numpy``: what runs
    off the TPU, and what the tests hold the kernel to.  Every row is
    computed: ``_step`` has made a row without a request the
    identity."""
    S = (g[..., None, None, None] * S
         + v[:, :, None, :, None] * phi_k[:, :, :, None, :])
    return S, jnp.einsum("bkgri,bkrvi->bkgv", phi_q, S)


def _visits(valid, batch: int):
    """The rows a step visits: ``rows [B] int32``, the valid rows first
    in slot order and the last of them again in every place after, and
    ``n [1] int32``, how many places count.  A step with no valid row
    visits row 0: a grid that visited nothing would still write its one
    never-filled output buffer back over a block of the state, and
    ``_step`` has made the visit of a row that is not valid the
    identity."""
    if valid is None:
        return (jnp.arange(batch, dtype=jnp.int32),
                jnp.full((1,), batch, jnp.int32))
    slot = jnp.arange(batch, dtype=jnp.int32)
    place = jnp.cumsum(valid, dtype=jnp.int32) - 1    # of a valid row
    # No sort and no scatter: [B, B] compares (B is the slots).
    rows = jnp.sum(jnp.where(valid[:, None] & (place[:, None] == slot),
                             slot[:, None], 0), axis=0)
    n = jnp.maximum(place[-1] + 1, 1)
    last = jnp.max(jnp.where(valid, slot, 0))
    return jnp.where(slot < n, rows, last), n[None]


def _step_pallas(g, v, phi_k, phi_q, S, valid=None, *, interpret: bool):
    """``(S_new, num)`` of one decode step through the kernel.  ``g [B,
    K]``, ``v [B, K, d]``, ``phi_k [B, K, R, d]``, ``phi_q [B, K, G, R,
    d]``, ``S [B, K, R, d, d]``, all float32; ``valid [B]`` (None: every
    row).  Only the valid rows' blocks of ``S`` are copied in and out:
    the grid is ``(B, K)`` whatever ``valid`` holds (one program), step
    ``(b, k)`` works on block ``(rows[b], k)`` while ``b`` is under the
    number of rows to visit, and every later step names the last live
    step's block again — for every operand and result, so that the
    pipeline sees a block index that did not change and issues no copy
    — and computes nothing.  ``num`` of a row not visited is memory
    nobody wrote."""
    B, K, R, d_v, d = S.shape
    G = phi_q.shape[2]
    Gp = -(-G // _SUBLANES) * _SUBLANES
    # The small operands as the kernel reads them: g along a row, v
    # down the sublanes of a [d_v, d] tile, phi(q) with the group as
    # the rows of each feature row's tile.
    g_rows = jnp.broadcast_to(g[:, :, None, None], (B, K, 1, d))
    v_cols = jnp.broadcast_to(v[..., None], (B, K, d_v, d))
    pq = jnp.pad(jnp.moveaxis(phi_q, 2, 3),
                 ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
    per_head = lambda *tail: pl.BlockSpec(                  # noqa: E731
        (1, 1) + tail,
        lambda b, k, rows, n: (rows[b], jnp.where(b < n[0], k, K - 1))
        + (0,) * len(tail))
    S_new, num = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K),
            in_specs=[per_head(1, d), per_head(d_v, d), per_head(R, d),
                      per_head(R, Gp, d), per_head(R, d_v, d)],
            out_specs=[per_head(R, d_v, d), per_head(Gp, d_v)]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, K, Gp, d_v), jnp.float32)],
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            # In order: the steps past the last row visited stay on its
            # last block.
            dimension_semantics=("arbitrary", "arbitrary"),
            # One head's S in and out, each double-buffered.
            vmem_limit_bytes=int(4.5 * R * d_v * d * 4) + (8 << 20)),
        name="hvd_tpu_retention_step",
        interpret=interpret,
    )(*_visits(valid, B), g_rows, v_cols, phi_k, pq, S)
    return S_new, num[:, :, :G]


def retention_step(q, k, v, log_g, state: tuple, valid=None, *,
                   interpret: Optional[bool] = None):
    """Decode: one token a row, one read-modify-write of the state.
    ``q [B, H, d]``, ``k, v [B, K, d]``, ``log_g [B, K]``, ``state`` the
    ``(S, z)`` of :func:`state_shapes`; ``valid [B]`` marks rows that
    hold a request (the others leave their state as it is, and their
    output row is zero).  On the TPU
    the state goes through the Pallas kernel, elsewhere through the same
    arithmetic in ``jax.numpy``; ``interpret`` is the tree-wide escape
    hatch of a kernel (True: the kernel under the interpreter, which is
    how the tests reach it; False: the kernel compiled wherever this
    runs, which is how it is compiled for a described chip).  Returns
    ``(o [B, H, d] float32, (S, z))``."""
    if interpret is None and jax.default_backend() != "tpu":
        update = _step_plain
    else:
        update = functools.partial(_step_pallas, valid=valid,
                                   interpret=bool(interpret))
    return _step(update, q, k, v, log_g, state, valid)


def _step(update, q, k, v, log_g, state, valid):
    B, H, d = q.shape
    K = k.shape[1]
    S, z = state
    g = jnp.exp(log_g.astype(jnp.float32))
    phi_k = expand(k.astype(jnp.float32))                 # [B, K, R, d]
    if valid is not None:
        g = jnp.where(valid[:, None], g, 1.0)
        phi_k = jnp.where(valid[:, None, None, None], phi_k, 0.0)
    phi_q = expand(q.reshape(B, K, H // K, d).astype(jnp.float32), 1.0 / d)
    S, num = update(g, v.astype(jnp.float32), phi_k, phi_q, S)
    if valid is not None:
        # The kernel wrote no read-out for a row it did not visit.
        num = jnp.where(valid[:, None, None, None], num, 0.0)
    z = g[..., None, None] * z + phi_k
    # z is small: its read-out is exact float32 at no cost worth counting.
    den = jnp.einsum("bkgri,bkri->bkg", phi_q, z,
                     precision=jax.lax.Precision.HIGHEST)
    return (num / (den[..., None] + EPS)).reshape(B, H, d), (S, z)
