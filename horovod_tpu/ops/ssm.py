"""The selective state-space scan of Mamba-2 (arXiv:2405.21060,
"Transformers are SSMs"): a per-head scalar decay, input and read-out
vectors shared by groups of heads, and a state of ``[P, N]`` numbers a
head whatever the context length.

For head ``h`` of size ``P`` reading group ``g(h) = h // (H / G)``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T          (h: [P, N], float32)
    y_t = h_t C_t + D x_t

with ``x_t [P]``, ``dt_t > 0`` (after its softplus), ``A < 0`` and ``D``
one scalar a head, ``B_t, C_t [N]`` of the head's group.

Two forms of the one operator live here and the tests hold them equal,
values and gradients: :func:`ssm_recurrent` (the definition, one token
at a time through :func:`ssm_step`, which is also what a decode step
needs) and :func:`ssm_chunked` (the training-shaped forward: inside a
chunk the quadratic dual — masked, decayed ``C_i . B_j`` scores against
the inputs, matrix products for the MXU — and between chunks one pass
over the states the chunks leave).  Both are plain ``jax.numpy`` and
differentiated as written.

The convolution in front of the scan and the gated norm behind it are
the layer's (``models/transformer.py::Mamba2``), not the operator's.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

CHUNK = 128     # tokens per chunk of the chunked form, as published


def state_shape(batch: int, heads: int, head_dim: int, state: int) -> tuple:
    """Shape of one layer's state for ``batch`` rows."""
    return (batch, heads, head_dim, state)


def _heads_per_group(heads: int, groups: int) -> int:
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    return heads // groups


def ssm_step(x, dt, A, Bm, Cm, D, state):
    """One token.  ``x [B, H, P]``, ``dt [B, H]``, ``A, D [H]``,
    ``Bm, Cm [B, G, N]``, ``state [B, H, P, N]`` float32.  Returns
    ``(y [B, H, P] float32, state)``; every product in float32."""
    R = _heads_per_group(x.shape[1], Bm.shape[1])
    x32, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = jnp.repeat(Bm.astype(jnp.float32), R, axis=1)        # [B, H, N]
    Ch = jnp.repeat(Cm.astype(jnp.float32), R, axis=1)
    decay = jnp.exp(dt * A.astype(jnp.float32))
    state = (decay[..., None, None] * state
             + (dt[..., None] * x32)[..., None] * Bh[:, :, None, :])
    y = jnp.sum(state * Ch[:, :, None, :], axis=-1)
    return y + D.astype(jnp.float32)[:, None] * x32, state


def ssm_recurrent(x, dt, A, Bm, Cm, D, state=None):
    """The definition, a token at a time.  ``x [B, T, H, P]``,
    ``dt [B, T, H]``, ``Bm, Cm [B, T, G, N]``; ``state`` is the one
    before the first token (None = zeros).  Returns ``(y [B, T, H, P]
    float32, state [B, H, P, N])``."""
    B, T, H, P = x.shape
    if state is None:
        state = jnp.zeros(state_shape(B, H, P, Bm.shape[-1]), jnp.float32)

    def body(state, xs):
        y, state = ssm_step(*xs[:2], A, *xs[2:], D, state)
        return state, y

    state, y = jax.lax.scan(
        body, state, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state


def _mm(eq: str, a, b):
    """``einsum`` with both inputs in ``a``'s dtype (bfloat16 in the
    program, float32 in the tests) and a float32 result."""
    return jnp.einsum(eq, a, b.astype(a.dtype),
                      preferred_element_type=jnp.float32)


def ssm_chunked(x, dt, A, Bm, Cm, D, state: Optional[jax.Array] = None,
                chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """The same numbers in chunks of ``chunk`` tokens.  The products
    take their inputs in ``x``'s dtype and accumulate in float32; the
    decays, the state and the result are float32.  ``T`` need not be a
    multiple of ``chunk``: the padding has ``dt = 0``, so it neither
    decays the state nor enters it.  Returns ``(y [B, T, H, P] float32,
    state [B, H, P, N])``."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = _heads_per_group(H, G)
    Q = min(chunk, T)
    n = -(-T // Q)
    if state is None:
        state = jnp.zeros(state_shape(B, H, P, N), jnp.float32)

    def chunks(a, *tail):
        a = jnp.pad(a, ((0, 0), (0, n * Q - T)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((B, n, Q) + (tail or a.shape[2:]))

    xc, Bc, Cc = chunks(x, G, R, P), chunks(Bm), chunks(Cm)
    dtc = chunks(dt.astype(jnp.float32), G, R)             # [B, n, Q, G, R]
    xdt = xc.astype(jnp.float32) * dtc[..., None]          # dt_j x_j
    # Running log-decay inside each chunk, and what is left of a token's
    # input when its chunk ends.
    cum = jnp.cumsum(dtc * A.astype(jnp.float32).reshape(G, R), axis=2)
    last = cum[:, :, -1]                                   # [B, n, G, R]
    left = jnp.exp(last[:, :, None] - cum)

    # Inside a chunk: y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j.
    scores = _mm("bcign,bcjgn->bcgij", Cc, Bc)             # [B, n, G, Q, Q]
    decay = (jnp.moveaxis(cum, 2, -1)[..., :, None]
             - jnp.moveaxis(cum, 2, -1)[..., None, :])     # [B, n, G, R, i, j]
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    weights = scores[:, :, :, None] * jnp.exp(jnp.where(seen, decay, -jnp.inf))
    y = _mm("bcgrij,bcjgrp->bcigrp", weights.astype(x.dtype),
            xdt.astype(x.dtype))

    # The state each chunk leaves, and one pass over them: the state
    # before every chunk.
    leaves = _mm("bcjgrp,bcjgn->bcgrpn",
                 (xdt * left[..., None]).astype(x.dtype), Bc)

    def body(h, xs):
        leaves_c, carry_c = xs
        return carry_c[..., None, None] * h + leaves_c, h

    state, before = jax.lax.scan(
        body, state.reshape(B, G, R, P, N),
        (jnp.moveaxis(leaves, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # [B, n, G, R, P, N]
    y = y + jnp.exp(cum)[..., None] * _mm(
        "bcign,bcgrpn->bcigrp", Cc, before)
    y = y + D.astype(jnp.float32).reshape(G, R, 1) * xc.astype(jnp.float32)
    return (y.reshape(B, n * Q, H, P)[:, :T], state.reshape(B, H, P, N))
