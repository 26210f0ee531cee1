"""Mixture-of-Experts FFN with expert parallelism (the ``ep`` mesh axis).

No reference analogue — Horovod has no expert parallelism (SURVEY.md
§2.9); this is a first-class capability of the TPU rebuild.  Technique
per the GShard line of work: a learned top-k router assigns each token
to experts under a fixed per-expert capacity (static shapes — XLA needs
them), dispatch/combine are einsums against a one-hot capacity tensor,
and the expert dimension of the weights is sharded over ``ep`` so GSPMD
inserts the all-to-alls that move token blocks to their experts' chips
(over ICI).  The router runs in float32 (softmax numerics), experts in
the model dtype (MXU).

Load balancing: the standard auxiliary loss (mean gate fraction × mean
dispatch fraction × E²) is sown under ``intermediates/moe_aux_loss``;
:func:`moe_aux_loss` sums it from a model's captured intermediates.

Beside it, :class:`DroplessExperts`: the expert layer of the sigmoid-
routed families (a selection bias, top-k normalised and scaled, a
shared expert) and of those that take a softmax over all experts
before the top-k, as ONE chip of an expert-parallel job runs it.  The
router scores every expert; the token-expert pairs are sorted by
expert; the pairs of experts this chip does not hold are discarded
before any expert arithmetic; a grouped matrix product (the Pallas
kernels of ``jax.experimental.pallas.ops.tpu.megablox``, whose grid is
the tiles the groups really fill) runs over the held experts' rows,
however unevenly they fall; the weighted results go back to their
tokens.
Nothing is dropped and nothing stands in for the absent chips: their
part of the sum is theirs to add.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _constrain(x, spec: P):
    """Best-effort sharding hint: annotate under jit, no-op outside."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def _expert_axes():
    """(expert, tensor) axis names for the sharding hints, derived from
    the session :class:`~horovod_tpu.plan.MeshPlan` at trace time: the
    planner's ``expert``/``tensor`` names when declared, else the legacy
    short names — so the same module body serves both vocabularies."""
    from .. import basics

    plan = basics.peek("mesh_plan")
    if plan is not None:
        return ("expert" if plan.has_axis("expert") else "ep",
                "tensor" if plan.has_axis("tensor") else "tp")
    return "ep", "tp"


class MoEMlp(nn.Module):
    """Drop-in replacement for the transformer's dense FFN block.

    ``[B, T, C] -> [B, T, C]``; ``n_experts`` expert FFNs, each token
    routed to its ``top_k`` highest-gate experts, capacity
    ``ceil(top_k * tokens / n_experts * capacity_factor)`` per expert.
    Route weights are the top-k gates normalized *before* capacity
    drops, so an overflowed route simply loses its share (GShard
    semantics) — survivors are never amplified.
    """

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        E = self.n_experts
        K = min(self.top_k, E)
        S = B * T
        cap = max(1, math.ceil(K * S / E * self.capacity_factor))

        xf = x.reshape(S, C)

        # --- router (float32) ------------------------------------------------
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)               # [S, E]

        # --- top-k assignment with capacity (GShard) -------------------------
        dispatch = jnp.zeros((S, E, cap), jnp.float32)
        slots = []
        remaining = gates
        # Tokens already slotted per expert accumulate across the k rounds
        # so round k's positions start after round k-1's.
        fill = jnp.zeros((E,), jnp.int32)
        topk_gates = []
        masks = []
        for _ in range(K):
            idx = jnp.argmax(remaining, axis=-1)              # [S]
            mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [S, E]
            gate_k = jnp.sum(gates * mask, axis=-1)           # [S]
            # Position of each token inside its expert's capacity buffer.
            pos = (jnp.cumsum(mask, axis=0) - 1.0) + fill[None, :].astype(
                jnp.float32)
            pos = jnp.sum(pos * mask, axis=-1)                # [S]
            keep = (pos < cap) & (gate_k > 0)
            # one_hot wants integer positions (float indices deprecate in
            # jax 0.9); pos comes from a float cumsum.
            pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                    dtype=jnp.float32)  # [S, cap]
            slot = mask[:, :, None] * pos_oh[:, None, :]      # [S, E, cap]
            slot = slot * keep[:, None, None]
            dispatch = dispatch + slot
            slots.append(slot)
            fill = fill + jnp.sum(mask * keep[:, None],
                                  axis=0).astype(jnp.int32)
            remaining = remaining * (1.0 - mask)
            topk_gates.append(gate_k)
            masks.append(mask)

        # Route weights: top-k gates normalized BEFORE capacity drops, so
        # a dropped route's share is lost, not redistributed.
        denom = jnp.maximum(sum(topk_gates), 1e-9)            # [S]
        combine = sum(
            slot * (gate_k / denom)[:, None, None]
            for slot, gate_k in zip(slots, topk_gates))

        # --- load-balancing auxiliary loss -----------------------------------
        me = jnp.mean(gates, axis=0)                          # [E]
        ce = jnp.mean(masks[0], axis=0)                       # top-1 fraction
        self.sow("intermediates", "moe_aux_loss",
                 jnp.sum(me * ce) * E * E)

        # --- expert computation (ep-sharded) ---------------------------------
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (E, C, self.d_ff), self.param_dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (E, self.d_ff, C), self.param_dtype)

        ep_ax, tp_ax = _expert_axes()
        expert_in = jnp.einsum("sec,sd->ecd", dispatch.astype(self.dtype),
                               xf.astype(self.dtype))         # [E, cap, C]
        expert_in = _constrain(expert_in, P(ep_ax, None, None))
        h = jnp.einsum("ecd,edf->ecf", expert_in,
                       w_up.astype(self.dtype))
        h = nn.gelu(h)
        h = _constrain(h, P(ep_ax, None, tp_ax))
        out_e = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        out_e = _constrain(out_e, P(ep_ax, None, None))
        out = jnp.einsum("sec,ecd->sd", combine.astype(self.dtype), out_e)
        return out.reshape(B, T, C)


def moe_aux_loss(intermediates, weight: float = 1e-2) -> jnp.ndarray:
    """Sum the sown load-balancing losses from
    ``model.apply(..., mutable=['intermediates'])`` captures."""
    total = jnp.float32(0.0)
    n = 0
    for leaf in jax.tree_util.tree_leaves(intermediates):
        total = total + jnp.sum(jnp.asarray(leaf, jnp.float32))
        n += 1
    if n == 0:
        return jnp.float32(0.0)
    return weight * total / n


# --- dropless experts, sigmoid- or softmax-routed ----------------------------

@jax.custom_vjp
def _take(x, idx, inv):
    """``x[idx]``: rows of ``x [M, C]`` as the sorted pairs want them.
    ``inv [M, J]`` lists for each row of ``x`` the places it went to
    (``len(idx)`` where it went to fewer than ``J``); with it the
    transpose is a gather too (each row's ``J`` places, summed), where
    the gather's own would be a scatter-add."""
    return x[idx]


def _take_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _put(rows, idx, inv):
    """The transpose of :func:`_take`: ``out[m]`` is the sum of the
    rows that came from ``m``."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return padded[inv].sum(axis=1)


def _take_bwd(res, g):
    return _put(g, *res), None, None


_take.defvjp(_take_fwd, _take_bwd)
_put = jax.custom_vjp(_put)
_put.defvjp(lambda rows, idx, inv: (_put(rows, idx, inv), (idx, inv)),
            lambda res, g: (_take(g, *res), None, None))


def route(scores, bias, top_k: int, scale: float):
    """Which experts a token goes to, and with what weight: the
    ``top_k`` largest of ``scores + bias`` (``scores [S, E]``: the
    router's scores, each logit's sigmoid or the softmax over all
    experts, as the layer's ``scoring`` says; ``bias [E]`` only selects
    and carries no gradient), weighted by their scores over the sum of
    the chosen, times ``scale``.  Returns ``(experts [S, K] int32, weights [S, K])``."""
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return experts.astype(jnp.int32), weights


def held_pairs(experts, held: Tuple[int, int]):
    """The token-expert pairs sorted by expert, those of the ``held``
    range ``(offset, count)`` first and the rest, which this chip has
    nothing to compute for, behind them.  Returns ``(order [S * K],
    place [S * K] (its inverse), sizes [count] (pairs of each held
    expert))``.  Two sorts and a comparison: no scatter."""
    offset, count = held
    local = (experts - offset).reshape(-1)
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)
    return order, place, sizes


# The sorted rows a layer works on when the load allows: this many times
# the pairs an even router would send here, in whole tiles of the
# grouped products.  A heavier
# load takes every row (the second branch of one ``cond``).  Read on a
# v5e (PR 38: 8 of 128 experts held, top 6 of 8,192 tokens, the
# selection bias at zero, so that nothing balances the router): over a
# training run's first dozen steps a layer's held load reached 3.9
# times the even one, and then stayed between 0.5 and 2.4 times it.
HEADROOM = 4
# Tiles (rows, contracted, result) of the grouped products, read on a
# v5e at 2688 x 1856 (PR 38): the six products of one layer's forward
# and backward take 3.41 ms over 3,116 rows in 8 groups so, 2.85 at 256
# rows a tile (fewer half-empty tiles; 16 % more a row where the tiles
# are full, as here), 19.4 at (128, 128, 128), and 11.5 through
# ``jax.lax.ragged_dot``.
GMM_TILES = (512, 1024, 1024)
# Where the groups are many and hold few rows each — fewer than
# SPARSE_ROWS a group: 128 experts all held under a block step's 1,536
# pairs, twelve a group — the row tile is SPARSE_TILE.  A tile visits
# every group it touches with all of its rows, so the products do about
# ``rows + groups x tile`` rows' worth of work, and at 512 that is
# forty times the pairs' own (PERF.md, PR 44, has the reading).
SPARSE_ROWS, SPARSE_TILE = 32, 128


def grouped_dot(rows, kernels, sizes, dtype, interpret: bool):
    """``rows[r] @ kernels[g]`` for the sorted rows ``r`` of each group
    ``g`` (``sizes [G]`` rows each, in order); rows past the groups are
    left as they fall.  ``rows [M, K]``, ``kernels [G, K, N]``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    sparse = rows.shape[0] < SPARSE_ROWS * kernels.shape[0]
    tm = math.gcd(rows.shape[0], SPARSE_TILE if sparse else GMM_TILES[0])
    if tm < 8 and not interpret:
        raise ValueError(f"{rows.shape[0]} rows (tokens x top_k) do not "
                         f"divide into tiles of 8 or more")
    return gmm(rows, kernels, sizes, dtype, (tm,) + GMM_TILES[1:],
               interpret=interpret)


def usual_rows(pairs: int, count: int, n_experts: int) -> int:
    """How many sorted rows hold every held pair under a load of up to
    ``HEADROOM`` times the even one."""
    from ..ops.pallas_common import round_up

    even = -(-pairs * count // n_experts)
    return min(pairs, round_up(HEADROOM * even, GMM_TILES[0]))


class DroplessExperts(nn.Module):
    """``[B, T, C] -> [B, T, C]``: ``sum_e w_e expert_e(x)`` over the
    held ones of a token's ``top_k`` experts, plus the shared expert.
    The router is float32: logits over all ``n_experts``, scored by
    ``scoring`` — ``'sigmoid'`` (each logit's own) or ``'softmax'``
    (over all experts, before the top-k) — and the chosen scores
    renormalised over their sum (:func:`route`).
    An expert is ``down(relu(up(x)) ** 2)`` (squared ReLU, not gated)
    or, ``gated``, ``down(silu(gate(x)) * up(x))``; the shared expert
    is of the same form.  ``held = (offset, count)``
    is the contiguous range of the ``n_experts`` that this chip holds
    (None = all): the router keeps every column, the stacked kernels
    hold ``count`` experts.  With ``shared_d_ff = 0`` there is no shared
    expert.

    The held pairs lie first among the sorted ones, so the layer
    gathers, multiplies and un-sorts the first :func:`usual_rows` rows
    only, and those at one cost whatever the router sent (a step's time
    does not follow the router's mood) — and all ``S x K`` rows in a
    step whose router sent more than that here: no load drops a
    pair.  A decode step of the serving engine is the same path at a
    small shape — 48 rows of 8 experts are 384 pairs, fewer than
    ``usual_rows`` would keep, so every pair is a sorted row, most held
    experts get none, and the grouped products read the weights of
    those that got one."""

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    shared_d_ff: int = 0
    scale: float = 1.0
    held: Optional[Tuple[int, int]] = None
    gated: bool = False
    scoring: str = "sigmoid"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    interpret: Optional[bool] = None   # ops/pallas_common.resolve_interpret

    @nn.compact
    def __call__(self, x):
        from ..ops.pallas_common import resolve_interpret

        B, T, C = x.shape
        E, K = self.n_experts, self.top_k
        offset, count = self.held or (0, E)
        if not (0 <= offset and 0 < count and offset + count <= E):
            raise ValueError(f"held={self.held!r} is no range of "
                             f"{E} experts")
        S = B * T
        xf = x.reshape(S, C)
        init = nn.initializers.normal(0.02)
        up = self.param("up", init, (count, C, self.d_ff), self.param_dtype)
        gate = (self.param("gate", init, (count, C, self.d_ff),
                           self.param_dtype) if self.gated else None)
        down = self.param("down", init, (count, self.d_ff, C),
                          self.param_dtype)
        bias = self.param("select_bias", nn.initializers.zeros, (E,),
                          jnp.float32)

        with jax.named_scope("hvd_tpu_moe_route"):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              param_dtype=self.param_dtype,
                              precision=jax.lax.Precision.HIGHEST,
                              kernel_init=init, name="router")(
                xf.astype(jnp.float32))
            if self.scoring not in ("sigmoid", "softmax"):
                raise ValueError(f"Unknown scoring {self.scoring!r}")
            scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                      else jax.nn.softmax(logits, axis=-1))
            experts, weights = route(scores, bias, K, self.scale)
            order, place, sizes = held_pairs(experts, (offset, count))
            # A counter for who asks (``mutable=["intermediates"]``):
            # the pairs each held expert was sent.
            self.sow("intermediates", "pairs_held", sizes)
            total = sizes.sum()

        def held_part(n: int, even_cost: bool):
            """The routed sum, from the first ``n`` sorted pairs.  With
            ``even_cost`` the rows past the held pairs count as the last
            held expert's (zero rows in, zero rows out): the grouped
            products then fill every tile of the ``n`` rows whatever the
            router sent, and a step costs what any other does."""
            with jax.named_scope("hvd_tpu_moe_route"):
                token, slot = order[:n] // K, jnp.minimum(place, n)
                # Rows past the held pairs belong to no group, and what
                # a grouped product (or its transpose) leaves there is
                # not defined: they are zeroed on the way in, which
                # zeroes their cotangent on the way back, and on the
                # way out.
                live = (jnp.arange(n) < total)[:, None]
                rows = jnp.where(live, _take(xf, token, slot.reshape(S, K)),
                                 0)
                w = _take(weights.reshape(S * K, 1), order[:n],
                          slot[:, None])
            with jax.named_scope("hvd_tpu_moe_experts"):
                grouped = functools.partial(
                    grouped_dot, dtype=self.dtype,
                    sizes=(sizes.at[-1].add(n - total) if even_cost
                           else sizes),
                    interpret=resolve_interpret(self.interpret))
                h = grouped(rows, up.astype(self.dtype))
                h = (nn.silu(grouped(rows, gate.astype(self.dtype))) * h
                     if self.gated else jnp.square(nn.relu(h)))
                rows = grouped(h, down.astype(self.dtype))
            with jax.named_scope("hvd_tpu_moe_route"):
                rows = jnp.where(live, rows, 0).astype(jnp.float32) * w
                return _put(rows, token, slot.reshape(S, K))

        n = usual_rows(S * K, count, E)
        if n < S * K:
            out = jax.lax.cond(total <= n, lambda: held_part(n, True),
                               lambda: held_part(S * K, False))
        else:
            out = held_part(n, False)
        out = out.astype(self.dtype)
        if self.shared_d_ff:
            with jax.named_scope("hvd_tpu_moe_shared"):
                dense = functools.partial(
                    nn.Dense, use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype, kernel_init=init)
                h = dense(self.shared_d_ff, name="shared_up")(xf)
                h = (nn.silu(dense(self.shared_d_ff,
                                   name="shared_gate")(xf)) * h
                     if self.gated else jnp.square(nn.relu(h)))
                out = out + dense(C, name="shared_down")(h)
        return out.reshape(B, T, C)
