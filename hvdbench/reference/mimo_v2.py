"""Plain reference of the ``mimo_v2`` family (MiMo-V2-Flash,
``model_type`` ``mimo_v2_flash``): a decoder whose attention layers are
of two kinds — *full* (every earlier position; 4 KV heads; rotary base
5e6) and *window* (the last 128 positions; 8 KV heads; base 1e4; a
learned sink logit a head) — with keys 192 and values 128 wide, rotary
positions on the first 64 numbers of a head, values scaled by 0.707,
and whose feed-forward is a gated SiLU layer in layer 0 and dropless
sigmoid-routed gated experts (top 8 of 256, no shared expert) in the
others.  For layer ``l`` of kind ``k``::

    n   = RMSNorm(x)
    q   = n W_q -> [T, H, 192];  k = n W_k -> [T, K_k, 192];  v = n W_v -> [T, K_k, 128]
    q,k = rotary on the first 64 numbers of a head (half-split), theta_k; the other 128 pass
    v   = 0.707 v
    s_ij = q_i . k_j / sqrt(192)  for j <= i, and for window layers i - j < 128
    p_ij = exp(s_ij - m_i) / (sum_j exp(s_ij - m_i) + [k = window] exp(sink_h - m_i))
    x   = x + (sum_j p_ij v_j).reshape(T, H * 128) W_o
    n2  = RMSNorm(x)
    l = 0:  x = x + down(silu(gate n2) * up n2)
    l >= 1: s = sigmoid(n2 W_r) in float32; chosen = top-8 of (s + b);
            w = s[chosen] / sum s[chosen]
            x = x + sum_{e chosen and held here} w_e down_e(silu(gate_e n2) * up_e n2)

and ``logits = RMSNorm(x_L) W_head`` over the vocabulary's slice.  What
the absent experts would have added is left out (the chip's share of a
stated deployment; the program leaves out the same).

Straightforward ``jax.numpy``, float32, ``precision=HIGHEST`` unless a
lower ``precision`` is asked for (the control that ``correct`` has to
fail).  No cache, no kernels, no batching; the queries go in blocks so
that a layer's scores fit.  It imports nothing of the program under
test and makes its own weights from the seed.  What ``config.json`` does
not say is the configuration file's ``assumed``.

**It streams its weights.**  In float32 eleven layers and the slice of
the vocabulary are 21.7 GB, more than a chip holds, so the reference
never has its tree: :func:`init_params` returns the seed's key, and the
forward makes, uses and frees the embedding, each layer's leaves and
the head in turn (one compiled program for each of the three sorts of
layer; the layer's index is data).  The model is published in bfloat16,
so a weight *is* a bfloat16 value: :func:`make_leaf` rounds what it
draws to bfloat16 and hands it out in float32, and the program holds
the very same numbers.  The sinks are float32, as the model keeps them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from hvdbench.reference import gpt2 as _shared

HIGHEST = jax.lax.Precision.HIGHEST
SINK_STD = 2.0
_QUERY_BLOCK = 256

matmul = _shared.matmul        # einsum in f32 (HIGHEST), bf16 or scaled fp8
seed_key = _shared.seed_key
_act = _shared._act            # the controls keep activations in bfloat16

ATTN_LEAVES = ("ln1", "wq", "wk", "wv", "sink", "wo", "ln2")
DENSE_LEAVES = ("gate", "up", "down")
EXPERT_LEAVES = ("router", "bias", "e_gate", "e_up", "e_down")
TOP_LEAVES = ("wte", "lnf", "head")
_ALL = ATTN_LEAVES + DENSE_LEAVES + EXPERT_LEAVES + TOP_LEAVES


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    L = int(config["num_hidden_layers"])
    pattern = tuple(int(x) for x in config["hybrid_layer_pattern"])
    moe = tuple(int(x) for x in config["moe_layer_freq"])
    if len(pattern) != L or len(moe) != L:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq state "
                         "one entry a layer")
    held = config["run"]["experts_held"]
    if int(held["count"]) != int(config["n_routed_experts"]):
        raise ValueError("n_routed_experts states the experts held here")
    if config.get("n_shared_experts") or int(config["n_group"]) != 1:
        raise ValueError("the mimo_v2 family has no shared expert and no "
                         "group stage")
    if (int(config["swa_head_dim"]) != int(config["head_dim"])
            or int(config["swa_v_head_dim"]) != int(config["v_head_dim"])
            or int(config["swa_num_attention_heads"])
            != int(config["num_attention_heads"])):
        raise ValueError("window layers share the full layers' head count "
                         "and widths in this family")
    D = int(config["head_dim"])
    return dict(
        V=int(config["vocab_size"]), L=L, d=int(config["hidden_size"]),
        H=int(config["num_attention_heads"]),
        K_full=int(config["num_key_value_heads"]),
        K_window=int(config["swa_num_key_value_heads"]),
        D=D, Dv=int(config["v_head_dim"]),
        rot=int(D * float(config["partial_rotary_factor"])) // 2 * 2,
        theta_full=float(config["rope_theta"]),
        theta_window=float(config["swa_rope_theta"]),
        window=int(config["sliding_window"]),
        sink_full=bool(config["add_full_attention_sink_bias"]),
        sink_window=bool(config["add_swa_attention_sink_bias"]),
        vscale=float(config["attention_value_scale"]),
        ff=int(config["intermediate_size"]),
        eff=int(config["moe_intermediate_size"]),
        E=int(config["run"]["router_outputs"]),
        held=(int(held["offset"]), int(held["count"])),
        top_k=int(config["num_experts_per_tok"]),
        scale=float(config["routed_scaling_factor"] or 1.0),
        eps=float(config["layernorm_epsilon"]),
        pattern=pattern, moe=moe)


def kind_of(s: dict, layer: int) -> str:
    return "window" if s["pattern"][layer] else "full"


def leaf_shape_std(name: str, s: dict, kind: str = "full"):
    """Shape and init of a leaf: a std for a matrix (normal(0.02), the
    residual projections scaled by 1/sqrt(2 L)) or a sink, None for a
    norm's scale (ones), "zero" for the selection bias."""
    d, V, L = s["d"], s["V"], s["L"]
    H, K, D, Dv = s["H"], s["K_" + kind], s["D"], s["Dv"]
    ff, eff, count = s["ff"], s["eff"], s["held"][1]
    resid = 0.02 / math.sqrt(2 * L)
    return {
        "wte": ((V, d), 0.02), "head": ((d, V), 0.02), "lnf": ((d,), None),
        "ln1": ((d,), None), "ln2": ((d,), None),
        "wq": ((d, H * D), 0.02), "wk": ((d, K * D), 0.02),
        "wv": ((d, K * Dv), 0.02), "sink": ((H,), SINK_STD),
        "wo": ((H * Dv, d), resid),
        "gate": ((d, ff), 0.02), "up": ((d, ff), 0.02),
        "down": ((ff, d), resid),
        "router": ((d, s["E"]), 0.02), "bias": ((s["E"],), "zero"),
        "e_gate": ((count, d, eff), 0.02), "e_up": ((count, d, eff), 0.02),
        "e_down": ((count, eff, d), resid),
    }[name]


def make_leaf(key, name: str, layer, s: dict, kind: str = "full"):
    """One parameter leaf from the seed: a bfloat16 value in float32 (a
    sink: float32 as drawn).  ``layer`` is -1 for a leaf outside the
    blocks, and may be traced; ``kind`` is the layer's."""
    shape, std = leaf_shape_std(name, s, kind)
    if std is None:
        return jnp.ones(shape, jnp.float32)
    if std == "zero":
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, _ALL.index(name)),
                           layer + 1)
    drawn = std * jax.random.normal(k, shape, jnp.float32)
    if name == "sink":
        return drawn
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def layer_leaves(s: dict, layer: int):
    """The names of ``layer``'s leaves."""
    kind = kind_of(s, layer)
    names = [n for n in ATTN_LEAVES if n != "sink" or s["sink_" + kind]]
    return names + list(EXPERT_LEAVES if s["moe"][layer] else DENSE_LEAVES)


def init_params(seed_key_, s: dict):
    """What the forward needs to make any leaf: the key.  The tree
    itself is never held (21.7 GB in float32)."""
    del s
    return seed_key_


# --- the block ---------------------------------------------------------------

def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _rope(x, theta, rot: int):
    """Rotary positions on the first ``rot`` numbers of each head of
    ``x [B, T, N, D]``, positions 0..T-1, the half-split convention;
    the rest pass."""
    T, half = x.shape[1], rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def attention(q, k, v, sink, window: int, precision: str):
    """Causal softmax attention, ``q [B, T, H, D]``, ``k [B, T, K, D]``,
    ``v [B, T, K, Dv]``; query head ``h`` reads KV head ``h // (H /
    K)``; with ``window`` a query sees the last ``window`` positions,
    itself among them; ``sink [H]`` (or None) joins the denominator."""
    B, T, H, D = q.shape
    K = k.shape[2]
    qg = q.reshape(B, T, K, H // K, D)
    block = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=1)
        scores = matmul(qb, k, precision, "bqkgd,bjkd->bkgqj") / math.sqrt(D)
        back = (start + jnp.arange(block))[:, None] - jnp.arange(T)[None]
        seen = back >= 0
        if window:
            seen &= back < window
        scores = jnp.where(seen, scores, -jnp.inf)
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(sink.reshape(K, H // K)[None, :, :, None,
                                                        None] - m)
        return matmul(_act(e / den, precision), v, precision,
                      "bkgqj,bjkd->bqkgd")

    att = jax.lax.map(rows, jnp.arange(0, T, block))   # [T/block, B, block, ..]
    return jnp.moveaxis(att, 0, 1).reshape(B, T, H * v.shape[-1])


def route(x, lp, s: dict):
    """``(experts [.., top_k], weights [.., top_k])`` of every token:
    float32 whatever the precision."""
    scores = jax.nn.sigmoid(
        jnp.einsum("...d,de->...e", x, lp["router"], precision=HIGHEST))
    _, experts = jax.lax.top_k(scores + lp["bias"], s["top_k"])
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = (chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
               * s["scale"])
    return experts, weights


def _proj(h, w, precision, eq="btd,de->bte"):
    return _act(matmul(_act(h, precision), w, precision, eq), precision)


def experts_layer(x, lp, s: dict, precision: str):
    """The held experts' part of the routed sum, on the normed input."""
    x = _act(x, precision)
    experts, weights = route(x, lp, s)
    offset, count = s["held"]

    def one(out, e):
        w_e = jnp.sum(jnp.where(experts == offset + e, weights, 0.0),
                      axis=-1)
        h = _act(jax.nn.silu(_proj(x, lp["e_gate"][e], precision))
                 * _proj(x, lp["e_up"][e], precision), precision)
        return out + w_e[..., None] * _proj(h, lp["e_down"][e], precision,
                                            "btf,fd->btd"), None

    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))[0]


def attention_half(x, lp: dict, s: dict, kind: str, precision: str):
    """``x`` after the layer's attention, on its residual."""
    B, T, _ = x.shape
    H, K, D, Dv = s["H"], s["K_" + kind], s["D"], s["Dv"]
    theta = s["theta_" + kind]
    n = _rms_norm(x, lp["ln1"], s["eps"])
    q = _proj(n, lp["wq"], precision).reshape(B, T, H, D)
    k = _proj(n, lp["wk"], precision).reshape(B, T, K, D)
    v = _proj(n, lp["wv"], precision).reshape(B, T, K, Dv)
    q = _act(_rope(q, theta, s["rot"]), precision)
    k = _act(_rope(k, theta, s["rot"]), precision)
    v = _act(s["vscale"] * v, precision)
    o = _act(attention(q, k, v, lp.get("sink"),
                       s["window"] if kind == "window" else 0, precision),
             precision)
    return x + _proj(o, lp["wo"], precision)


def block(x, lp: dict, s: dict, kind: str, precision: str):
    x = attention_half(x, lp, s, kind, precision)
    m = _rms_norm(x, lp["ln2"], s["eps"])
    if "router" in lp:
        return x + experts_layer(m, lp, s, precision)
    h = _act(jax.nn.silu(_proj(m, lp["gate"], precision))
             * _proj(m, lp["up"], precision), precision)
    return x + _proj(h, lp["down"], precision, "btf,fd->btd")


# --- the streamed forward ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
def _embed(key, tokens, s_items, precision):
    s = dict(s_items)
    return _act(make_leaf(key, "wte", -1, s)[tokens], precision)


@functools.partial(jax.jit, static_argnames=("s_items", "kind", "names",
                                             "precision"))
def _layer(key, layer, x, s_items, kind, names, precision):
    s = dict(s_items)
    lp = {n: make_leaf(key, n, layer, s, kind) for n in names}
    return _act(block(x, lp, s, kind, precision), precision)


@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
def _head(key, x, rows, s_items, precision):
    """Logits ``[N, M, V]`` at the ``rows [N, M]`` of ``x [N, T, d]``."""
    s = dict(s_items)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    x = _rms_norm(x, make_leaf(key, "lnf", -1, s), s["eps"])
    return matmul(_act(x, precision), make_leaf(key, "head", -1, s),
                  precision, "btd,dv->btv")


def hidden(key, tokens, s: dict, precision: str = "f32", layers=None):
    """Activations before the final norm, ``[B, T, d]``: embedding and
    every layer in turn (``layers``: those of them), each made from the
    key, used and freed."""
    items = tuple(sorted(s.items()))
    x = _embed(key, tokens, items, precision)
    for layer in (range(s["L"]) if layers is None else layers):
        x = _layer(key, jnp.int32(layer), x, items, kind_of(s, layer),
                   tuple(layer_leaves(s, layer)), precision)
    return x


def logits(key, tokens, s: dict, precision: str = "f32"):
    """``[B, T, V]`` for ``tokens [B, T]`` (the tests' sizes)."""
    B, T = tokens.shape
    rows = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return _head(key, hidden(key, tokens, s, precision), rows,
                 tuple(sorted(s.items())), precision)


def routing(key, tokens, s: dict, precision: str = "f32"):
    """The experts every token chose in every expert layer, ``[layers,
    B, T, top_k]``: what a count of routing flips compares."""
    items = tuple(sorted(s.items()))
    x = _embed(key, tokens, items, precision)
    chosen = []
    for layer in range(s["L"]):
        kind, names = kind_of(s, layer), tuple(layer_leaves(s, layer))
        if s["moe"][layer]:
            chosen.append(_routed(key, jnp.int32(layer), x, items, kind,
                                  names, precision))
        x = _layer(key, jnp.int32(layer), x, items, kind, names, precision)
    return jnp.stack(chosen)


@functools.partial(jax.jit, static_argnames=("s_items", "kind", "names",
                                             "precision"))
def _routed(key, layer, x, s_items, kind, names, precision):
    """The experts ``layer`` chooses for ``x``: its attention half, then
    the router on the second norm."""
    s = dict(s_items)
    lp = {n: make_leaf(key, n, layer, s, kind) for n in names
          if not n.startswith("e_")}
    x = attention_half(x, lp, s, kind, precision)
    return route(_act(_rms_norm(x, lp["ln2"], s["eps"]), precision), lp,
                 s)[0]


# --- serving -----------------------------------------------------------------

def served_token_gaps(params, sequences, s: dict, *, pad_to: int,
                      control_precision: str = ""):
    """For each ``(prompt, served)`` pair: one full forward over the
    prompt followed by its served tokens, and at every served position
    the gap by which the served token's logit lies below the
    reference's best.  With ``control_precision`` it also reads, at the
    same positions, the gap of the token that the lower precision puts
    first.  The sequences run one after another, each padded to a
    multiple of ``pad_to`` (and its served rows to a multiple of half of
    it), so a run compiles each piece for a few lengths and no program
    grows with the number of sequences.
    Returns ``(gaps, control_gaps)``, flat lists."""
    key = params
    items = tuple(sorted(s.items()))
    gaps, control = [], []
    for prompt, served in sequences:
        seq = list(prompt) + list(served)
        T = -(-len(seq) // pad_to) * pad_to
        half = max(1, pad_to // 2)
        M = -(-len(served) // half) * half
        tokens = jnp.asarray([seq + [0] * (T - len(seq))], jnp.int32)
        # Row n-1+i is what greedy decoding chose served[i] from; rows
        # past the served tokens repeat the last and are dropped.
        rows = jnp.asarray([[len(prompt) - 1 + min(i, len(served) - 1)
                             for i in range(M)]], jnp.int32)
        picked = jnp.asarray([list(served) + [0] * (M - len(served))],
                             jnp.int32)

        def at_rows(precision):
            x = hidden(key, tokens, s, precision)
            x = jnp.take_along_axis(x, rows[..., None], axis=1)
            return _head(key, x, jnp.arange(M, dtype=jnp.int32)[None],
                         items, precision)

        lg = at_rows("f32")
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, picked[..., None], axis=-1)[..., 0]
        gaps.extend(jax.device_get(best - got)[0, :len(served)].tolist())
        if control_precision:
            pick = jnp.argmax(at_rows(control_precision), axis=-1)
            low = jnp.take_along_axis(lg, pick[..., None], axis=-1)[..., 0]
            control.extend(
                jax.device_get(best - low)[0, :len(served)].tolist())
    return gaps, control
