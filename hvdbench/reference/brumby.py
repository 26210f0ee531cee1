"""Plain reference of the ``brumby`` family: a Qwen3-style block
(RMSNorm, rotary positions, grouped KV heads, RMS norm of q and k per
head, gated SiLU feed-forward, no biases, untied head) whose token
mixer is **power retention** (arXiv:2507.04239) in its *quadratic*
form, written out::

    a[t, j] = exp(sum_{s=j+1..t} log g_s) * (q_t . k_j / sqrt(d)) ** 2      (j <= t)
    o_t     = sum_j a[t, j] v_j / (sum_j a[t, j] + EPS)
    log g_t = log_sigmoid(W_g n_t + b_g)        one gate per KV head, float32

Straightforward ``jax.numpy``, float32, ``precision=HIGHEST`` unless a
lower ``precision`` is asked for (the control that ``correct`` has to
fail).  No cache, no chunks, no state, no kernels; it imports nothing
of the program under test and makes its own weights from the seed.
What the published ``config.json`` does not carry — the degree 2, the
gate, the ``1/sqrt(d)`` inside the power, the normaliser and its
``EPS``, the q/k norm and rotary positions kept from the Qwen3
skeleton — is the configuration file's ``assumed``; the program states
the same choices.

**It streams its weights.**  In float32 ten layers and the vocabulary
are 19.4 GB, more than a chip holds, so the reference never has its
tree: :func:`init_params` returns the seed's key, and the forward
makes, uses and frees the embedding, each layer's leaves and the head
in turn (one compiled program a piece; the layer's index is data).
The model is published in bfloat16, so a weight *is* a bfloat16 value:
:func:`make_leaf` rounds what it draws to bfloat16 and hands it out in
float32, and the program holds the very same numbers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from hvdbench.reference import gpt2 as _shared

HIGHEST = jax.lax.Precision.HIGHEST
DEGREE = 2
EPS = 1e-6

matmul = _shared.matmul        # einsum in f32 (HIGHEST), bf16 or scaled fp8
seed_key = _shared.seed_key
_act = _shared._act            # the controls keep activations in bfloat16

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wg", "bg", "q_norm", "k_norm",
                "wo", "ln2", "gate", "up", "down")
TOP_LEAVES = ("wte", "lnf", "head")


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    return dict(V=int(config["vocab_size"]),
                L=int(config["num_hidden_layers"]),
                H=int(config["num_attention_heads"]),
                K=int(config["num_key_value_heads"]),
                D=int(config["head_dim"]), d=int(config["hidden_size"]),
                ff=int(config["intermediate_size"]),
                P=int(config["max_position_embeddings"]),
                eps=float(config["rms_norm_eps"]),
                theta=float(config["rope_theta"]))


def leaf_shape_std(name: str, s: dict):
    """Shape and init of a leaf: a std for a matrix (Qwen's
    normal(0.02), residual projections scaled by 1/sqrt(2 L)), None for
    a norm's scale (ones) and for the gate's bias (spread over the KV
    heads, below)."""
    d, ff, V, L = s["d"], s["ff"], s["V"], s["L"]
    H, K, D = s["H"], s["K"], s["D"]
    resid = 0.02 / math.sqrt(2 * L)
    return {
        "wte": ((V, d), 0.02), "head": ((d, V), 0.02), "lnf": ((d,), None),
        "ln1": ((d,), None), "ln2": ((d,), None),
        "q_norm": ((D,), None), "k_norm": ((D,), None),
        "wq": ((d, H * D), 0.02), "wk": ((d, K * D), 0.02),
        "wv": ((d, K * D), 0.02), "wg": ((d, K), 0.02), "bg": ((K,), None),
        "wo": ((H * D, d), resid),
        "gate": ((d, ff), 0.02), "up": ((d, ff), 0.02),
        "down": ((ff, d), resid),
    }[name]


def make_leaf(key, name: str, layer, s: dict):
    """One parameter leaf from the seed, a bfloat16 value in float32.
    ``layer`` is -1 for a leaf outside the blocks, and may be traced.
    The gate's bias gives the KV heads memories from about eight tokens
    (sigmoid(2)) to about four hundred (sigmoid(6))."""
    shape, std = leaf_shape_std(name, s)
    if name == "bg":
        return jnp.linspace(2.0, 6.0, shape[0]).astype(
            jnp.bfloat16).astype(jnp.float32)
    if std is None:
        return jnp.ones(shape, jnp.float32)
    idx = (LAYER_LEAVES + TOP_LEAVES).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer + 1)
    drawn = std * jax.random.normal(k, shape, jnp.float32)
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def init_params(seed_key_, s: dict):
    """What the forward needs to make any leaf: the key.  The tree
    itself is never held (19.4 GB in float32)."""
    del s
    return seed_key_


# --- the block ---------------------------------------------------------------

def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotary positions on ``x [B, T, N, D]``, positions 0..T-1, the
    half-split convention."""
    T, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(q, k, v, log_g, precision: str):
    """Power retention, the quadratic form.  ``q [B, T, H, D]``, ``k, v
    [B, T, K, D]``, ``log_g [B, T, K]``; query head ``h`` reads KV head
    ``h // (H / K)``."""
    B, T, H, D = q.shape
    K = k.shape[2]
    qg = q.reshape(B, T, K, H // K, D)
    s = matmul(qg, k, precision, "btkgd,bjkd->bkgtj") / math.sqrt(D)
    cum = jnp.cumsum(log_g, axis=1)                            # [B, T, K]
    decay = cum[:, :, None, :] - cum[:, None, :, :]            # [B, t, j, K]
    seen = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    weight = jnp.exp(jnp.where(seen, decay, -jnp.inf))
    a = s ** DEGREE * weight.transpose(0, 3, 1, 2)[:, :, None]
    num = matmul(_act(a, precision), v, precision, "bkgtj,bjkd->btkgd")
    den = a.sum(-1).transpose(0, 3, 1, 2)[..., None]
    return (num / (den + EPS)).reshape(B, T, H * D)


def block(x, lp: dict, s: dict, precision: str):
    B, T, d = x.shape
    H, K, D = s["H"], s["K"], s["D"]

    def proj(h, w, eq="btd,de->bte"):
        return _act(matmul(_act(h, precision), w, precision, eq), precision)

    n = _rms_norm(x, lp["ln1"], s["eps"])
    q = proj(n, lp["wq"]).reshape(B, T, H, D)
    k = proj(n, lp["wk"]).reshape(B, T, K, D)
    v = proj(n, lp["wv"]).reshape(B, T, K, D)
    # The gate stays in float32 at every precision, as the state does.
    log_g = jax.nn.log_sigmoid(
        jnp.einsum("btd,dk->btk", n, lp["wg"], precision=HIGHEST)
        + lp["bg"])
    q = _act(_rope(_rms_norm(q, lp["q_norm"], s["eps"]), s["theta"]),
             precision)
    k = _act(_rope(_rms_norm(k, lp["k_norm"], s["eps"]), s["theta"]),
             precision)
    o = _act(retention(q, k, v, log_g, precision), precision)
    x = x + proj(o, lp["wo"])
    m = _rms_norm(x, lp["ln2"], s["eps"])
    h = _act(jax.nn.silu(proj(m, lp["gate"])) * proj(m, lp["up"]),
             precision)
    return x + proj(h, lp["down"], "btf,fd->btd")


# --- the streamed forward ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
def _embed(key, tokens, s_items, precision):
    s = dict(s_items)
    return _act(make_leaf(key, "wte", -1, s)[tokens], precision)


@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
def _layer(key, layer, x, s_items, precision):
    s = dict(s_items)
    lp = {n: make_leaf(key, n, layer, s) for n in LAYER_LEAVES}
    return _act(block(x, lp, s, precision), precision)


@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
def _head(key, x, rows, s_items, precision):
    """Logits ``[N, M, V]`` at the ``rows [N, M]`` of ``x [N, T, d]``."""
    s = dict(s_items)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    x = _rms_norm(x, make_leaf(key, "lnf", -1, s), s["eps"])
    return matmul(_act(x, precision), make_leaf(key, "head", -1, s),
                  precision, "btd,dv->btv")


def hidden(key, tokens, s: dict, precision: str = "f32"):
    """Activations before the final norm, ``[B, T, d]``: embedding and
    every layer in turn, each made from the key, used and freed."""
    items = tuple(sorted(s.items()))
    x = _embed(key, tokens, items, precision)
    for layer in range(s["L"]):
        x = _layer(key, jnp.int32(layer), x, items, precision)
    return x


def logits(key, tokens, s: dict, precision: str = "f32"):
    """``[B, T, V]`` for ``tokens [B, T]`` (the tests' sizes)."""
    B, T = tokens.shape
    rows = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return _head(key, hidden(key, tokens, s, precision), rows,
                 tuple(sorted(s.items())), precision)


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
    grows with the number of sequences.  As one batch, six sequences
    padded to 1,280 positions with up to 551 served rows each (2 GB of
    logits, 1.6 GB of scores a layer) halted the chip's core in every
    one of eight checks, though the compiler had planned them into
    memory; one at a time they ran in all nine (PERF.md section 6,
    PR 26: the cause was not found).
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
