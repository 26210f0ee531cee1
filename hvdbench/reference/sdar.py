"""Plain reference of the ``sdar_moe`` family (SDAR-30B-A3B-Chat): a
Qwen3-style mixture of experts that generates by diffusion over blocks
of ``B`` positions.  Blocks lie on absolute positions (``block = position
// B``), attention is causal over blocks and full inside one, and the
logit at a position predicts *that* position's token (no shift).  For
layer ``l``::

    n   = RMSNorm(x)
    q   = n W_q -> [T, H, D];  k = n W_k -> [T, K, D];  v = n W_v -> [T, K, D]
    q,k = RMSNorm over each head (a learned scale of D), then rotary
          over the whole head (half-split), theta
    s_ij = q_i . k_j / sqrt(D)   for j // B <= i // B
    x   = x + (softmax_j(s_ij) v_j).reshape(T, H * D) W_o
    n2  = RMSNorm(x)
    p   = softmax(n2 W_r) over all E experts, in float32
    chosen = top-k of p;  w = p[chosen] / sum p[chosen]
    x   = x + sum_{e chosen} w_e down_e(silu(gate_e n2) * up_e n2)

and ``logits = RMSNorm(x_L) W_head``.

**Generation** (:func:`generate`, the plain loop of the public SDAR
script, no cache): the prompt's whole blocks are context; its tail
(``P mod B`` tokens) opens the first generated block as positions
already clean; the rest of a block starts as masks.  A denoising step
forwards everything so far under the block mask, takes at every masked
position of the block the greedy token ``x0`` and its confidence
``p(x0)`` (a float32 softmax over the vocabulary) and unmasks the
``k_s = B // T`` (+1 in the first ``B mod T`` steps) masked positions
of highest confidence (``static``; equal confidences: the earlier
position first) — or (``dynamic``) every masked position over the
threshold when those are at least ``k_s``.  When no mask is left the
block is final.  Departure from the script, as the program: whether a
position is masked is a flag, never a comparison with the mask's id, so
a prompt may hold that id.

**The two-stream forward** is what block diffusion is trained with and
what the check uses: a *noised* copy of the sequence beside the *clean*
one.  A clean query sees the clean keys of its own and of earlier
blocks; a noised query sees the clean keys of earlier blocks and the
noised keys of its own.  One pass gives every block's logits at one
denoising step without a cache; the clean stream alone is the plain
forward under the block mask.  :func:`hidden` is both: every row of
its input has a position and says which stream it is of.

Straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")`` (every product also names
``precision=HIGHEST``) unless a lower ``precision`` is asked for (the
control that ``correct`` has to fail).  No cache, no kernels, no
batching; the queries go in blocks so that a layer's scores fit.  It
imports nothing of the program under test and makes its own weights
from the seed.  What ``config.json`` does not say is the configuration
file's ``assumed``.

**It streams its weights**, as ``reference/mimo_v2.py`` does: six
layers and the vocabulary are 4.36 B parameters, 17.4 GB in float32, so
:func:`init_params` returns the seed's key and the forward makes, uses
and frees the embedding, each layer's leaves and the head in turn.  The
model is published in bfloat16, so a weight *is* a bfloat16 value:
:func:`make_leaf` rounds what it draws to bfloat16 and hands it out in
float32, and the program holds the very same numbers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from hvdbench.reference import gpt2 as _shared

HIGHEST = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 256
_HEAD_ROWS = 256

matmul = _shared.matmul        # einsum in f32 (HIGHEST), bf16 or scaled fp8
seed_key = _shared.seed_key
_act = _shared._act            # the controls keep activations in bfloat16

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "qn", "kn", "wo", "ln2", "router",
                "e_gate", "e_up", "e_down")
TOP_LEAVES = ("wte", "lnf", "head")
_ALL = LAYER_LEAVES + TOP_LEAVES


def _highest(fn):
    """``fn`` traced and run under the highest matmul precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    if (config["hidden_act"] != "silu" or config["tie_word_embeddings"]
            or config["attention_bias"] or not config["norm_topk_prob"]
            or int(config["decoder_sparse_step"]) != 1
            or config["mlp_only_layers"] or config.get("rope_scaling")
            or config.get("use_sliding_window")):
        raise ValueError(
            "the sdar_moe family is gated SiLU experts in every layer, an "
            "untied head, no bias, no window, no scaling of positions, and "
            "probabilities normalised over the chosen")
    gen = config["run"]["generation"]
    return dict(
        V=int(config["vocab_size"]), L=int(config["num_hidden_layers"]),
        d=int(config["hidden_size"]), H=int(config["num_attention_heads"]),
        K=int(config["num_key_value_heads"]), D=int(config["head_dim"]),
        theta=float(config["rope_theta"]),
        eff=int(config["moe_intermediate_size"]),
        E=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        eps=float(config["rms_norm_eps"]),
        B=int(gen["block_length"]), mask=int(gen["mask_token"]))


def leaf_shape_std(name: str, s: dict):
    """Shape and init of a leaf: a std for a matrix (normal(0.02), the
    residual projections scaled by 1/sqrt(2 L)), None for a norm's
    scale (ones)."""
    d, V, L, H, K, D = s["d"], s["V"], s["L"], s["H"], s["K"], s["D"]
    E, eff = s["E"], s["eff"]
    resid = 0.02 / math.sqrt(2 * L)
    return {
        "wte": ((V, d), 0.02), "head": ((d, V), 0.02), "lnf": ((d,), None),
        "ln1": ((d,), None), "ln2": ((d,), None),
        "qn": ((D,), None), "kn": ((D,), None),
        "wq": ((d, H * D), 0.02), "wk": ((d, K * D), 0.02),
        "wv": ((d, K * D), 0.02), "wo": ((H * D, d), resid),
        "router": ((d, E), 0.02),
        "e_gate": ((E, d, eff), 0.02), "e_up": ((E, d, eff), 0.02),
        "e_down": ((E, eff, d), resid),
    }[name]


def make_leaf(key, name: str, layer, s: dict):
    """One parameter leaf from the seed: a bfloat16 value in float32.
    ``layer`` is -1 for a leaf outside the blocks, and may be traced."""
    shape, std = leaf_shape_std(name, s)
    if std is None:
        return jnp.ones(shape, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, _ALL.index(name)),
                           layer + 1)
    drawn = std * jax.random.normal(k, shape, jnp.float32)
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def init_params(seed_key_, s: dict):
    """What the forward needs to make any leaf: the key.  The tree
    itself is never held (17.4 GB in float32)."""
    del s
    return seed_key_


# --- the block ---------------------------------------------------------------

def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotary positions over the whole head of ``x [B, N, heads, D]``
    at ``pos [N]``, the half-split convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv               # [N, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sees(pos_q, noised_q, pos_k, noised_k, B: int):
    """The two-stream block mask, ``[Nq, Nk]``: a clean query sees the
    clean keys of its own and of earlier blocks; a noised query the
    clean keys of earlier blocks and the noised keys of its own."""
    bq, bk = pos_q[:, None] // B, pos_k[None, :] // B
    clean_k, noised_k = ~noised_k[None, :], noised_k[None, :]
    return jnp.where(noised_q[:, None],
                     (clean_k & (bk < bq)) | (noised_k & (bk == bq)),
                     clean_k & (bk <= bq))


def attention(q, k, v, pos, noised, B: int, precision: str):
    """Softmax attention under :func:`sees`, ``q [1, N, H, D]``, ``k``
    and ``v [1, N, K, D]``; query head ``h`` reads KV head ``h // (H /
    K)``."""
    _, N, H, D = q.shape
    K = k.shape[2]
    qg = q.reshape(1, N, K, H // K, D)
    block = _QUERY_BLOCK if N % _QUERY_BLOCK == 0 else N

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=1)
        scores = matmul(qb, k, precision, "bqkgd,bjkd->bkgqj") / math.sqrt(D)
        seen = sees(jax.lax.dynamic_slice_in_dim(pos, start, block),
                    jax.lax.dynamic_slice_in_dim(noised, start, block),
                    pos, noised, B)
        # Every row sees itself at least.
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return matmul(_act(p, precision), v, precision, "bkgqj,bjkd->bqkgd")

    att = jax.lax.map(rows, jnp.arange(0, N, block))   # [N/block, 1, block, ..]
    return jnp.moveaxis(att, 0, 1).reshape(1, N, H * D)


def route(x, lp, s: dict):
    """``(experts [.., top_k], weights [.., top_k])`` of every token:
    float32 whatever the precision; a softmax over all experts, the top
    ``top_k`` of it, their probabilities over their sum."""
    probs = jax.nn.softmax(
        jnp.einsum("...d,de->...e", x, lp["router"], precision=HIGHEST),
        axis=-1)
    chosen, experts = jax.lax.top_k(probs, s["top_k"])
    return experts, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _proj(h, w, precision, eq="btd,de->bte"):
    return _act(matmul(_act(h, precision), w, precision, eq), precision)


def experts_layer(x, lp, s: dict, precision: str):
    """The routed sum over all experts, on the normed input."""
    x = _act(x, precision)
    experts, weights = route(x, lp, s)

    def one(out, e):
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        h = _act(jax.nn.silu(_proj(x, lp["e_gate"][e], precision))
                 * _proj(x, lp["e_up"][e], precision), precision)
        return out + w_e[..., None] * _proj(h, lp["e_down"][e], precision,
                                            "btf,fd->btd"), None

    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(s["E"]))[0]


def attention_half(x, lp: dict, s: dict, pos, noised, precision: str):
    """``x`` after the layer's attention, on its residual."""
    _, N, _ = x.shape
    H, K, D = s["H"], s["K"], s["D"]
    n = _rms_norm(x, lp["ln1"], s["eps"])
    q = _proj(n, lp["wq"], precision).reshape(1, N, H, D)
    k = _proj(n, lp["wk"], precision).reshape(1, N, K, D)
    v = _proj(n, lp["wv"], precision).reshape(1, N, K, D)
    q = _act(_rms_norm(q, lp["qn"], s["eps"]), precision)
    k = _act(_rms_norm(k, lp["kn"], s["eps"]), precision)
    q = _act(_rope(q, pos, s["theta"]), precision)
    k = _act(_rope(k, pos, s["theta"]), precision)
    o = _act(attention(q, k, v, pos, noised, s["B"], precision), precision)
    return x + _proj(o, lp["wo"], precision)


def block(x, lp: dict, s: dict, pos, noised, precision: str):
    x = attention_half(x, lp, s, pos, noised, precision)
    m = _rms_norm(x, lp["ln2"], s["eps"])
    return x + experts_layer(m, lp, s, precision)


# --- the streamed forward ----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
@_highest
def _embed(key, tokens, s_items, precision):
    s = dict(s_items)
    return _act(make_leaf(key, "wte", -1, s)[tokens], precision)


@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
@_highest
def _layer(key, layer, x, pos, noised, s_items, precision):
    s = dict(s_items)
    lp = {n: make_leaf(key, n, layer, s) for n in LAYER_LEAVES}
    return _act(block(x, lp, s, pos, noised, precision), precision)


@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
@_highest
def _head(key, x, s_items, precision):
    """Logits ``[1, M, V]`` of ``x [1, M, d]``."""
    s = dict(s_items)
    x = _rms_norm(x, make_leaf(key, "lnf", -1, s), s["eps"])
    return matmul(_act(x, precision), make_leaf(key, "head", -1, s),
                  precision, "btd,dv->btv")


@functools.partial(jax.jit, static_argnames=("s_items", "precision"))
@_highest
def _head_stats(key, x, picked, s_items, precision):
    """Of the logits of ``x [M, d]`` (``M`` a multiple of 256, or
    fewer), without holding ``[M, V]``: the best logit, the logit of
    ``picked [M]``, the log-confidence of the best (its log-softmax)
    and which token it is."""
    s = dict(s_items)
    g, w = make_leaf(key, "lnf", -1, s), make_leaf(key, "head", -1, s)
    rows = _HEAD_ROWS if x.shape[0] % _HEAD_ROWS == 0 else x.shape[0]

    def some(args):
        xs, ps = args
        lg = matmul(_act(_rms_norm(xs, g, s["eps"]), precision), w,
                    precision, "td,dv->tv")
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, ps[:, None], axis=-1)[:, 0]
        return (best, got, best - jax.nn.logsumexp(lg, axis=-1),
                jnp.argmax(lg, axis=-1).astype(jnp.int32))

    out = jax.lax.map(some, (x.reshape(-1, rows, x.shape[-1]),
                             picked.reshape(-1, rows)))
    return tuple(o.reshape(-1) for o in out)


def hidden(key, tokens, s: dict, precision: str = "f32", pos=None,
           noised=None):
    """Activations before the final norm, ``[1, N, d]``, of ``tokens
    [1, N]``: row ``i`` is at position ``pos[i]`` (None: ``i``) of the
    clean stream or, where ``noised[i]``, of the noised one (None: all
    clean, the plain forward under the block mask).  Embedding and every
    layer in turn, each made from the key, used and freed."""
    N = tokens.shape[1]
    pos = jnp.arange(N, dtype=jnp.int32) if pos is None else jnp.asarray(
        pos, jnp.int32)
    noised = (jnp.zeros(N, bool) if noised is None
              else jnp.asarray(noised, bool))
    items = tuple(sorted(s.items()))
    x = _embed(key, tokens, items, precision)
    for layer in range(s["L"]):
        x = _layer(key, jnp.int32(layer), x, pos, noised, items, precision)
    return x


def logits(key, tokens, s: dict, precision: str = "f32", pos=None,
           noised=None):
    """``[1, N, V]`` for ``tokens [1, N]`` (the tests' sizes)."""
    return _head(key, hidden(key, tokens, s, precision, pos, noised),
                 tuple(sorted(s.items())), precision)


# --- generation --------------------------------------------------------------

def transfer_count(B: int, T: int, t: int) -> int:
    """Positions denoising step ``t`` of ``T`` unmasks: ``B // T``, one
    more in the first ``B mod T`` steps."""
    return B // T + (t < B % T)


def transfer(conf, masked, k: int, rule: str, threshold: float):
    """Which masked positions of a block a denoising step unmasks,
    from their confidences (``conf [B]``, numpy): the ``k`` highest
    (equal ones: the earlier position first), or under ``'dynamic'``
    every one over ``threshold`` when those are at least ``k``."""
    conf = np.where(masked, np.asarray(conf, np.float64), -1.0)
    over = masked & (conf > threshold)
    if rule == "dynamic" and over.sum() >= k:
        return over
    order = np.argsort(-conf, kind="stable")[:k]
    take = np.zeros(len(conf), bool)
    take[order] = True
    return take & masked


def generate(key, prompt, s: dict, *, max_new_tokens: int,
             denoising_steps: int = 0, rule: str = "dynamic",
             threshold: float = 0.9, precision: str = "f32",
             logits_fn=None):
    """Greedy block-diffusion generation, the plain loop with no cache.
    Returns ``(tokens, steps, trace)``: the generated tokens in order,
    cut at ``max_new_tokens``; for each the denoising step at which it
    was chosen; and ``trace``, one ``(block start, step, logits [B, V]
    of the block at that step, masked [B] before it)`` for every
    denoising step made.  ``logits_fn(tokens [1, N]) -> [1, N, V]``
    stands in for the model where a test makes the logits."""
    B, T = s["B"], denoising_steps or s["B"]
    logits_fn = logits_fn or (lambda toks: logits(key, toks, s, precision))
    prompt = [int(t) for t in prompt]
    start = len(prompt) - len(prompt) % B
    seq = prompt[:start]
    block_tokens = np.asarray(
        prompt[start:] + [s["mask"]] * (B - len(prompt) + start), np.int64)
    masked = np.arange(B) >= len(prompt) - start
    made = masked.copy()                  # the positions this block makes
    chosen = np.full(B, -1, np.int64)
    out, steps, trace, t = [], [], [], 0
    # One length for every call: what lies after a block is invisible
    # to it, so the sequence is padded to where the answer may end.
    total = -(-(len(prompt) + max_new_tokens) // B) * B
    while len(out) < max_new_tokens:
        toks = seq + block_tokens.tolist()
        padded = jnp.asarray([toks + [0] * (total - len(toks))], jnp.int32)
        lg = np.asarray(logits_fn(padded)[0, len(seq):len(seq) + B],
                        np.float32)
        trace.append((len(seq), t, lg, masked.copy()))
        x0 = lg.argmax(axis=-1)
        shifted = lg - lg.max(axis=-1, keepdims=True)
        conf = np.exp(shifted[np.arange(B), x0]
                      - np.log(np.exp(shifted).sum(axis=-1)))
        take = transfer(conf, masked, transfer_count(B, T, t), rule,
                        threshold)
        block_tokens = np.where(take, x0, block_tokens)
        chosen = np.where(take, t, chosen)
        masked = masked & ~take
        t += 1
        if not masked.any():
            out.extend(block_tokens[made].tolist())
            steps.extend(chosen[made].tolist())
            seq = seq + block_tokens.tolist()
            block_tokens = np.full(B, s["mask"], np.int64)
            masked = np.ones(B, bool)
            made = masked.copy()
            chosen = np.full(B, -1, np.int64)
            t = 0
    return out[:max_new_tokens], steps[:max_new_tokens], trace


# --- serving -----------------------------------------------------------------

def served_token_gaps(params, sequences, s: dict, *, pad_to: int,
                      denoising_steps: int = 0,
                      control_precision: str = ""):
    """For each ``(prompt, served tokens, their denoising steps)``: the
    two-stream forward once for every denoising step ``t`` of the
    ``denoising_steps`` a block has (0: as many as the request's steps
    show), the noised stream holding each generated block as it stood
    before step ``t`` — a generated position chosen at step ``t`` or
    later is a mask (by its flag; it embeds the mask's id), the others
    hold their served token — and at every position still masked there
    the reference's best logit, the served token's, and the
    log-confidence of the best.

    ``logit_gaps``: for every served token, at the step it was chosen,
    the reference's best logit at its position less the served
    token's.  ``order_gaps``: for every block and step, the largest
    log-confidence among the positions the program left masked less the
    smallest among those it unmasked, or 0 — which positions a step
    unmasks is part of the arithmetic.  An answer cut inside its last
    block leaves that block unread: what the program chose beyond the
    cut is not known, and every position of a block sees it.

    With ``control_precision`` the same forwards run again in the lower
    precision, and what *it* would have served is held to the float32
    reference in the same two ways: at every masked position of a step
    that it would unmask (its ``k_s`` most confident; the static rule),
    the gap of the token it puts first; and the order gap of its
    choice.  The sequences run one after another, each padded to a
    multiple of ``pad_to``.
    Returns ``{"logit_gaps", "order_gaps"[, "control_logit_gaps",
    "control_order_gaps"]}``, flat lists."""
    key, B, items = params, s["B"], tuple(sorted(s.items()))
    out = {"logit_gaps": [], "order_gaps": []}
    if control_precision:
        out.update(control_logit_gaps=[], control_order_gaps=[])
    for prompt, served, steps in sequences:
        prompt, P = list(prompt), len(prompt)
        n = (P + len(served)) // B * B           # whole blocks only
        if n <= P:
            continue
        served = list(served)[:n - P]
        steps = np.asarray(steps, np.int64)[:n - P]
        n_steps = denoising_steps or int(steps.max()) + 1
        T = -(-n // pad_to) * pad_to
        clean = np.zeros(T, np.int64)
        clean[:n] = prompt + served
        chosen = np.full(T, -1, np.int64)
        chosen[P:n] = steps
        generated = (np.arange(T) >= P) & (np.arange(T) < n)
        pos = np.concatenate([np.arange(T), np.arange(T)])
        noised = np.concatenate([np.zeros(T, bool), np.ones(T, bool)])
        for t in range(n_steps):
            masked = generated & (chosen >= t)
            if not masked.any():
                continue
            both = jnp.asarray(np.concatenate(
                [clean, np.where(masked, s["mask"], clean)])[None], jnp.int32)
            rows = np.nonzero(masked)[0]
            M = -(-len(rows) // _HEAD_ROWS) * _HEAD_ROWS

            def padded(v):
                return np.concatenate([v, np.full(M - len(rows), v[-1])])

            def block_rows(precision):
                """The noised stream at the masked positions."""
                return hidden(key, both, s, precision, pos,
                              noised)[0, T + padded(rows)]

            def head(x, picked, precision):
                return [np.asarray(v)[:len(rows)] for v in _head_stats(
                    key, x, jnp.asarray(padded(picked), jnp.int32), items,
                    precision)]

            x = block_rows("f32")
            best, got, conf, _ = head(x, clean[rows], "f32")
            now = chosen[rows] == t
            out["logit_gaps"].extend((best - got)[now].tolist())
            out["order_gaps"].extend(_order_gaps(rows, now, conf, B))
            if control_precision:
                _, _, low_conf, low_first = head(
                    block_rows(control_precision), clean[rows],
                    control_precision)
                low_got = head(x, low_first, "f32")[1]
                low_now = _would_unmask(rows, low_conf, B, n_steps, t)
                out["control_logit_gaps"].extend(
                    (best - low_got)[low_now].tolist())
                out["control_order_gaps"].extend(_order_gaps(
                    rows, low_now, conf, B))
    return out


def _order_gaps(rows, now, conf, B: int):
    """For each block among ``rows`` (positions masked before a step):
    the largest ``conf`` among those the step left masked less the
    smallest among those it unmasked (``now``), or 0."""
    gaps = []
    for b in np.unique(rows // B):
        mine = rows // B == b
        took, left = conf[mine & now], conf[mine & ~now]
        gaps.append(float(max(0.0, left.max() - took.min()))
                    if len(took) and len(left) else 0.0)
    return gaps


def _would_unmask(rows, conf, B: int, T: int, t: int):
    """Which of ``rows`` a static step ``t`` of ``T`` unmasks by
    ``conf``: in each block its ``k_s`` most confident."""
    now = np.zeros(len(rows), bool)
    for b in np.unique(rows // B):
        mine = np.nonzero(rows // B == b)[0]
        order = mine[np.argsort(-conf[mine], kind="stable")]
        now[order[:transfer_count(B, T, t)]] = True
    return now
