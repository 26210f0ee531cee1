"""Plain GPT-2 reference: forward, loss, gradients and AdamW in
straightforward ``jax.numpy``.  No kernels, no cache, no batching
tricks; float32 with ``precision=HIGHEST`` unless a lower ``precision``
is asked for (the control that ``correct`` has to fail).

It imports nothing of the program under test and makes its own weights
from the seed.  Departures from the published GPT-2 (all three follow
the program, and the configuration files list them under ``assumed``):
no biases on the linear layers, an output head that is not tied to the
token embedding, LayerNorm epsilon 1e-6.

Layer parameters are stacked ``[n_layer, ...]`` and the layers run
under ``lax.scan`` with rematerialisation, so that the reference fits
beside nothing else on one chip and compiles once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# name -> (shape as a function of the sizes, init std as a function of
# the sizes, or None for LayerNorm scale=1 / bias=0).  GPT-2's own
# init: normal(0.02), positions 0.01, residual projections scaled by
# 1/sqrt(2 * n_layer).
LAYER_LEAVES = ("ln1_g", "ln1_b", "qkv", "out", "ln2_g", "ln2_b", "up",
                "down")
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b", "head")


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    d = int(config["n_embd"])
    return dict(V=int(config["vocab_size"]), L=int(config["n_layer"]),
                H=int(config["n_head"]), d=d,
                ff=int(config.get("n_inner") or 4 * d),
                P=int(config["n_positions"]),
                eps=float(config["assumed"]["layer_norm_epsilon_run"]))


def leaf_shape_std(name: str, s: dict):
    d, ff, V, P, L = s["d"], s["ff"], s["V"], s["P"], s["L"]
    resid = 0.02 / math.sqrt(2 * L)
    return {
        "wte": ((V, d), 0.02), "wpe": ((P, d), 0.01),
        "head": ((d, V), 0.02),
        "lnf_g": ((d,), None), "lnf_b": ((d,), None),
        "ln1_g": ((d,), None), "ln1_b": ((d,), None),
        "ln2_g": ((d,), None), "ln2_b": ((d,), None),
        "qkv": ((d, 3 * d), 0.02), "out": ((d, d), resid),
        "up": ((d, ff), 0.02), "down": ((ff, d), resid),
    }[name]


def seed_key(seed: int):
    """A PRNG key from any whole number a run may be given (the
    driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_leaf(key, name: str, layer: int, s: dict):
    """One parameter leaf from the seed.  ``layer`` is -1 for a leaf
    outside the blocks.  The benchmark's model builders call this leaf
    by leaf; :func:`init_params` calls it under ``vmap`` over the
    layers, which gives the same numbers."""
    shape, std = leaf_shape_std(name, s)
    if std is None:
        return (jnp.ones if name.endswith("_g") else jnp.zeros)(
            shape, jnp.float32)
    idx = (LAYER_LEAVES + TOP_LEAVES).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer + 1)
    return std * jax.random.normal(k, shape, jnp.float32)


def init_params(seed_key_, s: dict) -> dict:
    params = {n: make_leaf(seed_key_, n, -1, s) for n in TOP_LEAVES}
    layers = jnp.arange(s["L"])
    for n in LAYER_LEAVES:
        params[n] = jax.vmap(
            lambda l, n=n: make_leaf(seed_key_, n, l, s))(layers)
    return params


# --- precision ---------------------------------------------------------------

def _fp8(x):
    """Per-tensor scaled float8 (e4m3) and back: what a fair fp8 matmul
    would feed the MXU.  Straight-through for gradients, as fp8
    training recipes are."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0)
    y = x / scale
    q = y + jax.lax.stop_gradient(
        y.astype(jnp.float8_e4m3fn).astype(jnp.float32) - y)
    return q.astype(jnp.bfloat16), scale


def matmul(a, b, precision: str, eq: str):
    """``einsum(eq, a, b)`` in the named precision: ``f32`` (HIGHEST),
    ``bf16`` (inputs rounded to bfloat16, float32 accumulation) or
    ``fp8`` (inputs rounded to scaled e4m3)."""
    if precision == "f32":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "bf16":
        return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        qa, sa = _fp8(a)
        qb, sb = _fp8(b)
        return jnp.einsum(eq, qa, qb,
                          preferred_element_type=jnp.float32) * (sa * sb)
    raise ValueError(f"unknown precision {precision!r}")


def _act(x, precision: str):
    """Activations between matmuls: the lower-precision controls keep
    them in bfloat16, as a program in that precision would."""
    if precision == "f32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# --- forward -----------------------------------------------------------------

def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x):   # GPT-2's gelu_new
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, s: dict, precision: str):
    B, T, d = x.shape
    H = s["H"]
    D = d // H
    h = _layer_norm(x, lp["ln1_g"], lp["ln1_b"], s["eps"])
    qkv = _act(matmul(_act(h, precision), lp["qkv"], precision,
                      "btd,de->bte"), precision)
    q, k, v = (t.reshape(B, T, H, D) for t in jnp.split(qkv, 3, axis=-1))
    scores = matmul(q, k, precision, "bqhd,bkhd->bhqk") * (D ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = _act(jax.nn.softmax(scores, axis=-1), precision)
    att = _act(matmul(probs, v, precision, "bhqk,bkhd->bqhd"), precision)
    x = x + _act(matmul(att.reshape(B, T, d), lp["out"], precision,
                        "btd,de->bte"), precision)
    h = _layer_norm(x, lp["ln2_g"], lp["ln2_b"], s["eps"])
    up = _act(_gelu(_act(matmul(_act(h, precision), lp["up"], precision,
                                "btd,df->btf"), precision)), precision)
    return x + _act(matmul(up, lp["down"], precision, "btf,fd->btd"),
                    precision)


def hidden(params, tokens, s: dict, precision: str = "f32"):
    """Final-LayerNorm activations ``[B, T, d]`` for ``tokens [B, T]``."""
    T = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:T][None]
    x = _act(x, precision)
    layer_params = {n: params[n] for n in LAYER_LEAVES}

    @jax.checkpoint
    def body(x, lp):
        return _act(_block(x, lp, s, precision), precision), None

    x, _ = jax.lax.scan(body, x, layer_params)
    return _layer_norm(x, params["lnf_g"], params["lnf_b"], s["eps"])


def logits(params, tokens, s: dict, precision: str = "f32"):
    x = hidden(params, tokens, s, precision)
    return matmul(_act(x, precision), params["head"], precision,
                  "btd,dv->btv")


def loss(params, inputs, targets, s: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over all rows and positions."""
    lg = logits(params, inputs, s, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# --- training ----------------------------------------------------------------

def loss_and_grads(params, inputs, targets, s: dict, *, rows_per_block: int,
                   precision: str = "f32"):
    """Loss and gradients of the whole batch, computed in blocks of
    ``rows_per_block`` rows so that the float32 logits of a block, not
    of the batch, are what has to fit."""
    n = inputs.shape[0]
    if n % rows_per_block:
        raise ValueError(f"{n} rows do not divide into blocks of "
                         f"{rows_per_block}")
    blocks = n // rows_per_block
    xi = inputs.reshape(blocks, rows_per_block, -1)
    xt = targets.reshape(blocks, rows_per_block, -1)
    grad_fn = jax.value_and_grad(
        lambda p, a, b: loss(p, a, b, s, precision))

    def body(carry, ab):
        tot, acc = carry
        l, g = grad_fn(params, *ab)
        return (tot + l, jax.tree.map(jnp.add, acc, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (tot, acc), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                 (xi, xt))
    return tot / blocks, jax.tree.map(lambda g: g / blocks, acc)


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def adamw_update(params, grads, state, opt: dict):
    """optax.adamw's arithmetic, written out: decoupled weight decay on
    every leaf, bias-corrected moments, epsilon outside the root."""
    lr, b1, b2 = opt["learning_rate"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]
    count = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf; layer-stacked leaves give one norm per
    layer, so that a leaf here is a leaf of the program's tree."""
    out = {}
    for name, x in tree.items():
        if name in LAYER_LEAVES:
            out[name] = jnp.sqrt(jnp.sum(
                jnp.square(x.astype(jnp.float32)),
                axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return out


def train_readings(seed: int, s: dict, batches, opt: dict, *,
                   rows_per_block: int, precision: str = "f32") -> dict:
    """Follow the first ``len(batches)`` optimizer steps from the
    seed's weights.  Returns the loss of every step, the per-leaf norms
    of the first gradient and the per-leaf norms of the parameters'
    change after the last step — all as host numbers."""
    key = seed_key(seed)
    init = jax.jit(functools.partial(init_params, s=s))
    params = init(key)
    state = jax.jit(adamw_init)(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state, inputs, targets):
        l, g = loss_and_grads(params, inputs, targets, s,
                              rows_per_block=rows_per_block,
                              precision=precision)
        new, state = adamw_update(params, g, state, opt)
        return new, state, l, leaf_norms(g)

    losses, grad_norms = [], None
    for inputs, targets in batches:
        params, state, l, gn = step(params, state, jnp.asarray(inputs),
                                    jnp.asarray(targets))
        losses.append(float(l))
        if grad_norms is None:
            grad_norms = jax.device_get(gn)
    delta = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        jnp.subtract, p, init_params(k, s))))(params, key)
    delta = jax.device_get(delta)
    del params, state
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


# --- serving -----------------------------------------------------------------

def served_token_gaps(params, sequences, s: dict, *, pad_to: int,
                      control_precision: str = ""):
    """For each ``(prompt, served)`` pair: one full forward over the
    prompt followed by its served tokens, and at every served position
    the gap by which the served token's logit lies below the
    reference's best.  With ``control_precision`` it also reads, at the
    same positions, the gap of the token that the lower precision puts
    first.  Returns ``(gaps, control_gaps)``, flat lists."""
    @functools.partial(jax.jit, static_argnames=("precision",))
    def rows(params, tokens, precision="f32"):
        return logits(params, tokens, s, precision)[0]

    gaps, control = [], []
    for prompt, served in sequences:
        n, m = len(prompt), len(served)
        seq = list(prompt) + list(served)
        T = -(-len(seq) // pad_to) * pad_to
        padded = jnp.zeros((1, T), jnp.int32).at[0, :len(seq)].set(
            jnp.asarray(seq, jnp.int32))
        # Row n-1+i is what greedy decoding chose served[i] from.
        lg = rows(params, padded)[n - 1:n - 1 + m]
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(
            lg, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps.extend(jax.device_get(best - got).tolist())
        if control_precision:
            low = rows(params, padded, precision=control_precision)[
                n - 1:n - 1 + m]
            pick = jnp.argmax(low, axis=-1)
            got_low = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            control.extend(jax.device_get(best - got_low).tolist())
    return gaps, control
