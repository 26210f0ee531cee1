"""Plain reference of the ``nemotron_h`` family (a hybrid of Mamba-2
mixers, sigmoid-routed experts with a shared expert, and attention
layers without positions): forward, loss, gradients and AdamW in
straightforward ``jax.numpy``.  No kernels, no grouped products, no
chunk algebra; float32 with ``precision=HIGHEST`` unless a lower
``precision`` is asked for (the control that ``correct`` has to fail).

It imports nothing of the program under test and makes its own weights
from the seed.  What the configuration file lists under ``assumed`` is
written out here: every layer is ``x + f(RMSNorm(x))`` with ``f`` by
the pattern's letter, the attention layers rotate nothing, the
selection bias stays zero, the loss is plain cross-entropy over the
vocabulary slice.

* ``M``: the state-space scan runs **token by token** (``lax.scan`` over
  the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t =
  h_t C_t + D x_t``), under a checkpoint per ``chunk_size`` tokens so
  that its backward keeps one state a chunk and not one a token; the
  convolution is its taps as shifted multiply-adds.
* ``E``: the router scores all experts, and the experts **held here**
  run as a loop with a mask, each over every token.  The experts that
  are not held add nothing: their part of the sum is another chip's.
* ``*``: causal softmax attention, the queries in blocks so that one
  block's scores, not the sequence's, are what has to fit.

Leaves of one kind of layer are stacked ``[layers of that kind, ...]``
in the order the layers come.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
LAYER_LEAVES = {
    "M": ("m_ln", "m_in", "m_conv_w", "m_conv_b", "m_dt_bias", "m_a_log",
          "m_d", "m_norm", "m_out"),
    "E": ("e_ln", "e_router", "e_bias", "e_up", "e_down", "e_shared_up",
          "e_shared_down"),
    "*": ("a_ln", "a_qkv", "a_out"),
}
TOP_LEAVES = ("wte", "lnf", "head")
_ALL_LEAVES = sum(LAYER_LEAVES.values(), ()) + TOP_LEAVES
_QUERY_BLOCK = 1024


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != int(config["num_hidden_layers"]) \
            or set(pattern) - set(KINDS):
        raise ValueError(f"pattern {pattern!r} does not give "
                         f"{config['num_hidden_layers']} layers of "
                         f"{sorted(KINDS)}")
    held = config["run"]["experts_held"]
    if int(held["count"]) != int(config["n_routed_experts"]):
        raise ValueError("n_routed_experts states the experts held here")
    if config["mlp_hidden_act"] != "relu2" or config["tie_word_embeddings"] \
            or int(config["n_group"]) != 1 or not config["norm_topk_prob"]:
        raise ValueError("the nemotron_h reference is squared-ReLU "
                         "experts, one routing group, normalised top-k "
                         "and an untied head")
    return dict(
        V=int(config["vocab_size"]), d=int(config["hidden_size"]),
        pattern=pattern, L=len(pattern), eps=float(config["norm_eps"]),
        H=int(config["num_attention_heads"]),
        K=int(config["num_key_value_heads"]), D=int(config["head_dim"]),
        mh=int(config["mamba_num_heads"]), mp=int(config["mamba_head_dim"]),
        mg=int(config["n_groups"]), mn=int(config["ssm_state_size"]),
        taps=int(config["conv_kernel"]), chunk=int(config["chunk_size"]),
        dt_min=float(config["time_step_min"]),
        dt_max=float(config["time_step_max"]),
        dt_floor=float(config["time_step_floor"]),
        E=int(config["run"]["router_outputs"]),
        held=(int(held["offset"]), int(held["count"])),
        top_k=int(config["num_experts_per_tok"]),
        ff=int(config["moe_intermediate_size"]),
        shared_ff=int(config["moe_shared_expert_intermediate_size"]),
        scale=float(config["routed_scaling_factor"]))


def layers_of(s: dict, letter: str):
    """Positions in the model of the layers of one kind."""
    return [i for i, c in enumerate(s["pattern"]) if c == letter]


def leaf_shape_init(name: str, s: dict):
    """``(shape, how)`` of one layer's leaf: ``how`` is a standard
    deviation, or the name of a recipe."""
    d, V = s["d"], s["V"]
    inner, bc = s["mh"] * s["mp"], 2 * s["mg"] * s["mn"]
    resid = 0.02 / math.sqrt(s["L"])
    held = s["held"][1]
    return {
        "wte": ((V, d), 0.02), "head": ((d, V), 0.02), "lnf": ((d,), "one"),
        "m_ln": ((d,), "one"), "e_ln": ((d,), "one"), "a_ln": ((d,), "one"),
        "m_in": ((d, 2 * inner + bc + s["mh"]), 0.02),
        "m_conv_w": ((s["taps"], inner + bc), "taps"),
        "m_conv_b": ((inner + bc,), "zero"),
        "m_dt_bias": ((s["mh"],), "dt"), "m_a_log": ((s["mh"],), "a_log"),
        "m_d": ((s["mh"],), "one"), "m_norm": ((inner,), "one"),
        "m_out": ((inner, d), resid),
        "e_router": ((d, s["E"]), 0.02), "e_bias": ((s["E"],), "zero"),
        "e_up": ((held, d, s["ff"]), 0.02),
        "e_down": ((held, s["ff"], d), resid),
        "e_shared_up": ((d, s["shared_ff"]), 0.02),
        "e_shared_down": ((s["shared_ff"], d), resid),
        "a_qkv": ((d, (s["H"] + 2 * s["K"]) * s["D"]), 0.02),
        "a_out": ((s["H"] * s["D"], d), resid),
    }[name]


def seed_key(seed: int):
    """A PRNG key from any whole number a run may be given (the
    driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_leaf(key, name: str, layer, s: dict):
    """One parameter leaf from the seed.  ``layer`` is the layer's
    position in the model, -1 for a leaf outside the layers.  The
    benchmark's model builder calls this leaf by leaf;
    :func:`init_params` calls it under ``vmap`` over the layers of a
    kind, which gives the same numbers."""
    shape, how = leaf_shape_init(name, s)
    k = jax.random.fold_in(
        jax.random.fold_in(key, _ALL_LEAVES.index(name)), layer + 1)
    if how == "one":
        return jnp.ones(shape, jnp.float32)
    if how == "zero":
        return jnp.zeros(shape, jnp.float32)
    if how == "taps":
        bound = shape[0] ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    if how == "dt":
        lo, hi = math.log(s["dt_min"]), math.log(s["dt_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape) * (hi - lo)
                                 + lo), s["dt_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    if how == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    return how * jax.random.normal(k, shape, jnp.float32)


def init_params(seed_key_, s: dict) -> dict:
    params = {n: make_leaf(seed_key_, n, -1, s) for n in TOP_LEAVES}
    for letter, names in LAYER_LEAVES.items():
        at = jnp.asarray(layers_of(s, letter), jnp.int32)
        for n in names:
            params[n] = jax.vmap(
                lambda l, n=n: make_leaf(seed_key_, n, l, s))(at)
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


# --- layers ------------------------------------------------------------------

def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def scan_token_by_token(x, dt, A, Bm, Cm, D, chunk: int):
    """The recurrence itself, float32: ``x [B, T, H, P]``, ``dt [B, T,
    H]``, ``A, D [H]``, ``Bm, Cm [B, T, G, N]`` (head ``h`` reads group
    ``h // (H / G)``).  Returns ``y [B, T, H, P]``."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    if T % chunk:
        chunk = T      # a short test sequence: one stretch
    # Heads by group, so that a group's B and C are read by its heads
    # without a copy a head.
    x, dt = x.reshape(B, T, G, R, P), dt.reshape(B, T, G, R)
    A, D = A.reshape(G, R), D.reshape(G, R)

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return h, (jnp.sum(h * c_t[:, :, None, None, :], axis=-1)
                   + D[..., None] * x_t)

    @jax.checkpoint
    def stretch(h, xs):
        return jax.lax.scan(token, h, xs)

    def by_time(a):      # [B, T, ...] -> [T / chunk, chunk, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((T // chunk, chunk) + a.shape[1:])

    _, y = jax.lax.scan(stretch, jnp.zeros((B, G, R, P, N), jnp.float32),
                        tuple(by_time(a) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y.reshape((T, B, H, P)), 0, 1)


def mamba_layer(x, lp, s: dict, precision: str):
    """``f`` of an ``M`` layer, on the normed input."""
    B, T, _ = x.shape
    H, P, G, N, taps = s["mh"], s["mp"], s["mg"], s["mn"], s["taps"]
    inner = H * P
    zxbcdt = matmul(_act(x, precision), lp["m_in"], precision, "btd,de->bte")
    z = _act(zxbcdt[..., :inner], precision)
    xbc = _act(zxbcdt[..., inner:2 * inner + 2 * G * N], precision)
    dt = zxbcdt[..., 2 * inner + 2 * G * N:]
    # Tap k reads the token taps - 1 - k back; before the first token
    # there is nothing.
    conv = lp["m_conv_b"]
    for k in range(taps):
        back = taps - 1 - k
        shifted = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :T]
        conv = conv + shifted * lp["m_conv_w"][k]
    xbc = _act(jax.nn.silu(conv), precision)
    xs = xbc[..., :inner].reshape(B, T, H, P)
    Bm = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
    Cm = xbc[..., inner + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + lp["m_dt_bias"])
    y = scan_token_by_token(xs, dt, -jnp.exp(lp["m_a_log"]), Bm, Cm,
                            lp["m_d"], s["chunk"])
    y = y.reshape(B, T, inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(B, T, G, inner // G), 1.0, s["eps"])
    y = _act(y.reshape(B, T, inner) * lp["m_norm"], precision)
    return matmul(y, lp["m_out"], precision, "bte,ed->btd")


def route(x, lp, s: dict):
    """``(experts [.., top_k], weights [.., top_k])`` of every token:
    float32 whatever the precision."""
    scores = jax.nn.sigmoid(
        jnp.einsum("...d,de->...e", x, lp["e_router"], precision=HIGHEST))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(lp["e_bias"]), s["top_k"])
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = (chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
               * s["scale"])
    return experts, weights


def experts_layer(x, lp, s: dict, precision: str):
    """``f`` of an ``E`` layer, on the normed input: the held experts'
    part of the routed sum, and the shared expert."""
    x = _act(x, precision)
    experts, weights = route(x, lp, s)
    offset, count = s["held"]
    out = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(experts == offset + e, weights, 0.0),
                      axis=-1)
        h = _act(_relu2(_act(matmul(x, lp["e_up"][e], precision,
                                    "btd,df->btf"), precision)), precision)
        out = out + w_e[..., None] * _act(
            matmul(h, lp["e_down"][e], precision, "btf,fd->btd"), precision)
    h = _act(_relu2(_act(matmul(x, lp["e_shared_up"], precision,
                                "btd,df->btf"), precision)), precision)
    return out + matmul(h, lp["e_shared_down"], precision, "btf,fd->btd")


def attention_layer(x, lp, s: dict, precision: str):
    """``f`` of a ``*`` layer, on the normed input: grouped KV heads,
    causal softmax at ``1 / sqrt(D)``, no positional signal."""
    B, T, _ = x.shape
    H, K, D = s["H"], s["K"], s["D"]
    qkv = _act(matmul(_act(x, precision), lp["a_qkv"], precision,
                      "btd,de->bte"), precision)
    q = qkv[..., :H * D].reshape(B, T, K, H // K, D)
    k = qkv[..., H * D:(H + K) * D].reshape(B, T, K, D)
    v = qkv[..., (H + K) * D:].reshape(B, T, K, D)
    block = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = matmul(qb, k, precision, "bqkgd,bjkd->bkgqj") * D ** -0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None]
        probs = _act(jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1),
                     precision)
        return matmul(probs, v, precision, "bkgqj,bjkd->bqkgd")

    att = jax.lax.map(rows, jnp.arange(0, T, block))       # [T/block, B, block, ..]
    att = _act(jnp.moveaxis(att, 0, 1).reshape(B, T, H * D), precision)
    return matmul(att, lp["a_out"], precision, "bte,ed->btd")


_LAYER = {"M": ("m_ln", mamba_layer), "E": ("e_ln", experts_layer),
          "*": ("a_ln", attention_layer)}


def _layers(params, s: dict):
    """``(letter, the layer's leaves)`` in the model's order."""
    seen = dict.fromkeys(LAYER_LEAVES, 0)
    for letter in s["pattern"]:
        yield letter, {n: params[n][seen[letter]]
                       for n in LAYER_LEAVES[letter]}
        seen[letter] += 1


def _layer(x, lp, letter: str, s: dict, precision: str):
    ln, f = _LAYER[letter]
    return _act(x + _act(f(_rms_norm(x, lp[ln], s["eps"]), lp, s, precision),
                         precision), precision)


def hidden(params, tokens, s: dict, precision: str = "f32"):
    """Final-norm activations ``[B, T, d]`` for ``tokens [B, T]``."""
    x = _act(params["wte"][tokens], precision)
    for letter, lp in _layers(params, s):
        x = jax.checkpoint(functools.partial(
            _layer, letter=letter, s=s, precision=precision))(x, lp)
    return _rms_norm(x, params["lnf"], s["eps"])


def routing(params, tokens, s: dict, precision: str = "f32"):
    """The experts every token chooses, ``[expert layers, B, T,
    top_k]``: what ``tools/route_flips.py`` holds the program's own
    choice against."""
    x = _act(params["wte"][tokens], precision)
    chosen = []
    for letter, lp in _layers(params, s):
        if letter == "E":
            chosen.append(route(_act(_rms_norm(x, lp["e_ln"], s["eps"]),
                                     precision), lp, s)[0])
        x = _layer(x, lp, letter, s, precision)
    return jnp.stack(chosen)


def logits(params, tokens, s: dict, precision: str = "f32"):
    x = hidden(params, tokens, s, precision)
    return matmul(_act(x, precision), params["head"], precision,
                  "btd,dv->btv")


def loss(params, inputs, targets, s: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over all rows and positions, the
    head's logits a block of positions at a time so that one block's,
    not the sequence's, are what has to fit."""
    x = _act(hidden(params, inputs, s, precision), precision)
    B, T, _ = x.shape
    block = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T

    @jax.checkpoint
    def log_likelihood(start):
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=1)
        tb = jax.lax.dynamic_slice_in_dim(targets, start, block, axis=1)
        logp = jax.nn.log_softmax(
            matmul(xb, params["head"], precision, "btd,dv->btv"), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1))

    return -jnp.sum(jax.lax.map(log_likelihood,
                                jnp.arange(0, T, block))) / (B * T)


# --- training ----------------------------------------------------------------

def loss_and_grads(params, inputs, targets, s: dict, *, rows_per_block: int,
                   precision: str = "f32"):
    """Loss and gradients of the whole batch, computed in blocks of
    ``rows_per_block`` rows."""
    n = inputs.shape[0]
    if n % rows_per_block:
        raise ValueError(f"{n} rows do not divide into blocks of "
                         f"{rows_per_block}")
    blocks = n // rows_per_block
    xi = inputs.reshape(blocks, rows_per_block, -1)
    xt = targets.reshape(blocks, rows_per_block, -1)
    grad_fn = jax.value_and_grad(
        lambda p, a, b: loss(p, a, b, s, precision))
    if blocks == 1:
        return grad_fn(params, xi[0], xt[0])

    def body(carry, ab):
        tot, acc = carry
        l, g = grad_fn(params, *ab)
        return (tot + l, jax.tree.map(jnp.add, acc, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (tot, acc), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero),
                                 (xi, xt))
    return tot / blocks, jax.tree.map(lambda g: g / blocks, acc)


def adamw_init(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
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
        x = x.astype(jnp.float32)
        axes = None if name in TOP_LEAVES else tuple(range(1, x.ndim))
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def train_readings(seed: int, s: dict, batches, opt: dict, *,
                   rows_per_block: int, precision: str = "f32") -> dict:
    """Follow the first ``len(batches)`` optimizer steps from the
    seed's weights.  Returns the loss of every step, the per-leaf norms
    of the first gradient and the per-leaf norms of the parameters'
    change after the last step — all as host numbers."""
    key = seed_key(seed)
    params = jax.jit(functools.partial(init_params, s=s))(key)
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
