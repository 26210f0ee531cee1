"""How the ``mimo_v2`` family is built on the program under test:
``horovod_tpu.models.GPT`` — the one model class — configured from a
configuration file's published sizes (attention layers of two kinds by
``hybrid_layer_pattern``: full, and window with KV heads, rotary base
and sinks of its own; keys and values of different widths; rotary on a
share of a head; scaled values; a gated SiLU feed-forward where
``moe_layer_freq`` says 0 and the dropless expert layer over the range
of experts held here where it says 1; RMSNorm; an untied head), and its
bfloat16 parameter tree made on the device from the seed, a layer a
jitted call — leaf by leaf with the reference's own per-leaf formula
(its leaves are bfloat16 values, so nothing is rounded here), without
ever holding a float32 copy of more than a leaf."""

from __future__ import annotations

from hvdbench.reference import mimo_v2 as ref

# wq, wk and wv are one kernel of the program's (``_layer_tree``).
_LAYER = {"ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
          "wo": ("attn", "out", "kernel"), "sink": ("attn", "sink"),
          "gate": ("mlp", "gate", "kernel"), "up": ("mlp", "up", "kernel"),
          "down": ("mlp", "down", "kernel"),
          "router": ("experts", "router", "kernel"),
          "bias": ("experts", "select_bias"), "e_gate": ("experts", "gate"),
          "e_up": ("experts", "up"), "e_down": ("experts", "down")}
_TOP = {"wte": ("embed", "embedding"), "lnf": ("ln_f", "scale"),
        "head": ("lm_head", "kernel")}
_FLOAT32 = ("sink", "bias")


def build_model(config: dict, attention: str):
    import jax.numpy as jnp

    from horovod_tpu.models import GPT, GPTConfig

    s = ref.sizes(config)
    if (config["hidden_act"] != "silu" or config["tie_word_embeddings"]
            or config["attention_bias"] or s["sink_full"]
            or not config["norm_topk_prob"]
            or config["scoring_func"] != "sigmoid"):
        raise ValueError(
            "the mimo_v2 family is gated SiLU, an untied head, no bias, "
            "sinks in window layers only, and sigmoid scores normalised "
            "over the chosen")
    return GPT(GPTConfig(
        vocab_size=s["V"], n_layer=s["L"], d_model=s["d"], d_ff=s["ff"],
        n_head=s["H"], n_kv_head=s["K_full"], head_dim=s["D"],
        v_head_dim=s["Dv"], rope_dim=s["rot"], rope_theta=s["theta_full"],
        attn=tuple("window" if w else "full" for w in s["pattern"]),
        window=s["window"], window_kv_head=s["K_window"],
        window_rope_theta=s["theta_window"], window_sinks=s["sink_window"],
        value_scale=s["vscale"],
        # What the deployment serves: the engine's default reach.
        max_seq_len=int(config["run"]["engine"]["max_seq_len"]),
        attention=attention, norm="rmsnorm", norm_eps=s["eps"],
        positions="rope", mlp="swiglu",
        ffn=tuple("experts" if m else "mlp" for m in s["moe"]),
        expert_count=s["E"], expert_top_k=s["top_k"], expert_d_ff=s["eff"],
        expert_scale=s["scale"], expert_held=s["held"], expert_mlp="swiglu",
        dtype=jnp.dtype(config["run"]["activation_dtype"]),
        param_dtype=jnp.dtype(config["run"]["param_dtype"])))


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _layer_tree(key, layer, s: dict, kind: str, names, dtype) -> dict:
    import jax.numpy as jnp

    def leaf(name):
        x = ref.make_leaf(key, name, layer, s, kind)
        return x if name in _FLOAT32 else x.astype(dtype)

    tree: dict = {}
    _put(tree, ("attn", "qkv", "kernel"),
         jnp.concatenate([leaf("wq"), leaf("wk"), leaf("wv")], axis=1))
    for name in names:
        if name in _LAYER:
            _put(tree, _LAYER[name], leaf(name))
    return tree


def make_params(config: dict, seed: int, sharding=None):
    """The program's parameter tree for ``seed``, made on the device: a
    compiled program for each of the sorts of layer the model has, run
    once a layer."""
    import jax
    import jax.numpy as jnp

    s = ref.sizes(config)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    key = ref.seed_key(seed)
    tree = jax.jit(lambda k: {
        path[0]: {path[1]: ref.make_leaf(k, name, -1, s).astype(dtype)}
        for name, path in _TOP.items()}, out_shardings=sharding)(key)
    make = jax.jit(
        lambda k, layer, kind, names: _layer_tree(k, layer, s, kind, names,
                                                  dtype),
        static_argnames=("kind", "names"), out_shardings=sharding)
    for layer in range(s["L"]):
        tree[f"block_{layer}"] = make(
            key, jnp.int32(layer), kind=ref.kind_of(s, layer),
            names=tuple(ref.layer_leaves(s, layer)))
    return tree
