"""How the ``brumby`` family is built on the program under test:
``horovod_tpu.models.GPT`` — the one model class — configured from a
configuration file's published sizes (RMSNorm, rotary positions,
grouped KV heads of a stated size, RMS norm of q and k, gated SiLU
feed-forward, a retention mixer in every layer), and its parameter
tree made on the device from the seed in one jitted call: leaf by leaf
with the reference's own per-leaf formula, in the bfloat16 the model is
published in (the reference's leaves are bfloat16 values, so nothing is
rounded here), without ever holding a float32 copy of the tree."""

from __future__ import annotations

from hvdbench.reference import brumby as ref

_BLOCK_LEAVES = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    "wq": ("retn", "q", "kernel"), "wk": ("retn", "k", "kernel"),
    "wv": ("retn", "v", "kernel"), "wo": ("retn", "out", "kernel"),
    "wg": ("retn", "gate", "kernel"), "bg": ("retn", "gate", "bias"),
    "q_norm": ("retn", "q_norm", "scale"),
    "k_norm": ("retn", "k_norm", "scale"),
    "gate": ("mlp", "gate", "kernel"), "up": ("mlp", "up", "kernel"),
    "down": ("mlp", "down", "kernel"),
}
_TOP_LEAVES = {
    "wte": ("embed", "embedding"), "lnf": ("ln_f", "scale"),
    "head": ("lm_head", "kernel"),
}


def build_model(config: dict, attention: str):
    import jax.numpy as jnp

    from horovod_tpu.models import GPT, GPTConfig

    s = ref.sizes(config)
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError("the brumby family is a gated SiLU feed-forward "
                         "and an untied head")
    return GPT(GPTConfig(
        vocab_size=s["V"], n_layer=s["L"], n_head=s["H"], n_kv_head=s["K"],
        head_dim=s["D"], d_model=s["d"], d_ff=s["ff"], max_seq_len=s["P"],
        attention=attention, norm="rmsnorm", norm_eps=s["eps"],
        positions="rope", rope_theta=s["theta"], qk_norm=True,
        mlp="swiglu", mixer="retention",
        dtype=jnp.dtype(config["run"]["activation_dtype"]),
        param_dtype=jnp.dtype(config["run"]["param_dtype"])))


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _build(key, s: dict, dtype) -> dict:
    tree: dict = {}
    for name, path in _TOP_LEAVES.items():
        _put(tree, path, ref.make_leaf(key, name, -1, s).astype(dtype))
    for layer in range(s["L"]):
        for name, path in _BLOCK_LEAVES.items():
            _put(tree, (f"block_{layer}",) + path,
                 ref.make_leaf(key, name, layer, s).astype(dtype))
    return tree


def make_params(config: dict, seed: int, sharding=None):
    """The program's parameter tree for ``seed``, made on the device."""
    import jax
    import jax.numpy as jnp

    s = ref.sizes(config)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    return jax.jit(lambda key: _build(key, s, dtype),
                   out_shardings=sharding)(ref.seed_key(seed))
