"""How the ``gpt2`` family is built on the program under test:
``horovod_tpu.models.GPT`` from a configuration file's published sizes,
and its parameter tree made on the device from the seed in one jitted
call — leaf by leaf with the reference's own per-leaf formula, so that
the reference, which makes its weights itself, starts from the same
numbers without taking anything from here."""

from __future__ import annotations

from hvdbench.reference import gpt2 as ref

_BLOCK_LEAVES = {
    "ln1_g": ("ln1", "scale"), "ln1_b": ("ln1", "bias"),
    "qkv": ("attn", "qkv", "kernel"), "out": ("attn", "out", "kernel"),
    "ln2_g": ("ln2", "scale"), "ln2_b": ("ln2", "bias"),
    "up": ("mlp", "up", "kernel"), "down": ("mlp", "down", "kernel"),
}
_TOP_LEAVES = {
    "wte": ("embed", "embedding"), "wpe": ("pos_embed",),
    "lnf_g": ("ln_f", "scale"), "lnf_b": ("ln_f", "bias"),
    "head": ("lm_head", "kernel"),
}


def build_model(config: dict, attention: str):
    import jax.numpy as jnp

    from horovod_tpu.models import GPT, GPTConfig

    s = ref.sizes(config)
    return GPT(GPTConfig(
        vocab_size=s["V"], n_layer=s["L"], n_head=s["H"], d_model=s["d"],
        d_ff=s["ff"], max_seq_len=s["P"], attention=attention,
        dtype=jnp.dtype(config["run"]["activation_dtype"]),
        param_dtype=jnp.dtype(config["run"]["param_dtype"])))


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _build(key, s: dict) -> dict:
    tree: dict = {}
    for name, path in _TOP_LEAVES.items():
        _put(tree, path, ref.make_leaf(key, name, -1, s))
    for layer in range(s["L"]):
        for name, path in _BLOCK_LEAVES.items():
            _put(tree, (f"block_{layer}",) + path,
                 ref.make_leaf(key, name, layer, s))
    return tree


def make_params(config: dict, seed: int, sharding=None):
    """The program's parameter tree for ``seed``, made on the device."""
    import jax

    s = ref.sizes(config)
    return jax.jit(lambda key: _build(key, s),
                   out_shardings=sharding)(ref.seed_key(seed))


def leaf_norms_like_reference(tree) -> dict:
    """Per-leaf L2 norms of a program-shaped tree, keyed and stacked as
    ``reference.gpt2.leaf_norms`` gives them (one norm per layer for a
    block leaf).  Call it inside ``jit``."""
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def get(sub, path):
        for key in path:
            sub = sub[key]
        return sub

    n_layer = sum(1 for k in tree if k.startswith("block_"))
    out = {name: norm(get(tree, path)) for name, path in _TOP_LEAVES.items()}
    for name, path in _BLOCK_LEAVES.items():
        out[name] = jnp.stack([norm(get(tree[f"block_{i}"], path))
                               for i in range(n_layer)])
    return out


def delta_norms(config: dict, params, seed: int):
    """Per-leaf norms of ``params`` minus the seed's initial weights,
    made again inside the jitted call so that no second copy of the
    model is held."""
    import jax

    s = ref.sizes(config)

    def fn(p, key):
        return leaf_norms_like_reference(
            jax.tree.map(lambda a, b: a - b, p, _build(key, s)))

    return jax.jit(fn)(params, ref.seed_key(seed))
