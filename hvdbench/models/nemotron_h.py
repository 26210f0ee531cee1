"""How the ``nemotron_h`` family is built on the program under test:
``horovod_tpu.models.GPT`` — the one model class — configured from a
configuration file's published sizes (a layer a letter of the pattern:
a Mamba-2 mixer, dropless sigmoid-routed experts with a shared expert
over the range of experts held here, or attention without positions,
each alone on its residual; RMSNorm; an untied head), and its parameter
tree made on the device from the seed in one jitted call — leaf by
leaf with the reference's own per-leaf formula, so that the reference,
which makes its weights itself, starts from the same numbers without
taking anything from here."""

from __future__ import annotations

from hvdbench.reference import nemotron_h as ref

_KIND = {"M": "ssm", "E": "experts", "*": "attention"}
_LAYER_LEAVES = {
    "M": {"m_ln": ("ln", "scale"), "m_in": ("ssm", "in_proj", "kernel"),
          "m_conv_w": ("ssm", "conv_kernel"), "m_conv_b": ("ssm", "conv_bias"),
          "m_dt_bias": ("ssm", "dt_bias"), "m_a_log": ("ssm", "A_log"),
          "m_d": ("ssm", "D"), "m_norm": ("ssm", "norm_scale"),
          "m_out": ("ssm", "out_proj", "kernel")},
    "E": {"e_ln": ("ln", "scale"),
          "e_router": ("experts", "router", "kernel"),
          "e_bias": ("experts", "select_bias"),
          "e_up": ("experts", "up"), "e_down": ("experts", "down"),
          "e_shared_up": ("experts", "shared_up", "kernel"),
          "e_shared_down": ("experts", "shared_down", "kernel")},
    "*": {"a_ln": ("ln", "scale"), "a_qkv": ("attn", "qkv", "kernel"),
          "a_out": ("attn", "out", "kernel")},
}
_TOP_LEAVES = {"wte": ("embed", "embedding"), "lnf": ("ln_f", "scale"),
               "head": ("lm_head", "kernel")}
_made = {}      # the seed of the last tree made, for pairs_held()


def build_model(config: dict, attention: str):
    import jax.numpy as jnp

    from horovod_tpu.models import GPT, GPTConfig

    s = ref.sizes(config)
    return GPT(GPTConfig(
        vocab_size=s["V"], n_layer=s["L"], d_model=s["d"], d_ff=s["ff"],
        n_head=s["H"], n_kv_head=s["K"], head_dim=s["D"],
        attention=attention, norm="rmsnorm", norm_eps=s["eps"],
        positions="none", layers=tuple(_KIND[c] for c in s["pattern"]),
        ssm_heads=s["mh"], ssm_head_dim=s["mp"], ssm_groups=s["mg"],
        ssm_state=s["mn"], ssm_conv=s["taps"], ssm_chunk=s["chunk"],
        expert_count=s["E"], expert_top_k=s["top_k"], expert_d_ff=s["ff"],
        expert_shared_d_ff=s["shared_ff"], expert_scale=s["scale"],
        expert_held=s["held"],
        remat_layers=tuple(_KIND[c] for c in config["run"]["recompute_layers"]),
        dtype=jnp.dtype(config["run"]["activation_dtype"]),
        param_dtype=jnp.dtype(config["run"]["param_dtype"])))


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _build(key, s: dict) -> dict:
    tree: dict = {}
    for name, path in _TOP_LEAVES.items():
        _put(tree, path, ref.make_leaf(key, name, -1, s))
    for layer, letter in enumerate(s["pattern"]):
        for name, path in _LAYER_LEAVES[letter].items():
            _put(tree, (f"block_{layer}",) + path,
                 ref.make_leaf(key, name, layer, s))
    return tree


def make_params(config: dict, seed: int, sharding=None):
    """The program's parameter tree for ``seed``, made on the device."""
    import jax

    s = ref.sizes(config)
    _made["seed"] = seed
    return jax.jit(lambda key: _build(key, s),
                   out_shardings=sharding)(ref.seed_key(seed))


def pairs_held(config: dict, traffic: dict) -> dict:
    """The token-expert pairs this chip holds in a step, counted by the
    program's own layer (its ``pairs_held`` counter) on the training
    ring's batches with the weights of the last seed a tree was made
    for, as made: ``mean_a_layer`` (pairs a step, one number per expert
    layer, the mean over the ring), and the fewest and the most pairs
    any held expert was sent in any batch.  For the per-layer readers,
    after the window."""
    import dataclasses

    import jax
    import numpy as np

    from hvdbench import generator

    seed = _made["seed"]
    model = build_model(config, config["run"]["attention"])
    model = model.clone(config=dataclasses.replace(model.config,
                                                   remat_layers=()))
    params = make_params(config, seed)

    @jax.jit
    def count(params, tokens):
        _, found = model.apply({"params": params}, tokens,
                               return_hidden=True, mutable=["intermediates"])
        return [v["experts"]["pairs_held"][0]
                for _, v in sorted(found["intermediates"].items(),
                                   key=lambda kv: int(kv[0].split("_")[1]))]

    rows = int(config["run"]["rows_per_chip"])
    sizes = np.asarray([jax.device_get(count(params, generator.train_batch(
        traffic, seed, i, rows, config["vocab_size"])[0]))
        for i in range(int(traffic["ring"]))])       # [ring, layers, held]
    return {"mean_a_layer": sizes.sum(axis=-1).mean(axis=0).tolist(),
            "fewest_an_expert": int(sizes.min()),
            "most_an_expert": int(sizes.max())}


def leaf_norms_like_reference(tree) -> dict:
    """Per-leaf L2 norms of a program-shaped tree, keyed and stacked as
    ``reference.nemotron_h.leaf_norms`` gives them (one norm per layer
    of its kind for a layer's leaf).  The layers' kinds are read from
    the tree.  Call it inside ``jit``."""
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    out = {name: norm(_get(tree, path)) for name, path in _TOP_LEAVES.items()}
    blocks = [tree[f"block_{i}"]
              for i in range(sum(1 for k in tree if k.startswith("block_")))]
    for letter, sub in (("M", "ssm"), ("E", "experts"), ("*", "attn")):
        for name, path in _LAYER_LEAVES[letter].items():
            out[name] = jnp.stack([norm(_get(b, path))
                                   for b in blocks if sub in b])
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
