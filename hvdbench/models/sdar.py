"""How the ``sdar`` family is built on the program under test:
``horovod_tpu.models.GPT`` — the one model class — configured from a
configuration file's published sizes (a Qwen3-style block: RMSNorm,
rotary positions over the whole head, grouped KV heads of a stated
size, RMS norm of q and k; the dropless expert layer as every block's
feed-forward, all experts held, a softmax over all of them before the
top-k; attention causal over blocks of ``run.generation.block_length``
positions and full inside one; an untied head), and its bfloat16
parameter tree made on the device from the seed, a layer a jitted call —
leaf by leaf with the reference's own per-leaf formula (its leaves are
bfloat16 values, so nothing is rounded here), without ever holding a
float32 copy of more than a leaf."""

from __future__ import annotations

from hvdbench.reference import sdar as ref

# wq, wk and wv are one kernel of the program's (``_layer_tree``); its
# selection bias is zero and no leaf of the reference's.
_LAYER = {"ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
          "qn": ("attn", "q_norm", "scale"), "kn": ("attn", "k_norm", "scale"),
          "wo": ("attn", "out", "kernel"),
          "router": ("experts", "router", "kernel"),
          "e_gate": ("experts", "gate"), "e_up": ("experts", "up"),
          "e_down": ("experts", "down")}
_TOP = {"wte": ("embed", "embedding"), "lnf": ("ln_f", "scale"),
        "head": ("lm_head", "kernel")}


def build_model(config: dict, attention: str):
    import jax.numpy as jnp

    from horovod_tpu.models import GPT, GPTConfig

    s = ref.sizes(config)
    return GPT(GPTConfig(
        vocab_size=s["V"], n_layer=s["L"], d_model=s["d"],
        n_head=s["H"], n_kv_head=s["K"], head_dim=s["D"],
        # What the deployment serves: the engine's default reach.
        max_seq_len=int(config["run"]["engine"]["max_seq_len"]),
        attention=attention, norm="rmsnorm", norm_eps=s["eps"],
        positions="rope", rope_theta=s["theta"], qk_norm=True,
        ffn="experts", expert_count=s["E"], expert_top_k=s["top_k"],
        expert_d_ff=s["eff"], expert_mlp="swiglu",
        expert_scoring="softmax",
        block_length=s["B"], mask_token=s["mask"],
        dtype=jnp.dtype(config["run"]["activation_dtype"]),
        param_dtype=jnp.dtype(config["run"]["param_dtype"])))


def _put(tree: dict, path, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _layer_tree(key, layer, s: dict, dtype) -> dict:
    import jax.numpy as jnp

    def leaf(name):
        return ref.make_leaf(key, name, layer, s).astype(dtype)

    tree: dict = {}
    _put(tree, ("attn", "qkv", "kernel"),
         jnp.concatenate([leaf("wq"), leaf("wk"), leaf("wv")], axis=1))
    for name, path in _LAYER.items():
        _put(tree, path, leaf(name))
    _put(tree, ("experts", "select_bias"), jnp.zeros((s["E"],), jnp.float32))
    return tree


def make_params(config: dict, seed: int, sharding=None):
    """The program's parameter tree for ``seed``, made on the device:
    one compiled program for a layer, run once a layer."""
    import jax
    import jax.numpy as jnp

    s = ref.sizes(config)
    dtype = jnp.dtype(config["run"]["param_dtype"])
    key = ref.seed_key(seed)
    tree = jax.jit(lambda k: {
        path[0]: {path[1]: ref.make_leaf(k, name, -1, s).astype(dtype)}
        for name, path in _TOP.items()}, out_shardings=sharding)(key)
    make = jax.jit(lambda k, layer: _layer_tree(k, layer, s, dtype),
                   out_shardings=sharding)
    for layer in range(s["L"]):
        tree[f"block_{layer}"] = make(key, jnp.int32(layer))
    return tree
