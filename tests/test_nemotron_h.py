"""A hybrid of state-space, expert and attention layers, each alone on
its residual (the ``nemotron_h`` family).

The oracle throughout is ``hvdbench/reference/nemotron_h.py``: plain
float32, the scan token by token, the experts as a masked loop, no
chunk algebra, no sort and no grouped product; it imports nothing of
the program.  Seeded random weights at a small size (the pattern
``MEMEM*EME``, width 32, 4 scan heads of 8 in 2 groups with a state of
16, 4 query / 2 KV heads of 8, 16 experts of width 24 of which 4 are
held, top 3, vocabulary 97); the benchmark holds the same comparison at
the published widths on the chip.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from hvdbench.models import nemotron_h as family        # noqa: E402
from hvdbench.reference import nemotron_h as ref         # noqa: E402
from horovod_tpu.models import GPT, GPTConfig            # noqa: E402
from horovod_tpu.models.transformer import (             # noqa: E402
    Mamba2, cache_kinds, init_kv_cache, lm_loss_fn)
from horovod_tpu.ops import ssm                          # noqa: E402
from horovod_tpu.parallel.moe import DroplessExperts     # noqa: E402

CONFIG = {
    "name": "nemotron-h-tiny", "vocab_size": 97, "hidden_size": 32,
    "num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
    "norm_eps": 1e-5, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "n_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
    "run": {"activation_dtype": "float32", "param_dtype": "float32",
            "router_outputs": 16, "experts_held": {"offset": 4, "count": 4},
            "recompute_layers": "ME"},
}
SEED = 2**31 + 5
SIZES = ref.sizes(CONFIG)
KEY = ref.seed_key(SEED)
OPT = dict(learning_rate=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-2)


def _close(got, want, tol=2e-5):
    """Widest gap, measured against the widest number wanted."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


def _scan_inputs(T, B=2, H=4, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(T), 7)
    return (jax.random.normal(ks[0], (B, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,))),
            jax.random.normal(ks[3], (B, T, G, N)),
            jax.random.normal(ks[4], (B, T, G, N)),
            jax.random.normal(ks[5], (H,)),
            jax.random.normal(ks[6], (B, H, P, N)))


class TestScan:
    """``ops/ssm.py``: the chunked form against the recurrent one."""

    # 64 and 32 are whole chunks of 16; 50 and 7 are not; 7 is shorter
    # than one chunk.
    @pytest.mark.parametrize("T", [64, 32, 50, 7])
    def test_chunked_gives_the_recurrent_values(self, T):
        args = _scan_inputs(T)
        y0, s0 = jax.jit(ssm.ssm_recurrent)(*args)
        y1, s1 = jax.jit(lambda *a: ssm.ssm_chunked(*a, chunk=16))(*args)
        assert y1.shape == y0.shape == args[0].shape
        assert y1.dtype == s1.dtype == jnp.float32
        _close(y1, y0)
        _close(s1, s0)

    @pytest.mark.parametrize("T", [64, 50, 7])
    def test_chunked_gives_the_recurrent_gradients(self, T):
        args = _scan_inputs(T)

        def grads(fn, **kw):
            def total(*a):
                y, s = fn(*a, **kw)
                return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.square(s))
            return jax.jit(jax.grad(total, argnums=tuple(range(7))))(*args)

        for got, want in zip(grads(ssm.ssm_chunked, chunk=16),
                             grads(ssm.ssm_recurrent)):
            _close(got, want, 1e-4)

    def test_the_state_carries_from_one_call_to_the_next(self):
        args = _scan_inputs(48)
        whole, state = ssm.ssm_chunked(*args[:6], chunk=16)
        cut = lambda a, lo, hi: a[:, lo:hi] if a.ndim > 1 else a  # noqa: E731
        first, mid = ssm.ssm_chunked(
            *(cut(a, 0, 20) for a in args[:6]), chunk=16)
        second, last = ssm.ssm_chunked(
            *(cut(a, 20, 48) for a in args[:6]), mid, chunk=16)
        _close(jnp.concatenate([first, second], axis=1), whole)
        _close(last, state)

    def test_both_forms_give_the_references_recurrence(self):
        x, dt, A, Bm, Cm, D, _ = _scan_inputs(32)
        want = ref.scan_token_by_token(x, dt, A, Bm, Cm, D, chunk=16)
        _close(ssm.ssm_chunked(x, dt, A, Bm, Cm, D, chunk=16)[0], want)
        _close(ssm.ssm_recurrent(x, dt, A, Bm, Cm, D)[0], want)

    def test_heads_that_do_not_divide_into_the_groups_are_refused(self):
        x, dt, A, Bm, Cm, D, _ = _scan_inputs(8, H=3, G=2)
        with pytest.raises(ValueError, match="3 heads"):
            ssm.ssm_chunked(x, dt, A, Bm, Cm, D)


def _model_config(**over):
    return dataclasses.replace(
        family.build_model(CONFIG, "full").config, **over)


def _layer_leaves(letter, layer):
    return {n: ref.make_leaf(KEY, n, layer, SIZES)
            for n in ref.LAYER_LEAVES[letter]}


def _hidden(seed, T=40, B=2):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (B, T, SIZES["d"]), jnp.float32)


class TestMamba2:
    def _program(self, lp):
        return {"in_proj": {"kernel": lp["m_in"]},
                "conv_kernel": lp["m_conv_w"], "conv_bias": lp["m_conv_b"],
                "dt_bias": lp["m_dt_bias"], "A_log": lp["m_a_log"],
                "D": lp["m_d"], "norm_scale": lp["m_norm"],
                "out_proj": {"kernel": lp["m_out"]}}

    @pytest.mark.parametrize("T", [48, 21])
    def test_the_module_is_the_references_layer(self, T):
        lp = _layer_leaves("M", 0)
        # A convolution bias and scales that are not their start values,
        # so that a layer which forgot one would differ.
        lp["m_conv_b"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), lp["m_conv_b"].shape)
        lp["m_norm"] = 1.0 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), lp["m_norm"].shape)
        lp["m_in"] = 10.0 * lp["m_in"]
        x = _hidden(3, T)
        layer = Mamba2(_model_config())
        params = self._program(lp)
        assert (jax.tree.structure(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x)["params"])
                == jax.tree.structure(params))
        _close(jax.jit(layer.apply)({"params": params}, x),
               jax.jit(lambda x, lp: ref.mamba_layer(x, lp, SIZES, "f32"))(
                   x, lp))

    def test_its_own_start_follows_the_recipe(self):
        p = Mamba2(_model_config()).init(jax.random.PRNGKey(0),
                                         _hidden(0, 8))["params"]
        dt = jax.nn.softplus(p["dt_bias"])
        assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1001
        A = jnp.exp(p["A_log"])
        assert float(A.min()) >= 1.0 and float(A.max()) <= 16.0
        assert np.all(np.asarray(p["D"]) == 1.0)
        assert np.all(np.asarray(p["conv_bias"]) == 0.0)
        assert float(jnp.abs(p["conv_kernel"]).max()) <= 0.5


def _experts_layer(held, n_experts=16, **kw):
    return DroplessExperts(
        d_model=SIZES["d"], d_ff=SIZES["ff"], n_experts=n_experts,
        top_k=SIZES["top_k"], shared_d_ff=SIZES["shared_ff"],
        scale=SIZES["scale"], held=held, dtype=jnp.float32, **kw)


def _experts_leaves(n_experts=16):
    """A whole layer's leaves (every expert), large enough that an
    expert's part of the result is not lost beside the shared one's."""
    s = dict(SIZES, held=(0, n_experts), E=n_experts)
    lp = {n: ref.make_leaf(KEY, n, 1, s) for n in ref.LAYER_LEAVES["E"]}
    return {n: (20.0 * v if n in ("e_up", "e_router") else v)
            for n, v in lp.items()}


def _share(lp, held):
    """What the chip that holds ``held`` has of the layer: the
    program's tree and the reference's leaves."""
    lo, n = held
    cut = dict(lp, e_up=lp["e_up"][lo:lo + n], e_down=lp["e_down"][lo:lo + n])
    return {"router": {"kernel": cut["e_router"]},
            "select_bias": cut["e_bias"], "up": cut["e_up"],
            "down": cut["e_down"],
            "shared_up": {"kernel": cut["e_shared_up"]},
            "shared_down": {"kernel": cut["e_shared_down"]}}, cut


class TestDroplessExperts:
    @pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)])
    def test_the_layer_is_the_references_masked_loop(self, held):
        params, lp = _share(_experts_leaves(), held)
        x = _hidden(5)
        s = dict(SIZES, held=held)
        layer = _experts_layer(held)
        assert (jax.tree.structure(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), x)["params"])
                == jax.tree.structure(params))
        _close(jax.jit(layer.apply)({"params": params}, x),
               ref.experts_layer(x, lp, s, "f32"))

        def program(p, x):
            return jnp.sum(jnp.sin(layer.apply({"params": p}, x)))

        def reference(lp, x):
            return jnp.sum(jnp.sin(ref.experts_layer(x, lp, s, "f32")))

        got = jax.jit(jax.grad(program, argnums=(0, 1)))(params, x)
        want = jax.jit(jax.grad(reference, argnums=(0, 1)))(lp, x)
        _close(got[1], want[1], 1e-4)
        got = got[0]
        for ours, theirs in (
                (got["router"]["kernel"], want[0]["e_router"]),
                (got["up"], want[0]["e_up"]),
                (got["down"], want[0]["e_down"]),
                (got["shared_up"]["kernel"], want[0]["e_shared_up"]),
                (got["shared_down"]["kernel"], want[0]["e_shared_down"])):
            _close(ours, theirs, 1e-4)
        # The bias selects and is not trained by the loss.
        assert not np.any(np.asarray(got["select_bias"]))

    def test_nothing_is_dropped_under_a_skewed_router(self):
        """One held expert is sent half the tokens (the rest of the load
        spread as the seed spreads it): every one of its pairs is
        computed, where a layer with a capacity would drop most."""
        held, star = (4, 4), 5
        params, lp = _share(_experts_leaves(), held)
        x = _hidden(7, T=64)
        S = x.shape[0] * x.shape[1]
        # Feature 0 decides: +4 on even tokens, -4 on odd ones, and the
        # router's column of the star reads nothing else.
        x = x.at[..., 0].set(jnp.where(jnp.arange(64) % 2 == 0, 4.0, -4.0))
        column = jnp.zeros((SIZES["d"],)).at[0].set(3.0)
        lp["e_router"] = lp["e_router"].at[:, star].set(column)
        params["router"]["kernel"] = lp["e_router"]
        layer = _experts_layer(held)
        y, found = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
        sent = np.asarray(found["intermediates"]["pairs_held"][0])
        experts, _ = ref.route(x, lp, dict(SIZES, held=held))
        want = [int(jnp.sum(experts == held[0] + e)) for e in range(held[1])]
        assert sent.tolist() == want
        assert sent[star - held[0]] == S // 2
        assert sent[star - held[0]] > 2 * S * SIZES["top_k"] // 16
        _close(y, ref.experts_layer(x, lp, dict(SIZES, held=held), "f32"))

    @pytest.mark.parametrize("load", ["even", "all_here"])
    def test_the_rows_worked_on_follow_the_load(self, load):
        """1,024 tokens, top 3 of 64, 4 held: an even router sends 192
        of the 3,072 pairs here and the layer works on the first 1,024
        sorted rows (the rows past the held pairs zero, and counted
        with the last held expert); a router that sends every token's
        three pairs here takes the other branch, all rows.  Values and
        gradients are the reference's in both."""
        from horovod_tpu.parallel import moe

        held = (4, 4)
        params, lp = _share(_experts_leaves(64), held)
        x = _hidden(13, T=512)
        usual = moe.usual_rows(3072, 4, 64)
        assert usual == 1024
        if load == "all_here":
            x = x.at[..., 0].set(4.0)
            lp["e_router"] = lp["e_router"].at[0].set(
                jnp.where((jnp.arange(64) >= 4) & (jnp.arange(64) < 8),
                          3.0, -3.0))
            params["router"]["kernel"] = lp["e_router"]
        s = dict(SIZES, held=held, E=64)
        layer = _experts_layer(held, n_experts=64)

        def program(p, x):
            y, found = layer.apply({"params": p}, x,
                                   mutable=["intermediates"])
            return jnp.sum(jnp.sin(y)), (y, found["intermediates"])

        def reference(lp, x):
            y = ref.experts_layer(x, lp, s, "f32")
            return jnp.sum(jnp.sin(y)), y

        got, (y, found) = jax.jit(jax.grad(program, argnums=(0, 1),
                                           has_aux=True))(params, x)
        want, y_ref = jax.jit(jax.grad(reference, argnums=(0, 1),
                                       has_aux=True))(lp, x)
        sent = int(found["pairs_held"][0].sum())
        assert (sent == 3072) if load == "all_here" else (0 < sent <= usual)
        _close(y, y_ref)
        _close(got[1], want[1], 1e-4)
        _close(got[0]["up"], want[0]["e_up"], 1e-4)
        _close(got[0]["down"], want[0]["e_down"], 1e-4)
        _close(got[0]["router"]["kernel"], want[0]["e_router"], 1e-4)

    def test_the_shares_add_up_to_the_whole_layer(self):
        """16 experts in 4 shares of 4: what the four chips compute,
        with the shared expert (which each computes alike) counted
        once, is what the uncut reference gives for the whole layer."""
        whole = _experts_leaves()
        x = _hidden(9)
        total = 0.0
        for lo in range(0, 16, 4):
            params, _ = _share(whole, (lo, 4))
            total = total + _experts_layer((lo, 4)).apply(
                {"params": params}, x)
        shared = ref.experts_layer(x, dict(whole, e_up=whole["e_up"][:0],
                                           e_down=whole["e_down"][:0]),
                                   dict(SIZES, held=(0, 0)), "f32")
        want = ref.experts_layer(x, whole, dict(SIZES, held=(0, 16)), "f32")
        assert float(jnp.abs(want - shared).max()) > 0.1 * float(
            jnp.abs(shared).max())      # the routed part is no rounding
        _close(total - 3.0 * shared, want, 1e-5)

    def test_a_held_range_outside_the_experts_is_refused(self):
        with pytest.raises(ValueError, match="no range of 16"):
            _experts_layer((14, 4)).init(jax.random.PRNGKey(0), _hidden(0, 8))

    def test_a_router_narrower_than_top_k_plus_held_still_routes(self):
        # Every expert held, top 3 of 4: each token's three pairs are
        # all here.
        s = dict(SIZES, E=4, held=(0, 4))
        lp = {n: ref.make_leaf(KEY, n, 1, s) for n in ref.LAYER_LEAVES["E"]}
        params, lp = _share(lp, (0, 4))
        x = _hidden(11, T=16)
        y, found = _experts_layer(None, n_experts=4).apply(
            {"params": params}, x, mutable=["intermediates"])
        assert int(found["intermediates"]["pairs_held"][0].sum()) == 32 * 3
        _close(y, ref.experts_layer(x, lp, s, "f32"))


@pytest.fixture(scope="module")
def model():
    return family.build_model(CONFIG, "full")


@pytest.fixture(scope="module")
def params():
    return family.make_params(CONFIG, SEED)


def _batch(step, rows=2, T=48):
    tokens = np.random.default_rng([11, step]).integers(
        0, CONFIG["vocab_size"], (rows, T + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def _as_reference(tree):
    """A program-shaped tree's leaves under the reference's names,
    stacked by kind of layer as the reference stacks them."""
    out = {n: family._get(tree, p) for n, p in family._TOP_LEAVES.items()}
    for letter, leaves in family._LAYER_LEAVES.items():
        for n, p in leaves.items():
            out[n] = jnp.stack([family._get(tree[f"block_{i}"], p)
                                for i in ref.layers_of(SIZES, letter)])
    return out


@pytest.fixture(scope="module")
def followed(model, params):
    """Three AdamW steps of the program and of the reference from the
    same seed, each side through one jitted step of its own."""
    tx = optax.adamw(**OPT)
    loss_fn = lm_loss_fn(model)

    @jax.jit
    def step(p, o, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss, g

    @jax.jit
    def ref_step(p, o, inputs, targets):
        loss, g = ref.loss_and_grads(p, inputs, targets, SIZES,
                                     rows_per_block=1)
        p, o = ref.adamw_update(p, g, o, OPT)
        return p, o, loss, g

    p, o = params, tx.init(params)
    rp = ref.init_params(KEY, SIZES)
    ro = ref.adamw_init(rp)
    out = {"losses": [], "ref_losses": []}
    for i in range(3):
        batch = _batch(i)
        p, o, loss, g = step(p, o, batch)
        rp, ro, ref_loss, rg = ref_step(rp, ro, *map(jnp.asarray, batch))
        out["losses"].append(float(loss))
        out["ref_losses"].append(float(ref_loss))
        if i == 0:
            out.update(grads=g, ref_grads=rg)
    out.update(params=p, ref_params=rp)
    return out


class TestWholeModel:
    def test_the_tree_is_one_sub_layer_a_layer(self, model, params):
        made = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                              _batch(0)[0])["params"]
        assert jax.tree.structure(made) == jax.tree.structure(params)
        assert (jax.tree.map(lambda x: x.shape, made)
                == jax.tree.map(jnp.shape, params))
        assert "pos_embed" not in params
        for i, letter in enumerate(CONFIG["hybrid_override_pattern"]):
            part = {"M": "ssm", "E": "experts", "*": "attn"}[letter]
            assert sorted(params[f"block_{i}"]) == sorted(["ln", part])

    def test_loss_and_every_leafs_gradient(self, followed):
        assert abs(followed["losses"][0] - followed["ref_losses"][0]) < 1e-5
        got, want = _as_reference(followed["grads"]), followed["ref_grads"]
        assert sorted(got) == sorted(want)
        for name in want:
            if name == "e_bias":
                assert not np.any(np.asarray(got[name]))
                assert not np.any(np.asarray(want[name]))
            else:
                _close(got[name], want[name], 2e-4)

    def test_three_adamw_steps(self, followed):
        for a, b in zip(followed["losses"], followed["ref_losses"]):
            assert abs(a - b) < 2e-5
        assert followed["losses"][0] != followed["losses"][2]
        got, rp = _as_reference(followed["params"]), followed["ref_params"]
        start = ref.init_params(KEY, SIZES)
        for name in rp:
            # The change, not the weights: three steps move a weight by
            # a few thousandths.
            _close(got[name] - start[name], rp[name] - start[name], 2e-3)
        norms = jax.jit(family.leaf_norms_like_reference)(followed["params"])
        for name, want in ref.leaf_norms(rp).items():
            _close(norms[name], want, 1e-5)

    def test_recomputed_layers_change_no_number(self, model, params):
        assert model.config.remat_layers == ("ssm", "experts")
        plain = GPT(dataclasses.replace(model.config, remat_layers=()))
        batch = _batch(1)
        a = jax.jit(jax.value_and_grad(lm_loss_fn(model)))(params, batch)
        b = jax.jit(jax.value_and_grad(lm_loss_fn(plain)))(params, batch)
        assert float(a[0]) == pytest.approx(float(b[0]), abs=1e-6)
        for x, y in zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])):
            _close(x, y, 1e-5)

    def test_serving_is_refused_in_one_sentence(self, model, params):
        sentence = (r"serving a model with 'ssm' layers is not built: a "
                    r"state-space layer needs a state cache beside the K/V "
                    r"cache in one cache manager \(serve/engine.py\)")
        with pytest.raises(NotImplementedError, match=sentence):
            cache_kinds(model.config)
        tokens = jnp.zeros((1, 4), jnp.int32)
        caches = init_kv_cache(
            dataclasses.replace(model.config, layers="block"), 1, 8)
        with pytest.raises(NotImplementedError, match=sentence):
            model.apply({"params": params}, tokens, kv_caches=caches,
                        positions=jnp.arange(4)[None])
        from horovod_tpu.serve import InferenceEngine

        with pytest.raises(NotImplementedError, match=sentence):
            InferenceEngine(model, params, seed=0)

    def test_a_pattern_of_the_wrong_length_is_refused(self):
        with pytest.raises(ValueError, match="layers must be one of"):
            _model_config(layers=("ssm", "experts")).layer_kinds
        with pytest.raises(ValueError, match="layers must be one of"):
            _model_config(layers="mamba").layer_kinds


# --- what the two-part block's models keep -----------------------------------

GPT2_TREE = {
    "embed": ["embedding"], "pos_embed": None, "ln_f": ["bias", "scale"],
    "block": {"attn": {"out": ["kernel"], "qkv": ["kernel"]},
              "ln1": ["bias", "scale"], "ln2": ["bias", "scale"],
              "mlp": {"down": ["kernel"], "up": ["kernel"]}},
}


def _names(tree):
    if not isinstance(tree, dict):
        return None
    if all(not isinstance(v, dict) for v in tree.values()):
        return sorted(tree)
    return {k: _names(v) for k, v in tree.items()}


class TestTheTwoPartBlockIsUntouched:
    def test_gpt2s_tree_and_logits(self):
        from hvdbench.models import gpt2 as gpt2_family
        from hvdbench.reference import gpt2 as gpt2_ref

        cfg = {"vocab_size": 97, "n_positions": 64, "n_ctx": 64, "n_embd": 32,
               "n_layer": 2, "n_head": 4, "n_inner": 128,
               "layer_norm_epsilon": 1e-5,
               "assumed": {"layer_norm_epsilon_run": 1e-6},
               "run": {"activation_dtype": "float32",
                       "param_dtype": "float32"}}
        model = gpt2_family.build_model(cfg, "full")
        assert model.config.layers == "block"
        assert model.config.layer_kinds == ("block", "block")
        params = gpt2_family.make_params(cfg, SEED)
        names = _names(jax.tree.map(lambda x: x, params))
        assert sorted(names) == ["block_0", "block_1", "embed", "lm_head",
                                 "ln_f", "pos_embed"]
        assert names["block_0"] == names["block_1"] == GPT2_TREE["block"]
        tokens = _batch(0, T=24)[0]
        got = model.apply({"params": params}, tokens)
        s = gpt2_ref.sizes(cfg)
        want = gpt2_ref.logits(gpt2_ref.init_params(gpt2_ref.seed_key(SEED),
                                                    s), tokens, s)
        _close(got, want, 1e-4)
        # The per-layer spelling of the default is the same program.
        spelled = GPT(dataclasses.replace(model.config,
                                          layers=("block", "block")))
        assert np.array_equal(np.asarray(spelled.apply({"params": params},
                                                       tokens)),
                              np.asarray(got))

    def test_brumbys_tree_and_logits(self):
        from hvdbench.models import brumby as brumby_family
        from hvdbench.reference import brumby as brumby_ref

        cfg = {"vocab_size": 97, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
               "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
               "rope_theta": 1000000, "hidden_act": "silu",
               "tie_word_embeddings": False,
               "run": {"activation_dtype": "float32",
                       "param_dtype": "float32"}}
        model = brumby_family.build_model(cfg, "full")
        assert model.config.layer_kinds == ("block", "block")
        params = brumby_family.make_params(cfg, SEED)
        names = _names(jax.tree.map(lambda x: x, params))
        assert sorted(names) == ["block_0", "block_1", "embed", "lm_head",
                                 "ln_f"]
        assert sorted(names["block_0"]) == ["ln1", "ln2", "mlp", "retn"]
        assert names["block_0"]["mlp"] == {"down": ["kernel"],
                                           "gate": ["kernel"],
                                           "up": ["kernel"]}
        tokens = _batch(0, T=24)[0]
        s = brumby_ref.sizes(cfg)
        want = brumby_ref.logits(
            brumby_ref.init_params(brumby_ref.seed_key(SEED), s), tokens, s)
        _close(model.apply({"params": params}, tokens), want, 1e-4)
        assert cache_kinds(model.config) == ("state", "state")


class TestSmallerPieces:
    def test_squared_relu_feed_forward(self):
        from horovod_tpu.models.transformer import MlpBlock

        cfg = GPTConfig(d_model=16, d_ff=40, mlp="relu2",
                        dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
        layer = MlpBlock(cfg)
        p = layer.init(jax.random.PRNGKey(1), x)["params"]
        assert sorted(p) == ["down", "up"]
        want = jnp.square(jnp.maximum(x @ p["up"]["kernel"], 0.0)) \
            @ p["down"]["kernel"]
        _close(layer.apply({"params": p}, x), want)

    def test_no_positions_means_no_table_and_no_rotation(self):
        cfg = GPTConfig(vocab_size=50, n_layer=1, n_head=2, d_model=16,
                        d_ff=32, max_seq_len=32, attention="full",
                        positions="none", dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jnp.asarray(_batch(0, rows=1, T=12)[0]) % 50
        p = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
        assert "pos_embed" not in p
        # A causal model without positions reads a prefix the same
        # wherever the tokens after it go.
        apply = jax.jit(lambda t: model.apply({"params": p}, t))
        a, b = apply(tokens), apply(tokens.at[:, 8:].set(tokens[:, 4:8]))
        _close(b[:, :8], a[:, :8], 1e-6)
        with pytest.raises(ValueError, match="Unknown positions"):
            GPT(dataclasses.replace(cfg, positions="sinusoid")).init(
                jax.random.PRNGKey(0), tokens)
