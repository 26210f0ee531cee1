"""Model zoo + multi-axis SPMD training tests."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import MLP, ResNet18, GPT, GPTConfig
from horovod_tpu.models.transformer import lm_loss_fn
from horovod_tpu.parallel import (
    make_mesh, make_spmd_train_step, shard_batch, shard_params,
    init_opt_state,
)
from jax.sharding import PartitionSpec as P


class TestMLP:
    def test_trains_on_toy_mnist(self, world_size):
        rng = np.random.RandomState(0)
        x = rng.randn(64, 28 * 28).astype(np.float32)
        y = rng.randint(0, 10, 64)
        model = MLP()
        params = model.init(jax.random.PRNGKey(0), x[:1])["params"]

        def loss_fn(params, batch):
            xb, yb = batch
            logits = model.apply({"params": params}, xb)
            return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(len(yb)), yb])

        tx = optax.adam(1e-3)
        step = hvd.make_train_step(loss_fn, tx, donate=False)
        state = tx.init(params)
        losses = []
        for _ in range(20):
            params, state, loss = step(params, state, (x, y))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7


class TestResNet:
    def test_forward_shape_and_train_step(self):
        model = ResNet18(num_classes=10, width=8)
        x = jnp.zeros((4, 32, 32, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x)
        logits, mutated = model.apply(variables, x, mutable=["batch_stats"])
        assert logits.shape == (4, 10)
        assert "batch_stats" in mutated

    def test_sync_bn_axis(self, world_size):
        # SyncBatchNorm statistics ride the mapped axis: build the model
        # with bn_axis_name and run under shard_map.
        from horovod_tpu._compat import shard_map

        gm = hvd.global_mesh()
        model = ResNet18(num_classes=4, width=8, bn_axis_name=gm.axis_name)
        x = np.random.RandomState(0).randn(8, 8, 8, 3).astype(np.float32)
        variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x[:1]))

        def fwd(xb):
            out, _ = model.apply(variables, xb, mutable=["batch_stats"])
            return out

        body = shard_map(fwd, mesh=gm.mesh, in_specs=P(gm.axis_name),
                         out_specs=P(gm.axis_name), check=False)
        out = jax.jit(body)(jnp.asarray(x))
        assert out.shape == (8, 4)
        assert bool(jnp.isfinite(out).all())


def _tiny_gpt(attention="full", mesh=None, seq=16):
    cfg = GPTConfig(vocab_size=64, n_layer=2, n_head=4, d_model=32,
                    d_ff=64, max_seq_len=seq, attention=attention,
                    dtype=jnp.float32)
    model = GPT(cfg, mesh=mesh)
    tokens = np.random.RandomState(0).randint(0, 64, (8, seq))
    # Init with a mesh-divisible dummy (B=2, T=16 divides dp/sp sizes used
    # in these tests); param shapes don't depend on B/T.
    params = model.init(jax.random.PRNGKey(0),
                        jnp.asarray(tokens[:2, :16]))["params"]
    return model, params, tokens


class TestGPT:
    def test_forward(self):
        model, params, tokens = _tiny_gpt()
        logits = model.apply({"params": params}, jnp.asarray(tokens))
        assert logits.shape == (8, 16, 64)
        assert bool(jnp.isfinite(logits).all())

    def test_dp_training_loss_decreases(self, world_size):
        model, params, tokens = _tiny_gpt()
        loss_fn = lm_loss_fn(model)
        tx = optax.adam(1e-2)
        step = hvd.make_train_step(loss_fn, tx, donate=False)
        state = tx.init(params)
        batch = (tokens[:, :-1], tokens[:, 1:])
        first = None
        for _ in range(10):
            params, state, loss = step(params, state, batch)
            first = float(loss) if first is None else first
        assert float(loss) < first

    def test_flash_attention_matches_full(self):
        """Same weights, same logits: pallas flash kernel (interpret mode
        on CPU) vs full attention."""
        import dataclasses

        model_f, params, tokens = _tiny_gpt("full")
        model_fl = GPT(dataclasses.replace(model_f.config,
                                           attention="flash"))
        lf = model_f.apply({"params": params}, jnp.asarray(tokens))
        lfl = model_fl.apply({"params": params}, jnp.asarray(tokens))
        np.testing.assert_allclose(np.asarray(lfl), np.asarray(lf),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_attention_grads_match_full(self, causal):
        """Same weights, same gradients: the flash kernels, forward and
        backward, under the model's own entry point (a padded causal
        length; one clamped block when not causal)."""
        import dataclasses

        model_f, params, tokens = _tiny_gpt("full")
        tokens = jnp.asarray(tokens)

        def grads(attention):
            model = GPT(dataclasses.replace(
                model_f.config, attention=attention, causal=causal))
            return jax.grad(lambda p: jnp.sum(
                model.apply({"params": p}, tokens) ** 2))(params)

        flat_f = jax.tree_util.tree_leaves_with_path(grads("full"))
        flat_fl = jax.tree_util.tree_leaves(grads("flash"))
        for (path, a), b in zip(flat_f, flat_fl):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=2e-3, atol=2e-3,
                err_msg=jax.tree_util.keystr(path))

    def test_flash_noncausal_short_seq_ok(self):
        """T < 128 runs as one clamped block — must not be rejected by
        the non-causal guard (regression)."""
        import dataclasses

        model_f, params, tokens = _tiny_gpt("full")
        cfg = dataclasses.replace(model_f.config, attention="flash",
                                  causal=False)
        model_fl = GPT(cfg)
        model_ref = GPT(dataclasses.replace(cfg, attention="full"))
        lfl = model_fl.apply({"params": params}, jnp.asarray(tokens))
        lf = model_ref.apply({"params": params}, jnp.asarray(tokens))
        np.testing.assert_allclose(np.asarray(lfl), np.asarray(lf),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_attention_matches_full(self):
        """The same weights must produce the same logits under sp=8 ring
        attention as under single-chip full attention."""
        import dataclasses

        mesh = make_mesh({"sp": 8})
        model_f, params, tokens = _tiny_gpt("full")
        model_r = GPT(dataclasses.replace(model_f.config, attention="ring"),
                      mesh=mesh)
        lf = model_f.apply({"params": params}, jnp.asarray(tokens))
        lr = model_r.apply({"params": params}, jnp.asarray(tokens))
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                                   rtol=2e-4, atol=2e-4)

    def test_dp_sp_tp_training(self):
        """Full 3-axis SPMD training step: dp×sp×tp = 2×2×2, ring
        attention, tp-sharded params, one step runs and loss is finite."""
        mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
        model, params, _ = _tiny_gpt("ring", mesh=mesh, seq=17)
        # inputs/targets of length 16: divisible by sp=2
        tokens = np.random.RandomState(1).randint(0, 64, (8, 17))
        params = shard_params(params, mesh)
        loss_fn = lm_loss_fn(model)
        tx = optax.adam(1e-2)
        opt_state = init_opt_state(tx, params)
        step = make_spmd_train_step(loss_fn, tx, donate=False)
        batch = shard_batch(
            (jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])),
            mesh, P("dp", "sp"))
        params2, opt_state, loss = step(params, opt_state, batch)
        assert np.isfinite(float(loss))
        # and a second step with the updated params still works
        params3, opt_state, loss2 = step(params2, opt_state, batch)
        assert np.isfinite(float(loss2))


class TestBert:
    """BERT family — the reference's 'BERT-Large fine-tune with tensor
    fusion + fp16 Compression' baseline config (BASELINE.json #4) on a
    tiny config."""

    def _tiny(self, **kw):
        from horovod_tpu.models import BertConfig

        kw.setdefault("vocab_size", 64)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 2)
        kw.setdefault("d_model", 16)
        kw.setdefault("d_ff", 32)
        kw.setdefault("max_seq_len", 16)
        kw.setdefault("dtype", jnp.float32)
        return BertConfig(**kw)

    @pytest.mark.slow
    def test_classifier_forward_shape(self):
        from horovod_tpu.models import BertForSequenceClassification

        model = BertForSequenceClassification(self._tiny(), num_classes=3)
        ids = jnp.zeros((2, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        logits = model.apply({"params": params}, ids)
        assert logits.shape == (2, 3)
        assert logits.dtype == jnp.float32

    def test_padding_mask_blocks_padded_keys(self):
        # The [CLS] output (hence the classifier logits) must not depend
        # on the *content* of positions masked out by attention_mask.
        from horovod_tpu.models import BertForSequenceClassification

        model = BertForSequenceClassification(self._tiny())
        rng = np.random.RandomState(0)
        ids_a = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
        ids_b = ids_a.at[:, 6:].set(jnp.asarray(
            rng.randint(0, 64, (2, 2)), jnp.int32))
        mask = jnp.asarray([[1] * 6 + [0] * 2] * 2, jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids_a)["params"]
        la = model.apply({"params": params}, ids_a, None, mask)
        lb = model.apply({"params": params}, ids_b, None, mask)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)
        # ...and with the mask open the padded-position content matters.
        lc = model.apply({"params": params}, ids_b)
        assert not np.allclose(np.asarray(la), np.asarray(lc), atol=1e-4)

    @pytest.mark.slow
    def test_mlm_tied_decoder(self):
        # MLM logits come from Embed.attend: no separate [V, d] decoder
        # matrix exists, and the embedding receives gradient from the
        # head (both directions of the tie).
        from horovod_tpu.models import BertForMaskedLM

        model = BertForMaskedLM(self._tiny())
        ids = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        logits = model.apply({"params": params}, ids)
        assert logits.shape == (1, 8, 64)
        flat = jax.tree_util.tree_leaves_with_path(params)
        decoders = [jax.tree_util.keystr(k) for k, v in flat
                    if v.ndim == 2 and v.shape == (64, 16)]
        assert decoders == ["['bert']['tok_embed']['embedding']"], decoders

        def loss(p):
            lg = model.apply({"params": p}, ids)
            return -jnp.mean(jax.nn.log_softmax(lg)[..., 0])

        g = jax.grad(loss)(params)
        assert float(jnp.abs(
            g["bert"]["tok_embed"]["embedding"]).sum()) > 0.0

    def test_mlm_loss_attention_mask_path(self, world_size):
        # 4-tuple batches thread attention_mask into the encoder: the
        # loss must ignore pad-token *content* (review-r3: the 3-tuple
        # contract had no way to pass it).
        from horovod_tpu.models import BertForMaskedLM
        from horovod_tpu.models.bert import masked_lm_loss_fn

        model = BertForMaskedLM(self._tiny())
        rng = np.random.RandomState(10)
        ids_a = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
        ids_b = ids_a.at[:, 6:].set(jnp.asarray(
            rng.randint(0, 64, (2, 2)), jnp.int32))
        attn = jnp.asarray([[1] * 6 + [0] * 2] * 2, jnp.int32)
        labels = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
        lmask = jnp.asarray([[1, 1, 0, 0, 0, 0, 0, 0]] * 2, jnp.float32)
        params = model.init(jax.random.PRNGKey(0), ids_a)["params"]
        for chunk in (0, 5):
            fn = masked_lm_loss_fn(model, vocab_chunk_size=chunk)
            la = fn(params, (ids_a, attn, labels, lmask))
            lb = fn(params, (ids_b, attn, labels, lmask))
            np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)

    def test_finetune_step_with_fusion_and_fp16(self, world_size):
        # The baseline config end to end: DistributedOptimizer with
        # tensor fusion + Compression.fp16 over the mesh.
        from horovod_tpu.models import BertForSequenceClassification
        from horovod_tpu.models.bert import classification_loss_fn

        model = BertForSequenceClassification(self._tiny(), num_classes=4)
        rng = np.random.RandomState(1)
        ids = jnp.asarray(rng.randint(0, 64, (16, 8)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 4, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]
        tx = hvd.DistributedOptimizer(optax.adam(5e-3),
                                      compression=hvd.Compression.fp16)
        step = hvd.make_train_step(classification_loss_fn(model), tx,
                                   donate=False)
        state = tx.init(params)
        losses = []
        for _ in range(12):
            params, state, loss = step(params, state, (ids, labels))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8, losses

    def test_masked_batch_loss_path(self, world_size):
        # (input_ids, attention_mask, labels) batches reach the model's
        # key-padding mask through the shipped training path.
        from horovod_tpu.models import BertForSequenceClassification
        from horovod_tpu.models.bert import classification_loss_fn

        model = BertForSequenceClassification(self._tiny())
        rng = np.random.RandomState(2)
        ids = jnp.asarray(rng.randint(0, 64, (8, 8)), jnp.int32)
        mask = jnp.ones((8, 8), jnp.int32).at[:, 6:].set(0)
        labels = jnp.asarray(rng.randint(0, 2, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:1])["params"]
        loss_fn = classification_loss_fn(model)
        l_masked = loss_fn(params, (ids, mask, labels))
        # Padded-token identity must not affect the masked loss.
        ids_b = ids.at[:, 6:].set(jnp.asarray(
            rng.randint(0, 64, (8, 2)), jnp.int32))
        l_masked_b = loss_fn(params, (ids_b, mask, labels))
        np.testing.assert_allclose(float(l_masked), float(l_masked_b),
                                   rtol=1e-5)
        l_open = loss_fn(params, (ids_b, labels))
        assert abs(float(l_open) - float(l_masked_b)) > 1e-6


    def test_mlm_loss_chunked_matches_dense(self, world_size):
        from horovod_tpu.models import BertForMaskedLM
        from horovod_tpu.models.bert import masked_lm_loss_fn

        model = BertForMaskedLM(self._tiny())
        rng = np.random.RandomState(9)
        ids = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 64, (2, 8)), jnp.int32)
        mask = jnp.asarray(rng.rand(2, 8) < 0.25, jnp.float32)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        batch = (ids, labels, mask)
        dense = masked_lm_loss_fn(model)
        chunked = masked_lm_loss_fn(model, vocab_chunk_size=5)
        np.testing.assert_allclose(float(chunked(params, batch)),
                                   float(dense(params, batch)), rtol=1e-5)
        gd = jax.grad(dense)(params, batch)
        gc = jax.grad(chunked)(params, batch)
        for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gc)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)



@pytest.mark.slow
class TestBenchmarkConvnets:
    """VGG-16 + Inception-V3 — the reference's scaling-table models
    (docs/benchmarks.rst rows; bench.py --model vehicles)."""

    def test_vgg16_forward_and_grad(self):
        from horovod_tpu.models import VGG16

        model = VGG16(num_classes=7, dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3),
                        jnp.float32)
        params = model.init(jax.random.PRNGKey(0), x)["params"]
        logits = model.apply({"params": params}, x)
        assert logits.shape == (2, 7)
        # BN-free: the huge dense head is the communication-bound story
        assert "fc6" in params and "bn" not in str(params.keys())
        g = jax.grad(lambda p: model.apply({"params": p}, x).sum())(params)
        assert float(jnp.abs(g["fc6"]["kernel"]).sum()) > 0

    def test_inception3_forward_shapes(self):
        from horovod_tpu.models import InceptionV3

        model = InceptionV3(num_classes=5, dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 75, 75, 3),
                        jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x)
        logits, mutated = model.apply(variables, x, mutable=["batch_stats"])
        assert logits.shape == (2, 5)
        assert "batch_stats" in mutated  # BN everywhere, upstream-style
        # eval mode runs off the running stats without mutation
        eval_logits = model.apply(variables, x, train=False)
        assert eval_logits.shape == (2, 5)
