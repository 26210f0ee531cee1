"""Flash-attention kernel tests (interpret mode on the CPU mesh;
numerics vs the full_attention reference implementation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas_attention import (
    flash_attention, flash_attention_padded, flash_attention_with_lse,
    padded_length, plan,
)
from horovod_tpu.parallel.ring_attention import full_attention


def _qkv(b=2, t=64, h=2, d=16, dtype=jnp.float32, seed=0, tk=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = [(b, t, h, d)] + [(b, tk or t, h, d)] * 2
    return tuple(jax.random.normal(k, shape, dtype)
                 for k, shape in zip(ks, shapes))


def _lse(q, k, causal):
    """The rows' logsumexp in float32, ``[B, H, T]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * q.shape[-1] ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


# GPT-2 medium's call (8 x 1024, 16 heads of 64) and the Nemotron
# layer's (1 x 8192, 32 heads of 128), as the train cells make them.
GPT2M = dict(b=8, h=16, t=1024, tk=1024, d=64, dtype=jnp.bfloat16,
             causal=True)
NEMOTRON = dict(b=1, h=32, t=8192, tk=8192, d=128, dtype=jnp.bfloat16,
                causal=True)


class TestPlan:
    """What a call will do, read where ``_flash_fwd`` reads it."""

    @pytest.mark.parametrize("shape,heads,most_steps,most_chunks", [
        (GPT2M, 2, 512, 512), (NEMOTRON, 1, 4096, 4096),
    ], ids=["gpt2-medium", "nemotron"])
    def test_a_few_hundred_steps_and_none_dead(self, shape, heads,
                                               most_steps, most_chunks):
        p = plan(**shape)
        assert p.steps == p.grid[0] * p.grid[1] * p.grid[2] <= most_steps
        assert p.dead_steps == 0
        assert p.heads == heads and p.lanes == 128 and p.lane_packed
        assert p.block_q * p.heads >= 512
        # A block of queries masks its own keys and no others.
        assert p.masked_chunks == p.steps <= p.chunks <= most_chunks
        assert p.vmem_limit_bytes >= 16 << 20

    def test_one_chunk_sequence_has_no_walk(self):
        p = plan(**GPT2M)
        assert p.block_k == 1024 and p.chunks == p.steps

    @pytest.mark.parametrize("t,padded", [
        (130, 256), (1000, 1024), (1024, 1024), (37, 40), (128, 128)])
    def test_padding_stops_at_the_128_multiple(self, t, padded):
        assert padded_length(t) == padded
        p = plan(**dict(GPT2M, t=padded, tk=padded))
        assert padded % p.block_q == 0 and padded % p.block_k == 0
        assert p.dead_steps == 0

    def test_explicit_blocks_override_the_choice(self):
        p = plan(**GPT2M, block_q=128, block_k=128)
        assert (p.block_q, p.block_k) == (128, 128)
        assert p.grid == (8, 8, 8)
        # Block i walks i chunks below the diagonal and its own.
        assert p.chunks == 8 * 8 * sum(range(1, 9))
        assert padded_length(100, 32, 32) == 128

    @pytest.mark.parametrize("h,d,heads,lane_packed", [
        (16, 64, 2, True), (32, 128, 1, True), (4, 32, 4, True),
        (25, 64, 1, False), (2, 16, 1, False), (2, 256, 1, True)])
    def test_heads_a_step_follow_the_head_size(self, h, d, heads,
                                               lane_packed):
        p = plan(2, h, 256, 256, d, jnp.bfloat16, True)
        assert (p.heads, p.lane_packed) == (heads, lane_packed)
        assert p.lanes == heads * d

    def test_float32_asks_for_more_vmem(self):
        bf16 = plan(**NEMOTRON)
        f32 = plan(**dict(NEMOTRON, dtype=jnp.float32))
        assert f32.vmem_limit_bytes > bf16.vmem_limit_bytes
        assert (f32.block_q, f32.block_k) == (bf16.block_q, bf16.block_k)

    def test_lengths_the_blocks_cannot_tile_are_refused(self):
        with pytest.raises(ValueError, match="multiples"):
            plan(1, 2, 200, 200, 64, jnp.float32, True)
        with pytest.raises(ValueError, match="divide the other"):
            plan(1, 2, 384, 384, 64, jnp.float32, True,
                 block_q=128, block_k=192)
        with pytest.raises(ValueError, match="Tq == Tk"):
            plan(1, 2, 128, 256, 64, jnp.float32, True)


# (t, tk, block_q, block_k): the blocks None where the call chooses.
ONE_BLOCK = (128, None, None, None)
SEVERAL = (384, None, None, None)        # three blocks, one chunk: no walk
WALK = (512, None, 128, 256)             # chunks below, two last widths
NARROW_KEYS = (256, None, 128, 64)       # block_k under block_q
CROSS = (128, 384, None, 128)


class TestChosenBlocks:
    """Forward and ``lse`` against the reference through the paths the
    shapes select: heads stacked in a step (64), a head a step (128)."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("h,d", [(2, 64), (1, 128)],
                             ids=["d64", "d128"])
    @pytest.mark.parametrize("causal,lengths", [
        (True, ONE_BLOCK), (True, SEVERAL), (True, WALK),
        (True, NARROW_KEYS), (False, ONE_BLOCK), (False, SEVERAL),
        (False, CROSS),
    ], ids=["causal-one", "causal-several", "causal-walk",
            "causal-narrow-keys", "full-one", "full-several",
            "full-cross"])
    def test_forward_and_lse(self, causal, lengths, h, d, dtype):
        t, tk, block_q, block_k = lengths
        q, k, v = _qkv(b=1, t=t, tk=tk, h=h, d=d, dtype=dtype)
        out, lse = flash_attention_with_lse(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k)
        ref = full_attention(q, k, v, causal=causal)
        assert out.dtype == dtype and lse.dtype == jnp.float32
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol)
        # The scores are exact products of the inputs in either dtype.
        np.testing.assert_allclose(np.asarray(lse),
                                   np.asarray(_lse(q, k, causal)),
                                   atol=1e-4, rtol=1e-5)

    def test_negative_scale(self):
        q, k, v = _qkv(b=1, t=128, h=2, d=64)
        out = flash_attention(q, k, v, causal=True, scale=-0.3)
        ref = full_attention(q, k, v, causal=True, scale=-0.3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("t", [130, 200])
    def test_padded_lengths_with_chosen_blocks(self, t):
        q, k, v = _qkv(b=1, t=t, h=2, d=64)
        out = flash_attention_padded(q, k, v)
        ref = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_single_block(self):
        q, k, v = _qkv(t=32)
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = full_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = full_attention(q, k, v)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_cross_attention_lengths(self):
        q, _, _ = _qkv(t=32)
        _, k, v = _qkv(t=64, seed=1)
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = full_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("t", [
        24, 48, pytest.param(100, marks=pytest.mark.slow)])
    def test_padded_odd_lengths(self, t):
        # Non-block-multiple causal self-attention via the padded entry.
        q, k, v = _qkv(t=t, d=8)
        out = flash_attention_padded(q, k, v, block_q=32, block_k=32)
        ref = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_padded_grads(self):
        q, k, v = _qkv(t=24, d=8)

        def loss(q, k, v):
            return jnp.sum(flash_attention_padded(
                q, k, v, block_q=32, block_k=32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_bad_shapes_rejected(self):
        q, k, v = _qkv(t=48)
        with pytest.raises(ValueError, match="multiples"):
            flash_attention(q, k, v, block_q=32, block_k=32)
        with pytest.raises(ValueError, match="B, T, H, D"):
            flash_attention(q[0], k[0], v[0])


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_full_attention(self, causal):
        q, k, v = _qkv(t=64, d=8)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal,
                                block_q=32, block_k=32)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = full_attention(q, k, v, causal=causal)
            return jnp.sum(o * o)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,d", [(2, 64), (1, 128)],
                             ids=["d64", "d128"])
    def test_grads_do_not_follow_the_forward_blocks(self, causal, h, d):
        """The backward walks keys 128 at a time whatever the forward
        chose: the gradients of the chosen blocks are those of explicit
        ones, and the reference's."""
        q, k, v = _qkv(b=1, t=256, h=h, d=d)
        w = jax.random.normal(jax.random.PRNGKey(7), (1, h, 256))

        def loss(attend):
            def f(q, k, v):
                o, lse = attend(q, k, v)
                return jnp.sum(o * o) + jnp.sum(w * lse)
            return jax.grad(f, argnums=(0, 1, 2))

        chosen = loss(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal))(q, k, v)
        given = loss(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal, block_q=128, block_k=64))(q, k, v)
        ref = loss(lambda q, k, v: (
            full_attention(q, k, v, causal=causal),
            _lse(q, k, causal)))(q, k, v)
        for a, b, c, name in zip(chosen, given, ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5,
                                       err_msg=f"d{name} chosen vs given")
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=2e-4, rtol=2e-4,
                                       err_msg=f"d{name} vs reference")

    def test_jit_and_value(self):
        q, k, v = _qkv(t=32, d=8)

        @jax.jit
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   block_q=32, block_k=32).sum()

        assert np.isfinite(float(f(q, k, v)))
