"""Flash-attention kernel tests (interpret mode on the CPU mesh;
numerics vs the full_attention reference implementation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas_attention import (
    flash_attention, flash_attention_padded, flash_attention_with_lse,
    padded_length, plan, plan_bwd,
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

    def test_bad_shapes_rejected(self):
        q, k, v = _qkv(t=48)
        with pytest.raises(ValueError, match="multiples"):
            flash_attention(q, k, v, block_q=32, block_k=32)
        with pytest.raises(ValueError, match="B, T, H, D"):
            flash_attention(q[0], k[0], v[0])


# (h, d): two 64-wide heads share a vector; a 128-wide head fills one;
# three 64-wide heads are no whole vectors and an 8-wide head is no
# vector, so both go in as [B * H, T, D].
LAYOUTS = {"d64x2": (2, 64), "d128": (1, 128), "d64x3": (3, 64),
           "d8": (2, 8)}
# bfloat16: each gradient within 2 % of the float32 reference's norm
# (p, dS and the results are rounded to 8 bits of mantissa).
BF16_NORM_GAP = 2e-2


def _grads(attend, q, k, v, w=None):
    """Gradients of a loss that reads ``o`` and, where ``w`` is given,
    ``lse`` (so its cotangent is not zero)."""
    def loss(q, k, v):
        o, lse = attend(q, k, v)
        o = o.astype(jnp.float32)
        out = jnp.sum(o * o)
        return out if w is None else out + jnp.sum(w * lse)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _reference(causal, scale=None):
    def attend(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        lse = _lse(q, k, causal) if scale is None else None
        return full_attention(q, k, v, causal=causal, scale=scale), lse
    return attend


def _assert_grads(got, want, dtype, what=""):
    for a, b, name in zip(got, want, "qkv"):
        assert a.dtype == dtype and a.shape == b.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                       err_msg=f"d{name} {what}")
        else:
            gap = np.linalg.norm(a - b) / np.linalg.norm(b)
            assert gap < BF16_NORM_GAP, f"d{name} {what}: {gap}"


class TestPlanBwd:
    """What a backward call will do, read where ``_flash_bwd`` reads
    it."""

    @pytest.mark.parametrize("shape,heads,block_k,steps,chunks", [
        (GPT2M, 2, 256, 256, 448), (NEMOTRON, 1, 512, 512, 2560),
    ], ids=["gpt2-medium", "nemotron"])
    def test_a_few_hundred_steps_and_none_dead(self, shape, heads, block_k,
                                               steps, chunks):
        p = plan_bwd(**shape)
        assert p.steps == p.grid[0] * p.grid[1] * p.grid[2] == steps
        assert p.dead_steps == 0
        assert p.heads == heads and p.lanes == 128 and p.lane_packed
        assert (p.block_k, p.block_q) == (block_k, 1024)
        # A block of keys masks its own queries and no others.
        assert p.masked_chunks == p.steps and p.chunks == chunks
        assert p.vmem_limit_bytes >= 16 << 20

    def test_one_chunk_sequence_has_no_loop(self):
        p = plan_bwd(**GPT2M)
        # Its own queries, and what is left of the one chunk: nothing
        # for the last block.
        assert p.block_q == GPT2M["t"]
        assert p.chunks == 2 * p.steps - p.steps // p.grid[2]

    @pytest.mark.parametrize("causal", [True, False])
    def test_chunks_walked_and_masked_as_the_triangle_says(self, causal):
        p = plan_bwd(**dict(GPT2M, causal=causal), block_q=128, block_k=128)
        assert (p.block_k, p.block_q) == (128, 128) and p.grid == (8, 8, 8)
        if causal:
            # Key block j walks the 8 - j chunks from its diagonal on.
            assert p.chunks == 8 * 8 * sum(range(1, 9))
            assert p.masked_chunks == p.steps
        else:
            assert p.chunks == 8 * 8 * 8 * 8 and p.masked_chunks == 0

    def test_given_blocks_are_the_chunk_and_the_key_block(self):
        full = plan_bwd(**dict(GPT2M, causal=False), block_q=128,
                        block_k=256)
        assert (full.block_q, full.block_k) == (128, 256)
        # A causal chunk is whole key blocks: the larger of the two.
        causal = plan_bwd(**GPT2M, block_q=128, block_k=256)
        assert (causal.block_q, causal.block_k) == (256, 128)
        # Two blocks a chunk: every other block has half a chunk left.
        assert causal.chunks == 8 * 8 * (8 + 4 + sum(range(4)) * 2)

    @pytest.mark.parametrize("h,d,heads,lane_packed", [
        (16, 64, 2, True), (32, 128, 1, True), (4, 32, 4, True),
        (25, 64, 1, False), (2, 16, 1, False)])
    def test_layout_is_the_forwards(self, h, d, heads, lane_packed):
        fwd = plan(2, h, 256, 256, d, jnp.bfloat16, True)
        bwd = plan_bwd(2, h, 256, 256, d, jnp.bfloat16, True)
        assert (bwd.heads, bwd.lanes, bwd.lane_packed) == (
            fwd.heads, fwd.lanes, fwd.lane_packed) == (
            heads, heads * d, lane_packed)
        assert bwd.grid[:2] == fwd.grid[:2]

    def test_float32_asks_for_more_vmem(self):
        bf16 = plan_bwd(**NEMOTRON)
        f32 = plan_bwd(**dict(NEMOTRON, dtype=jnp.float32))
        assert f32.vmem_limit_bytes > bf16.vmem_limit_bytes
        assert (f32.block_k, f32.block_q) == (bf16.block_k, bf16.block_q)

    def test_lengths_the_blocks_cannot_tile_are_refused(self):
        with pytest.raises(ValueError, match="multiples"):
            plan_bwd(1, 2, 200, 200, 64, jnp.float32, True)
        with pytest.raises(ValueError, match="whole key blocks"):
            plan_bwd(1, 2, 384, 384, 64, jnp.float32, True,
                     block_q=128, block_k=192)
        with pytest.raises(ValueError, match="Tq == Tk"):
            plan_bwd(1, 2, 128, 256, 64, jnp.float32, True)


class TestFlashBackward:
    """The backward kernel against ``full_attention``'s gradients (and
    ``lse``'s), in float32 whatever the inputs."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("layout", list(LAYOUTS))
    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    def test_grads_match_full_attention(self, causal, layout, dtype):
        """Four key blocks of 64 against chunks of 128 queries: under
        ``causal`` a block's own queries, the half chunk left beside
        them, and the chunks after; ``dlse`` is not zero."""
        h, d = LAYOUTS[layout]
        q, k, v = _qkv(b=2, t=256, h=h, d=d, dtype=dtype)
        w = jax.random.normal(jax.random.PRNGKey(7), (2, h, 256))
        got = _grads(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal, block_q=128, block_k=64), q, k, v, w)
        want = _grads(_reference(causal), q, k, v, w)
        _assert_grads(got, want, dtype)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,d", [(2, 64), (1, 128)],
                             ids=["d64", "d128"])
    def test_grads_do_not_follow_the_given_blocks(self, causal, h, d):
        """The blocks are a matter of speed: the gradients of the
        chosen blocks (one key block at this length, no walk) are those
        of given ones (four key blocks, a walk), and the reference's."""
        q, k, v = _qkv(b=1, t=256, h=h, d=d)
        w = jax.random.normal(jax.random.PRNGKey(7), (1, h, 256))
        assert plan_bwd(1, h, 256, 256, d, q.dtype, causal).grid[2] == 1
        chosen = _grads(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal), q, k, v, w)
        given = _grads(lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal, block_q=128, block_k=64), q, k, v, w)
        _assert_grads(chosen, given, jnp.float32, "chosen vs given")
        _assert_grads(chosen, _grads(_reference(causal), q, k, v, w),
                      jnp.float32, "vs reference")

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("layout", ["d64x2", "d64x3"])
    def test_cross_attention_lengths(self, layout, dtype):
        h, d = LAYOUTS[layout]
        q, k, v = _qkv(b=1, t=128, tk=384, h=h, d=d, dtype=dtype)
        w = jax.random.normal(jax.random.PRNGKey(7), (1, h, 128))
        got = _grads(lambda q, k, v: flash_attention_with_lse(
            q, k, v, block_k=128), q, k, v, w)
        _assert_grads(got, _grads(_reference(False), q, k, v, w), dtype)

    @pytest.mark.parametrize("t,d,blocks", [
        (24, 8, 32), (130, 64, None), (300, 64, None),
        pytest.param(100, 8, 32, marks=pytest.mark.slow)])
    def test_padded_odd_lengths(self, t, d, blocks):
        q, k, v = _qkv(b=1, t=t, d=d)
        got = _grads(lambda q, k, v: (flash_attention_padded(
            q, k, v, block_q=blocks, block_k=blocks), None), q, k, v)
        _assert_grads(got, _grads(_reference(True), q, k, v), jnp.float32)

    def test_negative_scale(self):
        q, k, v = _qkv(b=1, t=128, h=2, d=64)
        got = _grads(lambda q, k, v: (flash_attention(
            q, k, v, causal=True, scale=-0.3), None), q, k, v)
        want = _grads(_reference(True, scale=-0.3), q, k, v)
        _assert_grads(got, want, jnp.float32)

    def test_jit_and_value(self):
        q, k, v = _qkv(t=32, d=8)

        @jax.jit
        def f(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   block_q=32, block_k=32).sum()

        assert np.isfinite(float(f(q, k, v)))
