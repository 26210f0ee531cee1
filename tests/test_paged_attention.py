"""The paged decode kernel against the gathered view's arithmetic.

All on the CPU: the kernel is reached through ``interpret=True``, the
view is what ``paged_decode`` runs off the TPU.  What the interpreter
cannot see (what Mosaic refuses, what the chip's compiler does with the
pools) is in ``tests/test_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import paged_attention as pa

TRASH = 0
STALE = 3.0e4       # large, finite: what a reused block may hold


def _pools(rng, num_blocks, block, row, dtype):
    shape = (num_blocks, block, row)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _table(rng, lengths, block, cols, num_blocks):
    """Every row's live blocks drawn without order from the pool's
    blocks 1.., the rest of its columns (and rows of length 0, which
    hold no request) on the trash block."""
    free = list(rng.permutation(np.arange(1, num_blocks)))
    table = np.full((len(lengths), cols), TRASH, np.int32)
    for b, n in enumerate(lengths):
        for c in range(-(-n // block)):
            table[b, c] = free.pop()
    return table


def _case(H, K, D, block, cols, lengths, dtype, *, stale=False, seed=0):
    """``(kernel's output, view's output, rows that hold a request)``
    for rows of ``lengths`` tokens (0: a row without a request, which
    arrives at position 0 on trash blocks)."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    row = -(-K * D // 128) * 128
    num_blocks = 1 + sum(-(-n // block) for n in lengths) + 3
    k_pool, v_pool = _pools(rng, num_blocks, block, row, dtype)
    table = _table(rng, lengths, block, cols, num_blocks)
    positions = np.maximum(np.asarray(lengths, np.int32) - 1, 0)
    if stale:
        # Everything no row may see: the trash block, blocks no chain
        # holds, and the rows of each last block beyond the length.
        dead = np.ones((num_blocks, block), bool)
        for b, n in enumerate(lengths):
            for i in range(n):
                dead[table[b, i // block], i % block] = False
        fill = jnp.asarray(dead[..., None] * STALE, dtype)
        k_pool = jnp.where(dead[..., None], fill, k_pool)
        v_pool = jnp.where(dead[..., None], -fill, v_pool)
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    args = (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(positions), K)
    live = np.asarray(lengths) > 0
    return (np.asarray(pa.paged_decode(*args, interpret=True), np.float32),
            np.asarray(pa.paged_decode(*args), np.float32), live)


# GPT-2 XL as the benchmark serves it: 25 heads of 64 (a pool row of
# 1,600 padded to 1,664), blocks of 16, 64 + 1 table columns, 8 rows.
XL = dict(H=25, K=25, D=64, block=16, cols=65,
          lengths=[1, 16, 17, 100, 484, 1024, 0, 0], dtype=jnp.bfloat16)

CASES = {
    "gpt2xl_shape": XL,
    "head_128": dict(H=8, K=8, D=128, block=16, cols=9,
                     lengths=[1, 33, 128, 0], dtype=jnp.bfloat16),
    "grouped_kv_heads": dict(H=20, K=4, D=128, block=16, cols=20,
                             lengths=[5, 300, 64, 17], dtype=jnp.bfloat16),
    "float32_block_8": dict(H=4, K=2, D=64, block=8, cols=40,
                            lengths=[3, 8, 9, 300], dtype=jnp.float32),
    "several_waves": dict(H=2, K=2, D=64, block=16, cols=70,
                          lengths=[1100, 513, 512], dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_the_view(name):
    """Scattered, unordered chains at every depth: one token, a whole
    block, one token into the next block, several waves, the table's
    full width, rows without a request."""
    case = CASES[name]
    got, want, live = _case(**case)
    # The view rounds its scores to the pool's dtype and the kernel
    # keeps them in float32: bfloat16 agrees to its own rounding.
    tol = 2e-2 if case["dtype"] == jnp.bfloat16 else 2e-5
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)


@pytest.mark.parametrize("name", ["gpt2xl_shape", "grouped_kv_heads"])
def test_stale_data_never_reaches_the_output(name):
    """Large values beyond each row's length, in blocks no chain holds
    and in the trash block change no live row's output by a bit: the
    kernel reads only live blocks, and a last block's dead rows get a
    probability of exactly zero."""
    clean, _, live = _case(**CASES[name])
    dirty, want, _ = _case(**CASES[name], stale=True)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty[live], clean[live])
    np.testing.assert_allclose(dirty[live], want[live], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("why,row,block,dtype", [
    ("row_not_whole_vectors", 32, 8, jnp.float32),
    ("block_not_whole_tiles", 128, 4, jnp.float32),
    ("bfloat16_block_of_8", 128, 8, jnp.bfloat16),
])
def test_a_pool_the_kernel_cannot_copy_takes_the_view(why, row, block,
                                                      dtype):
    """Chosen by the pool's shape, whatever ``interpret`` says: no
    kernel in the lowered program, and the view's numbers."""
    rng = np.random.default_rng(1)
    k_pool, v_pool = _pools(rng, 9, block, row, dtype)
    q = jnp.asarray(rng.standard_normal((2, 2, 16)), dtype)
    args = (q, k_pool, v_pool, jnp.asarray([[1, 2, 0], [3, 0, 0]]),
            jnp.asarray([block + 1, 0]), 2)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: pa.paged_decode(*a, 2, interpret=True))(*args[:-1]))
    np.testing.assert_array_equal(
        np.asarray(pa.paged_decode(*args, interpret=True), np.float32),
        np.asarray(pa._decode_view(*args), np.float32))


def test_off_the_tpu_the_default_is_the_views_arithmetic():
    """``interpret=None`` here: no kernel, and the numbers the model's
    view path gives for the same chunk."""
    case = dict(CASES["head_128"])
    rng = np.random.default_rng(2)
    k_pool, v_pool = _pools(rng, 16, 16, 1024, jnp.float32)
    table = jnp.asarray(_table(rng, case["lengths"], 16, 9, 16))
    positions = jnp.asarray([0, 32, 127, 0])
    q = jnp.asarray(rng.standard_normal((4, 8, 128)), jnp.float32)
    want = pa.view_attention(
        q[:, None], pa.gathered_view(k_pool, table, 8, 128),
        pa.gathered_view(v_pool, table, 8, 128), positions[:, None])[:, 0]
    fn = lambda *a: pa.paged_decode(*a, 8)                  # noqa: E731
    args = (q, k_pool, v_pool, table, positions)
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: pa.paged_decode(*a, 8, interpret=True))(*args))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(*args)),
                                  np.asarray(want))
