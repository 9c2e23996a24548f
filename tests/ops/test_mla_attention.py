"""The absorbed decode attention kernel (ops/mla_attention.py) in
interpret mode against the XLA formulation ``forward`` keeps beside it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.ops.mla_attention import block_positions, mla_decode_attention


def xla(q_lat, q_pe, c, r, layer, lengths, scale):
    """Over positions ``0 .. lengths[b] - 1``; zeros for a length of 0."""
    c, r = c[layer].astype(jnp.float32), r[layer].astype(jnp.float32)
    s = (
        jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), c)
        + jnp.einsum("bhe,bse->bhs", q_pe.astype(jnp.float32), r)
    ) * scale
    seen = jnp.arange(c.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    out = jnp.einsum("bhs,bsr->bhr", p, c)
    return jnp.where(lengths[:, None, None] > 0, out, 0.0)


@pytest.mark.parametrize(
    "S,lengths",
    [
        (64, [1, 64, 18]),         # one block, the whole cache
        (256, [6, 256, 129]),      # two blocks of 128: a slot in the first only
        (1536, [1536, 4, 701]),    # three blocks of 512
        (256, [0, 128, 0, 0, 129, 0]),   # nobody holds slots 0, 2, 3, 5
        (64, [0, 0]),              # nobody holds any
    ],
)
def test_kernel_is_the_xla_formulation(S, lengths):
    L, B, H, rank, rope = 3, len(lengths), 8, 128, 64
    keys = jax.random.split(jax.random.key(S), 4)
    q_lat = jax.random.normal(keys[0], (B, H, rank), jnp.float32)
    q_pe = jax.random.normal(keys[1], (B, H, rope), jnp.float32)
    c = jax.random.normal(keys[2], (L, B, S, rank), jnp.float32)
    r = jax.random.normal(keys[3], (L, B, S, rope), jnp.float32)
    pos = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 2):
        got = mla_decode_attention(
            q_lat, q_pe, c, r, jnp.int32(layer), pos, 0.07, interpret=True
        )
        want = xla(q_lat, q_pe, c, r, layer, pos, 0.07)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )


def test_rows_at_or_above_a_slot_s_length_do_not_count():
    """Whatever lies at or above the length (a former tenant's rows; all
    of a slot nobody holds) leaves every result as it is."""
    B, H, rank, rope, S = 3, 4, 128, 64, 256
    keys = jax.random.split(jax.random.key(0), 4)
    q_lat = jax.random.normal(keys[0], (B, H, rank))
    q_pe = jax.random.normal(keys[1], (B, H, rope))
    c = jax.random.normal(keys[2], (1, B, S, rank))
    r = jax.random.normal(keys[3], (1, B, S, rope))
    pos = jnp.asarray([41, 0, 131], jnp.int32)
    clean = mla_decode_attention(
        q_lat, q_pe, c, r, jnp.int32(0), pos, 0.1, interpret=True
    )
    above = jnp.arange(S)[None, :, None] >= pos[:, None, None]
    dirty = mla_decode_attention(
        q_lat, q_pe, jnp.where(above, -3e4, c[0])[None],
        jnp.where(above, 1e4, r[0])[None], jnp.int32(0), pos, 0.1,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    assert not np.asarray(clean[1]).any()


def test_the_block_divides_the_cache_or_there_is_none():
    assert block_positions(8192) == 1024
    assert block_positions(1536) == 512
    assert block_positions(64) == 64          # one block, the tests' caches
    assert block_positions(1000) is None      # forward takes the XLA form
    assert block_positions(100) is None
