"""The block's row write (ops/cache_write.py) in interpret mode against
``_write_rows``' scatter, bit for bit, and which steps
``attend_over_cache`` gives to it."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.transformer import _write_rows, attend_over_cache
from gpustack_tpu.ops import cache_write
from gpustack_tpu.ops.cache_write import (
    a_block_is_whole_tiles,
    gqa_write_block_rows,
)
from gpustack_tpu.ops.decode_attention import gqa_walk

L, S = 3, 32


@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("T,heads,width", [
    (4, 4, 128),      # the cell's: 4 rows of 4 heads of 128, a bf16 tile
    (8, 2, 128),      # four heads of 64 stored two to a row, a block of 8
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_call_writes_what_the_scatter_writes(dtype, T, heads, width, layer):
    """Starts at 0, at ``S - T``, past ``S - T`` (clamped), below 0, two
    usual ones and one that is no multiple of ``T`` (a dead slot's stale
    position, floored to its block, which is all that differs from the
    scatter); every position outside the written blocks as it was."""
    keys = jax.random.split(jax.random.key(T * heads + layer), 4)
    start = jnp.asarray([0, S - T, S + 3, -2, 2 * T, 3 * T, T + 1], jnp.int32)
    B = start.shape[0]
    k_cache = jax.random.normal(keys[0], (L, B, S, heads, width), dtype)
    v_cache = jax.random.normal(keys[1], (L, B, S, heads, width), dtype)
    k = jax.random.normal(keys[2], (B, T, heads, width), dtype)
    v = jax.random.normal(keys[3], (B, T, heads, width), dtype)
    got_k, got_v = jax.jit(
        lambda *a: gqa_write_block_rows(*a, interpret=True)
    )(k_cache, v_cache, k, v, jnp.int32(layer), start)
    floored = jnp.clip(start, 0, S - T) // T * T
    assert floored.tolist() == [0, S - T, S - T, 0, 2 * T, 3 * T, T]
    for got, cache, rows in ((got_k, k_cache, k), (got_v, v_cache, v)):
        want = _write_rows(cache, rows, jnp.int32(layer), floored)
        assert got.dtype == cache.dtype and got.shape == cache.shape
        assert np.array_equal(np.asarray(got), np.asarray(want))
        # the scatter's own contract, spelt out: the block, nothing else
        written = np.zeros((L, B, S), bool)
        for b, at in enumerate(floored.tolist()):
            written[layer, b, at:at + T] = True
            assert np.array_equal(
                np.asarray(got[layer, b, at:at + T]), np.asarray(rows[b])
            )
        assert np.array_equal(
            np.asarray(got)[~written], np.asarray(cache)[~written]
        )


@pytest.mark.parametrize("dtype,T,heads,S,whole", [
    (jnp.bfloat16, 4, 4, 2560, True),    # 16 rows: one bf16 tile
    (jnp.float32, 4, 2, 256, True),      # 8 rows: one float32 tile
    (jnp.bfloat16, 4, 2, 256, False),    # half a bf16 tile
    (jnp.bfloat16, 1, 4, 2560, False),   # one row a slot: a quarter
    (jnp.float32, 4, 4, 30, False),      # the blocks do not divide the cache
])
def test_a_block_is_whole_tiles_by_its_rows_and_the_dtype(
    dtype, T, heads, S, whole
):
    cache = jax.ShapeDtypeStruct((2, 3, S, heads, 128), dtype)
    assert a_block_is_whole_tiles(cache, T) == whole
    if not whole:
        # the call refuses what it would have to read before it writes
        rows = jnp.zeros((3, T, heads, 128), dtype)
        zeros = jnp.zeros(cache.shape, dtype)
        with pytest.raises(ValueError, match="no whole stored tiles"):
            gqa_write_block_rows(
                zeros, zeros, rows, rows, jnp.int32(0),
                jnp.zeros((3,), jnp.int32), interpret=True,
            )


@pytest.mark.parametrize("T,block,dtype,calls", [
    (4, 4, jnp.float32, 1),      # a block of whole tiles: the call
    (1, 4, jnp.float32, 0),      # one row a slot of the same model
    (1, 0, jnp.float32, 0),      # one row a slot of any other model
    (4, 4, jnp.bfloat16, 0),     # 8 rows of bf16: a block that is no tile
], ids=["block", "one-row", "no-block", "part-tile"])
def test_attend_over_cache_keeps_the_scatter_for_everything_else(
    T, block, dtype, calls
):
    """And either way the caches come back with the step's rows where
    the scatter puts them."""
    B, Hkv, G, hd, layer = 2, 2, 2, 128, 1
    keys = jax.random.split(jax.random.key(T + block), 5)
    q = jax.random.normal(keys[0], (B, T, Hkv, G, hd), dtype)
    k = jax.random.normal(keys[1], (B, T, Hkv, hd), dtype)
    v = jax.random.normal(keys[2], (B, T, Hkv, hd), dtype)
    buf_k = jax.random.normal(keys[3], (L, B, S, Hkv, hd), dtype)
    buf_v = jax.random.normal(keys[4], (L, B, S, Hkv, hd), dtype)
    start = jnp.asarray([8, 20], jnp.int32)
    with mock.patch.object(
        cache_write, "gqa_write_block_rows",
        wraps=cache_write.gqa_write_block_rows,
    ) as tile_write:
        attn, new_k, new_v = attend_over_cache(
            q, k, v, buf_k, buf_v, jnp.int32(layer), start,
            positions=start[:, None] + jnp.arange(T)[None], mask=None,
            scale=hd ** -0.5, decode_attn_impl="kernel_interpret",
            walk=gqa_walk(start + T, buf_k), block=block,
        )
    assert tile_write.call_count == calls
    assert attn.shape == (B, T, Hkv * G * hd)
    for new, buf, rows in ((new_k, buf_k, k), (new_v, buf_v, v)):
        want = _write_rows(buf, rows, jnp.int32(layer), start)
        assert np.array_equal(np.asarray(new), np.asarray(want))


def test_the_xla_form_never_takes_the_call():
    """``decode_attn_impl == "xla"`` (a mesh, any other platform, a
    prefill of the same model) writes a block by the scatter too."""
    B, T, Hkv, hd = 2, 4, 2, 128
    x = jnp.ones((B, T, Hkv, hd), jnp.float32)
    buf = jnp.zeros((L, B, S, Hkv, hd), jnp.float32)
    start = jnp.asarray([0, 4], jnp.int32)
    mask = jnp.ones((B, T, S), bool)
    with mock.patch.object(cache_write, "gqa_write_block_rows") as tile_write:
        attend_over_cache(
            x[:, :, :, None], x, x, buf, buf, jnp.int32(0), start,
            positions=start[:, None] + jnp.arange(T)[None], mask=mask,
            scale=1.0, decode_attn_impl="xla", block=T,
        )
    assert tile_write.call_count == 0
