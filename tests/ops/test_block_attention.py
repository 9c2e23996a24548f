"""The two attention kernels under the mask of generation by diffusion
over blocks (a query sees the keys up to the last position of its own
block), in interpret mode against the einsum under the written mask: the
flash prefill kernel with its static ``block``, through every tile and
from an offset of whole blocks, and the decode kernel with a block's
``L`` rows folded into its group of query heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.transformer import (
    KVCache,
    _attend,
    attend_over_cache,
    block_rows_as_heads,
)
from gpustack_tpu.ops import flash_attention as fa
from gpustack_tpu.ops.decode_attention import gqa_decode_attention, gqa_walk


def rows(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def masked(q, k, v, q_pos, block, scale):
    """``_attend`` under the block mask, ``q [B, T, Hq, d]``."""
    B, T, Hq, d = q.shape
    Hkv = k.shape[2]
    sees = q_pos - q_pos % block + block - 1
    mask = jnp.arange(k.shape[1])[None, None, :] <= sees[:, :, None]
    return _attend(
        q.reshape(B, T, Hkv, Hq // Hkv, d), k, v, mask, scale
    )


@pytest.mark.parametrize("block", [4, 8, 32])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256), None])
def test_flash_prefill_sees_to_the_end_of_a_row_s_block(block, blocks):
    T, Hq, Hkv, d = 512, 4, 2, 128
    q, k, v = rows(0, 1, T, Hq, d), rows(1, 1, T, Hkv, d), rows(2, 1, T, Hkv, d)
    scale = d ** -0.5
    qt, kt, vt = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
    got = fa.flash_call(
        qt, kt, vt, jnp.zeros((1,), jnp.int32), scale=scale, seq_k=T,
        interpret=True, block=block,
        **({} if blocks is None else {"_blocks": blocks}),
    )
    got = jnp.transpose(got, (0, 2, 1, 3)).reshape(1, T, Hq * d)
    want = masked(q, k, v, jnp.arange(T)[None], block, scale)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    causal = fa.flash_attention_prefill(q, k, v, scale, interpret=True)
    assert float(jnp.max(jnp.abs(causal - want))) > 1e-2


@pytest.mark.parametrize("offset,T,S", [(0, 200, 200), (128, 128, 300), (36, 92, 128)])
def test_flash_prefill_with_a_block_from_an_offset_of_whole_blocks(
    offset, T, S
):
    """Lengths that are no whole tiles (the padding is masked by
    ``seq_k``) and queries that begin a whole number of blocks in."""
    Hq, Hkv, d, block = 4, 2, 128, 4
    q, k, v = rows(3, 1, T, Hq, d), rows(4, 1, S, Hkv, d), rows(5, 1, S, Hkv, d)
    scale = d ** -0.5
    got = fa.flash_attention_prefill(
        q, k, v, scale, interpret=True, q_offset=offset, block=block
    )
    want = masked(q, k, v, offset + jnp.arange(T)[None], block, scale)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


@pytest.mark.parametrize("block,window", [(3, 0), (256, 0), (4, 64)])
def test_a_block_that_straddles_a_tile_or_comes_with_a_window_is_refused(
    block, window
):
    q = rows(6, 1, 128, 2, 128)
    with pytest.raises(ValueError, match="block of"):
        fa.flash_attention_prefill(
            q, q, q, 1.0, interpret=True, block=block, window=window
        )


def test_a_block_s_rows_fold_into_the_group_and_back():
    x = rows(7, 3, 4, 2, 5, 8)          # [B, T, Hkv, G, hd]
    folded = block_rows_as_heads(x)
    assert folded.shape == (3, 2 * 4 * 5, 8)
    # a kv head's T x G rows together, the kernel's head h of kv head
    # h // (Hq / Hkv)
    assert jnp.array_equal(folded[:, :20].reshape(3, 4, 5, 8), x[:, :, 0])
    back = block_rows_as_heads(folded.reshape(3, -1), back=(4, 2))
    assert jnp.array_equal(back, x.reshape(3, 4, -1))


@pytest.mark.parametrize("starts", [(0, 8, 124, 60), (252, 0, 4, 128)])
def test_the_decode_kernel_takes_a_block_s_rows_as_more_query_heads(starts):
    """Four slots at different block starts (one of them dead), L = 4
    rows a slot, 2 kv heads of 2 query heads: the kernel's result at
    ``lengths = start + L`` is the einsum's under the block mask."""
    B, S, L, Hkv, G, d, layers = 4, 256, 4, 2, 2, 128, 2
    k_cache = rows(8, layers, B, S, Hkv, d)
    v_cache = rows(9, layers, B, S, Hkv, d)
    q = rows(10, B, L, Hkv, G, d)
    start = jnp.asarray(starts, jnp.int32)
    live = jnp.asarray([True, True, False, True])
    lengths = jnp.where(live, start + L, 0)
    scale = d ** -0.5
    got = gqa_decode_attention(
        block_rows_as_heads(q), k_cache, v_cache, jnp.int32(1),
        gqa_walk(lengths, k_cache), scale, interpret=True,
    )
    got = block_rows_as_heads(got, back=(L, Hkv))
    positions = start[:, None] + jnp.arange(L)[None]
    want = masked(
        q.reshape(B, L, Hkv * G, d), k_cache[1], v_cache[1], positions, L,
        scale,
    )
    for b in range(B):
        if live[b]:
            assert float(jnp.max(jnp.abs(got[b] - want[b]))) < 2e-5, b
        else:
            assert not np.asarray(got[b]).any()


def test_attend_over_cache_writes_the_block_then_attends_it():
    """The layer's own path: the step's four rows go into the cache at
    the block's start and are among the keys, by the kernel and by the
    einsum alike."""
    B, S, L, Hkv, G, d = 2, 128, 4, 2, 2, 128
    buf_k, buf_v = rows(11, 1, B, S, Hkv, d), rows(12, 1, B, S, Hkv, d)
    q = rows(13, B, L, Hkv, G, d)
    k, v = rows(14, B, L, Hkv, d), rows(15, B, L, Hkv, d)
    start = jnp.asarray([16, 100], jnp.int32)
    positions = start[:, None] + jnp.arange(L)[None]
    sees = positions - positions % L + L - 1
    mask = jnp.arange(S)[None, None, :] <= sees[:, :, None]
    scale = d ** -0.5
    outs = {}
    for impl in ("xla", "kernel_interpret"):
        outs[impl] = attend_over_cache(
            q, k, v, buf_k, buf_v, jnp.int32(0), start,
            positions=positions, mask=mask, scale=scale,
            decode_attn_impl=impl, block=L,
            walk=gqa_walk(start + L, buf_k),
        )
    for a, b in zip(outs["xla"], outs["kernel_interpret"]):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5
    new_k = outs["xla"][1]
    assert jnp.array_equal(new_k[0, 1, 100:104], k[1])
    assert jnp.array_equal(new_k[0, 0, :16], buf_k[0, 0, :16])
