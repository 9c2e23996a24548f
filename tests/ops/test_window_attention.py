"""Attention under a sliding window in the blocked kernels, in interpret
mode against the einsum with the mask (``_attend``): the flash prefill
kernel with a band (``ops/flash_attention.py``, ``window``), every tile,
with the band's edges inside a block, on a block's edge and past the
sequence; and the decode kernel over a ring of window rows
(``ops/decode_attention.py``: a ring is a short cache, its lengths
``min(length, window)``, the order of its rows nothing to the softmax)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.transformer import _attend
from gpustack_tpu.ops.decode_attention import (
    gqa_block_positions,
    gqa_decode_attention,
)
from gpustack_tpu.ops.flash_attention import (
    candidate_tiles,
    flash_attention_prefill,
    flash_call,
)

HD = 128


def banded(q, k, v, window, off=0):
    """``_attend`` with the causal mask and the band: query ``i`` sees
    key ``j`` iff ``0 <= i - j < window``."""
    B, T, Hq, _ = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    i = off + jnp.arange(T)[:, None]
    j = jnp.arange(S)[None, :]
    mask = jnp.broadcast_to(((j <= i) & (i - j < window))[None], (B, T, S))
    return _attend(
        q.reshape(B, T, Hkv, Hq // Hkv, HD), k, v, mask, HD ** -0.5
    )


def operands(B, T, S, Hq, Hkv, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.key(seed + T + Hq), 3)
    return (
        jax.random.normal(keys[0], (B, T, Hq, HD), dtype),
        jax.random.normal(keys[1], (B, S, Hkv, HD), dtype),
        jax.random.normal(keys[2], (B, S, Hkv, HD), dtype),
    )


@pytest.mark.parametrize(
    "T,Hq,Hkv,window",
    [
        (512, 4, 2, 128),     # the band's edge on a sub-block's edge
        (640, 8, 2, 200),     # inside a sub-block
        (1024, 16, 1, 384),   # sixteen query heads a group, as the model's
        (300, 4, 2, 77),      # T no multiple of 128; a band under a sub-block
        (384, 4, 4, 1),       # a query sees itself alone
        (256, 6, 2, 255),     # all but one key of the longest row
        (256, 4, 2, 256),     # the whole triangle
        (256, 4, 2, 4096),    # a window past the sequence
    ],
)
def test_flash_with_a_band_is_the_einsum_with_the_mask(T, Hq, Hkv, window):
    q, k, v = operands(1, T, T, Hq, Hkv)
    got = flash_attention_prefill(
        q, k, v, HD ** -0.5, interpret=True, window=window
    )
    np.testing.assert_allclose(
        got, banded(q, k, v, window), atol=3e-6, rtol=1e-5
    )


def test_the_band_follows_the_offset_of_a_continuation():
    q, k, v = operands(1, 256, 512, 4, 2)
    got = flash_attention_prefill(
        q, k, v, HD ** -0.5, interpret=True, window=130, q_offset=256
    )
    np.testing.assert_allclose(
        got, banded(q, k, v, 130, off=256), atol=3e-6, rtol=1e-5
    )


@pytest.mark.parametrize(
    "tiles", candidate_tiles(1024, 1024, 4),
    ids=lambda t: f"{t.block_q}x{t.block_k}",
)
def test_every_tile_skips_the_blocks_outside_the_band_and_masks_the_edges(tiles):
    """A band of 300 over 1,024 positions: under every pair of block
    sizes some key blocks lie wholly below a query block's band, some
    straddle its lower edge, some its diagonal."""
    q, k, v = operands(1, 1024, 1024, 8, 2, seed=1)
    qt, kt, vt = (jnp.transpose(x, (0, 2, 1, 3)) for x in (q, k, v))
    out = flash_call(
        qt, kt, vt, jnp.zeros((1,), jnp.int32), scale=HD ** -0.5,
        seq_k=1024, interpret=True, window=300,
        _blocks=(tiles.block_q, tiles.block_k),
    )
    got = jnp.transpose(out, (0, 2, 1, 3)).reshape(1, 1024, -1)
    np.testing.assert_allclose(
        got, banded(q, k, v, 300), atol=3e-6, rtol=1e-5
    )


def test_without_a_window_the_kernel_is_the_one_it_was():
    """``window=0`` traces the causal kernel's own program: nothing of
    the band is in it (the other models' programs are unchanged)."""
    q, k, v = operands(1, 256, 256, 4, 2)

    def lowered(**kw):
        return jax.jit(
            lambda q, k, v: flash_attention_prefill(
                q, k, v, HD ** -0.5, interpret=True, **kw
            )
        ).lower(q, k, v).as_text()

    assert lowered() == lowered(window=0)
    assert lowered() != lowered(window=100)


@pytest.mark.parametrize(
    "window,lengths",
    [
        (128, [1, 127, 128, 129, 400, 0]),   # under, at and over the window
        (512, [513, 2000, 512, 0, 7]),
        (1024, [5000, 1024, 1023]),          # two blocks of 512 a ring
    ],
)
def test_the_decode_kernel_over_a_ring_is_the_einsum_over_the_window(
    window, lengths
):
    """A slot of ``length`` positions has written position ``p`` to row
    ``p mod window``; the kernel walks ``min(length, window)`` rows of
    the ring and gives what the einsum gives over the last ``window``
    positions in their order."""
    slots, Hkv, G, layers = len(lengths), 2, 16, 2
    longest = max(lengths)
    keys = jax.random.split(jax.random.key(window), 3)
    q = jax.random.normal(keys[0], (slots, Hkv * G, HD), jnp.float32)
    k_all, v_all = (
        jax.random.normal(key, (layers, slots, longest, Hkv, HD), jnp.float32)
        for key in keys[1:]
    )
    # the rings as the decode steps leave them, and the windows in order
    ring_k = np.zeros((layers, slots, window, Hkv, HD), np.float32)
    ring_v = np.zeros_like(ring_k)
    want = []
    for b, n in enumerate(lengths):
        for p in range(n):
            ring_k[:, b, p % window] = k_all[:, b, p]
            ring_v[:, b, p % window] = v_all[:, b, p]
    layer = 1
    lens = jnp.asarray(lengths, jnp.int32)
    assert gqa_block_positions(window, Hkv, HD, 4) is not None
    got = gqa_decode_attention(
        q, jnp.asarray(ring_k), jnp.asarray(ring_v), jnp.int32(layer),
        jnp.minimum(lens, window), HD ** -0.5, interpret=True,
        name="gqa_window_decode_attention",
    )
    for b, n in enumerate(lengths):
        if n == 0:
            want.append(jnp.zeros((Hkv * G * HD,), jnp.float32))
            continue
        first = max(0, n - window)
        mask = jnp.ones((1, 1, n - first), bool)
        want.append(_attend(
            q[b].reshape(1, 1, Hkv, G, HD),
            k_all[layer, b, first:n][None], v_all[layer, b, first:n][None],
            mask, HD ** -0.5,
        )[0, 0])
    np.testing.assert_allclose(got, jnp.stack(want), atol=3e-6, rtol=1e-5)
