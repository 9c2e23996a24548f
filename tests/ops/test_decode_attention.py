"""The GQA decode attention kernel (ops/decode_attention.py) in interpret
mode against the XLA form ``forward`` keeps beside it (``_attend`` over
the layer's slab), alone and inside ``forward``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import get_config
from gpustack_tpu.models.transformer import (
    KVCache,
    _attend,
    forward,
    init_params,
)
from gpustack_tpu.ops.decode_attention import (
    gqa_block_positions,
    gqa_decode_attention,
    slot_walk,
)

HD = 128


def xla(q, k, v, layer, lengths, scale):
    """``_attend`` over positions ``0 .. lengths[b] - 1`` of the layer's
    slab; zeros for a slot of length 0."""
    B, Hq, _ = q.shape
    S, Hkv = k.shape[2:4]
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    out = _attend(
        q.reshape(B, 1, Hkv, Hq // Hkv, HD), k[layer], v[layer], mask, scale
    )[:, 0]
    return jnp.where(lengths[:, None] > 0, out, 0.0)


def operands(S, kv_heads, group, slots, dtype=jnp.float32, layers=3):
    keys = jax.random.split(jax.random.key(S + kv_heads), 3)
    shape = (layers, slots, S, kv_heads, HD)
    return (
        jax.random.normal(keys[0], (slots, kv_heads * group, HD), dtype),
        jax.random.normal(keys[1], shape, dtype),
        jax.random.normal(keys[2], shape, dtype),
    )


@pytest.mark.parametrize(
    "S,kv_heads,group,lengths",
    [
        (64, 4, 8, [1, 64, 18]),               # a cache of one block
        (256, 8, 4, [6, 256, 129, 128, 127]),  # either side of a block's edge
        (256, 4, 8, [255, 1, 2]),              # ragged; a length of 1
        (2048, 8, 4, [2048, 513, 512]),        # four blocks of 512
        (256, 4, 8, [0, 128, 0, 0, 129, 0]),   # nobody holds 0, 2, 3, 5
        (256, 8, 4, [40, 0]),                  # ... or the last
        # Nemotron-3-Nano: 2 kv heads, sixteen query heads a group
        (1024, 2, 16, [1024, 513, 0, 7]),
        (64, 2, 16, [33, 64]),
        (64, 2, 2, [0, 0]),                    # ... or any
    ],
)
def test_kernel_is_the_xla_form(S, kv_heads, group, lengths):
    q, k, v = operands(S, kv_heads, group, len(lengths))
    lengths = jnp.asarray(lengths, jnp.int32)
    for layer in (0, 2):
        got = gqa_decode_attention(
            q, k, v, jnp.int32(layer), lengths, 0.09, interpret=True
        )
        want = xla(q, k, v, layer, lengths, 0.09)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )


def test_in_bf16_the_forms_differ_by_a_rounding():
    """The served dtype: both forms round their weights to bf16 before
    ``P @ V``, the kernel before the division by their sum and the XLA
    form after it, and the XLA form rounds its scores too."""
    q, k, v = operands(256, 4, 8, 3, jnp.bfloat16)
    lengths = jnp.asarray([256, 100, 0], jnp.int32)
    got = gqa_decode_attention(
        q, k, v, jnp.int32(1), lengths, HD ** -0.5, interpret=True
    )
    want = xla(q, k, v, 1, lengths, HD ** -0.5)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2,
    )


def test_rows_at_or_above_a_slot_s_length_do_not_count():
    """Whatever lies at or above the length (a former tenant's rows; all
    of a slot nobody holds) leaves every result as it is, bit for bit."""
    q, k, v = operands(256, 4, 8, 3, layers=1)
    lengths = jnp.asarray([41, 0, 131], jnp.int32)
    clean = gqa_decode_attention(
        q, k, v, jnp.int32(0), lengths, 0.1, interpret=True
    )
    above = (
        jnp.arange(256)[None, :] >= lengths[:, None]
    )[None, :, :, None, None]
    dirty = gqa_decode_attention(
        q, jnp.where(above, 3e4, k), jnp.where(above, -3e4, v),
        jnp.int32(0), lengths, 0.1, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    assert not np.asarray(clean[1]).any()


def test_a_slot_nobody_holds_names_the_block_already_resident():
    """What makes a dead slot cost no copy: its grid points name the
    block the walk over the slots left resident, not one of its own."""
    lengths = jnp.asarray([0, 0, 700, 0, 0, 1, 2049, -1], jnp.int32)
    walk = slot_walk(lengths, 2048, 512)
    assert walk.lengths.tolist() == [0, 0, 700, 0, 0, 1, 2048, 0]
    assert walk.slot.tolist() == [2, 2, 2, 2, 2, 5, 6, 6]
    assert walk.block.tolist() == [0, 0, 1, 1, 1, 0, 3, 3]
    walk = slot_walk(jnp.zeros((3,), jnp.int32), 2048, 512)
    assert (walk.slot.tolist(), walk.block.tolist()) == ([0, 0, 0], [0, 0, 0])


def test_the_block_follows_the_cache_and_the_heads():
    assert gqa_block_positions(2048, 8, 128) == 512
    assert gqa_block_positions(2048, 4, 128) == 512
    assert gqa_block_positions(2048, 32, 128) == 256     # 2 MiB of K
    assert gqa_block_positions(2048, 8, 128, 4) == 512
    assert gqa_block_positions(1280, 8, 128) == 256
    assert gqa_block_positions(64, 8, 128) == 64         # one block
    assert gqa_block_positions(1000, 8, 128) is None     # nothing divides
    assert gqa_block_positions(2048, 8, 64) is None      # half a lane tile
    assert gqa_block_positions(2048, 8, 192) is None


# ---- inside forward -------------------------------------------------------


@pytest.fixture(scope="module")
def small():
    """tiny-qwen3 with heads a lane tile wide (the kernel's condition),
    two query heads a kv head, in float32."""
    cfg = dataclasses.replace(
        get_config("tiny-qwen3"), head_dim=HD, dtype="float32"
    )
    return cfg, init_params(cfg, jax.random.key(0), dtype=jnp.float32)


def decoded(cfg, params, impl, live, steps=6):
    """A prefill of three slots' prompts, then ``steps`` greedy decode
    steps under ``impl``: every step's logits and tokens. Slot 1 holds a
    finished tenant's rows where ``live`` says nobody holds it."""
    slots, T, S = 3, 8, 64
    prompts = jax.random.randint(
        jax.random.key(3), (slots, T), 0, cfg.vocab_size
    )
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (slots, T))
    logits, cache = forward(
        params, cfg, prompts, pos, KVCache.create(cfg, slots, S)
    )
    tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    step = jax.jit(
        lambda tokens, positions, cache: forward(
            params, cfg, tokens[:, None], positions[:, None], cache,
            decode_attn_impl=impl, live=live,
        )
    )
    all_logits, all_tokens = [], []
    for i in range(steps):
        # slots advance apart: 8, 19, 30 positions in, and on
        positions = jnp.asarray([T, T + 11, T + 22], jnp.int32) + i
        logits, cache = step(tokens, positions, cache)
        tokens = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        all_logits.append(np.asarray(logits[:, 0]))
        all_tokens.append(np.asarray(tokens))
    return np.stack(all_logits), np.stack(all_tokens)


@pytest.mark.parametrize(
    "live", [None, [True, False, True]], ids=["all-live", "slot-1-dead"]
)
def test_forward_decodes_the_same_under_either_form(small, live):
    cfg, params = small
    if live is not None:
        live = jnp.asarray(live)
    want_logits, want_tokens = decoded(cfg, params, "xla", live)
    got_logits, got_tokens = decoded(cfg, params, "kernel_interpret", live)
    held = slice(None) if live is None else np.asarray(live)
    np.testing.assert_allclose(
        got_logits[:, held], want_logits[:, held], atol=2e-4, rtol=2e-4
    )
    np.testing.assert_array_equal(got_tokens[:, held], want_tokens[:, held])


def test_a_dead_slot_changes_no_live_slot_s_logits(small):
    """The live slots' logits are the same bits whether or not the slot
    between them is held: it is not read, and nothing of it is mixed in."""
    cfg, params = small
    all_live, _ = decoded(cfg, params, "kernel_interpret", None)
    one_dead, _ = decoded(
        cfg, params, "kernel_interpret", jnp.asarray([True, False, True])
    )
    np.testing.assert_array_equal(all_live[:, [0, 2]], one_dead[:, [0, 2]])
