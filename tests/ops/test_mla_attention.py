"""The latent's kernels (ops/mla_attention.py) in interpret mode: the
absorbed decode attention against the XLA formulation ``forward`` keeps
beside it, the two writes against ``_write_rows``, and the decompressed
prefill's call against ``_attend`` over the keys built out."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.ops.mla_attention import (
    block_positions,
    mla_decode_attention,
    mla_prefill_attention,
    mla_prefill_takes,
    mla_write_latent_rows,
    mla_write_rope_keys,
)


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


# ---- a decode step's rope keys, written in place (mla_write_rope_keys) ----

ROPE_S = 256     # two lane tiles a slot


def _rope_cache(L, B, S, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.key(L * S + B), 2)
    return (
        jax.random.normal(keys[0], (L, B, S, 64), jnp.float32).astype(dtype),
        jax.random.normal(keys[1], (B, 1, 64), jnp.float32).astype(dtype),
    )


def _the_call_writes_what_write_rows_does(call, cache, rows, starts, layer):
    from gpustack_tpu.models.transformer import _write_rows

    start = jnp.asarray(starts, jnp.int32)
    want = _write_rows(cache, rows, jnp.int32(layer), start)
    got = call(cache, rows[:, 0], jnp.int32(layer), start, interpret=True)
    bits = lambda a: np.asarray(a).view(np.uint16)
    np.testing.assert_array_equal(bits(got), bits(want))
    # and the oracle did write: one row a slot differs from the old cache
    at = np.clip(starts, 0, cache.shape[2] - 1)
    changed = (bits(want) != bits(cache)).any(axis=-1)
    assert changed.sum() == len(starts)
    assert changed[layer, np.arange(len(starts)), at].all()


@pytest.mark.parametrize("layer", [0, 2], ids=["first-layer", "last-layer"])
@pytest.mark.parametrize(
    "starts",
    [
        [0, 0, 0, 0],                     # every slot at the same position
        [127, 128, 1, 254],               # a tile's last lane, the next's first
        [255, 255, 0, 128],               # S_max - 1
        [256, 300, 9000, 2 ** 31 - 1],    # past S_max: clamped to the last
        [-1, -128, -(2 ** 31), 5],        # negative: clamped to the first
        [3, 77, 130, 201],                # all at different ones
    ],
    ids=["same", "tile-edge", "last", "past-the-end", "negative", "different"],
)
def test_the_rope_key_write_is_write_rows_pass_bit_for_bit(starts, layer):
    """The aliased call against ``_write_rows``' pass over the layer, on
    the bits: the step's keys where the pass puts them, and every other
    layer and position of the cache as it was."""
    cache, rows = _rope_cache(3, len(starts), ROPE_S)
    _the_call_writes_what_write_rows_does(
        mla_write_rope_keys, cache, rows, starts, layer
    )


@pytest.mark.parametrize(
    "S,dtype", [(64, jnp.float32), (1536, jnp.bfloat16)],
    ids=["one-short-tile-f32", "twelve-tiles"],
)
def test_the_rope_key_write_takes_the_tests_caches_and_float32(S, dtype):
    from gpustack_tpu.models.transformer import _write_rows

    cache, rows = _rope_cache(2, 3, S, dtype)
    start = jnp.asarray([S - 1, 0, S // 2 + 1], jnp.int32)
    # through _write_rows, as forward calls it
    write = lambda impl: _write_rows(
        cache, rows, jnp.int32(1), start, decode_attn_impl=impl
    )
    np.testing.assert_array_equal(
        np.asarray(write("kernel_interpret"), np.float32),
        np.asarray(write("xla"), np.float32),
    )


# ---- a decode step's latent rows, in place (mla_write_latent_rows) ----

LATENT_S = 64    # four tiles of 16 rows a slot


def _latent_cache(L, B, S, dtype=jnp.bfloat16, rank=256):
    keys = jax.random.split(jax.random.key(L * S + B + rank), 2)
    return (
        jax.random.normal(keys[0], (L, B, S, rank), jnp.float32).astype(dtype),
        jax.random.normal(keys[1], (B, 1, rank), jnp.float32).astype(dtype),
    )


@pytest.mark.parametrize(
    "layer", [0, 1, 2], ids=["first-layer", "middle-layer", "last-layer"]
)
@pytest.mark.parametrize(
    "starts",
    [
        [0, 0, 0, 0],                     # every slot at the same position
        [15, 16, 1, 47],                  # a tile's last row, the next's first
        [63, 63, 0, 32],                  # S_max - 1
        [64, 300, 9000, 2 ** 31 - 1],     # past S_max: clamped to the last
        [-1, -16, -(2 ** 31), 5],         # negative: clamped to the first
        [3, 17, 30, 61],                  # all at different ones
    ],
    ids=["same", "tile-edge", "last", "past-the-end", "negative", "different"],
)
def test_the_latent_row_write_is_write_rows_scatter_bit_for_bit(starts, layer):
    """The aliased call against ``_write_rows``' scatter, on the bits: the
    step's rows where the scatter puts them, and every other layer and
    position of the cache as it was."""
    cache, rows = _latent_cache(3, len(starts), LATENT_S)
    _the_call_writes_what_write_rows_does(
        mla_write_latent_rows, cache, rows, starts, layer
    )


@pytest.mark.parametrize(
    "S,dtype,rank",
    [
        (64, jnp.float32, 128), (256, jnp.float32, 128),
        (1536, jnp.bfloat16, 512), (40, jnp.bfloat16, 128),
    ],
    ids=["four-tiles-f32", "sixteen-tiles-f32", "96-tiles-of-512",
         "no-tile-divides-40"],
)
def test_the_latent_row_write_takes_the_tests_caches_and_float32(
    S, dtype, rank
):
    from gpustack_tpu.models.transformer import _write_rows

    cache, rows = _latent_cache(2, 3, S, dtype, rank)
    start = jnp.asarray([S - 1, 0, S // 2 + 1], jnp.int32)
    # through _write_rows, as forward calls it
    write = lambda impl: _write_rows(
        cache, rows, jnp.int32(1), start, decode_attn_impl=impl
    )
    np.testing.assert_array_equal(
        np.asarray(write("kernel_interpret"), np.float32),
        np.asarray(write("xla"), np.float32),
    )


def _count_calls(monkeypatch, took):
    """Every call of either aliased write appends its name to ``took``."""
    from gpustack_tpu.ops import mla_attention

    for name in ("mla_write_latent_rows", "mla_write_rope_keys"):
        real = getattr(mla_attention, name)
        monkeypatch.setattr(
            mla_attention, name,
            lambda *a, _name=name, _real=real, **kw:
                took.append(_name) or _real(*a, **kw),
        )


def test_only_a_decode_step_s_one_row_a_slot_takes_the_calls(monkeypatch):
    """``_write_rows`` gives a latent cache's arrays to the aliased calls
    at one row a slot under the kernel's attention and at nothing else:
    ``T > 1``, the XLA step and ``by_position`` keep the scatter and the
    pass, as does a cache with its heads."""
    from gpustack_tpu.models.transformer import _write_rows

    took = []
    _count_calls(monkeypatch, took)
    latent, row = _latent_cache(2, 3, LATENT_S)
    keys, key = _rope_cache(2, 3, ROPE_S)
    start = jnp.asarray([5, 0, 33], jnp.int32)
    write = lambda buf, rows, **kw: _write_rows(
        buf, rows, jnp.int32(1), start, **kw
    )
    write(latent, row, decode_attn_impl="kernel_interpret")
    write(keys, key, decode_attn_impl="kernel_interpret")
    assert took == ["mla_write_latent_rows", "mla_write_rope_keys"]
    two = lambda rows: jnp.concatenate([rows, rows], axis=1)
    write(latent, row, decode_attn_impl="xla")
    write(keys, key)
    write(latent, two(row), decode_attn_impl="kernel_interpret")
    write(keys, two(key), decode_attn_impl="kernel_interpret")
    write(latent, row, by_position=True, decode_attn_impl="kernel_interpret")
    write(latent[:, :, :, None], row[:, :, None])     # [L, B, S, 1, width]
    assert len(took) == 2


MLA_HF = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "axk1",
    "vocab_size": 264, "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 128,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 64, "v_head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "max_position_embeddings": 512, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_group": 2, "topk_group": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "none",
    "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
}


def test_a_decode_step_of_a_two_layer_model_writes_the_xla_step_s_cache(
    monkeypatch,
):
    """One decode step of a two-layer A.X-K1 through ``forward``, the
    kernels' step (attention and both arrays' writes, the latent's rows
    and the rope keys, interpret mode) against the XLA step: of either
    array the first layer's rows and every position the step does not
    write on the bits, the second layer's rows (behind the
    first's attention, whose two forms round apart) and the logits to
    float32's rounding."""
    import dataclasses

    from gpustack_tpu.models.config import config_from_hf
    from gpustack_tpu.models.transformer import KVCache, forward, init_params

    cfg = dataclasses.replace(
        config_from_hf(MLA_HF, "tiny-axk1"), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    B, S = 3, 256
    keys = jax.random.split(jax.random.key(4), 3)
    empty = KVCache.create(cfg, B, S)
    cache = KVCache(
        k=jax.random.normal(keys[0], empty.k.shape, empty.k.dtype),
        v=jax.random.normal(keys[1], empty.v.shape, empty.v.dtype),
    )
    toks = jax.random.randint(keys[2], (B, 1), 0, cfg.vocab_size)
    pos = jnp.asarray([[127], [128], [255]], jnp.int32)
    took = []
    _count_calls(monkeypatch, took)
    want, want_cache = forward(
        params, cfg, toks, pos, cache, decode_attn_impl="xla"
    )
    assert took == []
    got, got_cache = forward(
        params, cfg, toks, pos, cache, decode_attn_impl="kernel_interpret"
    )
    # the latent's array (128 wide here, whole lane tiles as A.X-K1's
    # 512) and the rope keys', each through its call in both layers
    assert sorted(took) == (
        ["mla_write_latent_rows"] * 2 + ["mla_write_rope_keys"] * 2
    )
    written = np.zeros((2, B, S), bool)
    written[:, np.arange(B), np.asarray(pos[:, 0])] = True
    for name in ("k", "v"):
        new, ref, old = (
            np.asarray(getattr(c, name)) for c in (got_cache, want_cache, cache)
        )
        np.testing.assert_array_equal(new[0], ref[0])
        np.testing.assert_array_equal(new[~written], old[~written])
        assert (new[written] != old[written]).any(axis=(-1, -2)).all()
        np.testing.assert_allclose(new, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
    )


# ---- the decompressed prefill's call ----

# (heads, nope, rope, value): A.X-K1's widths (two heads' queries are three
# lane tiles) and another latent's (four heads' are five; a value of two)
AXK1_WIDTHS, OTHER_WIDTHS = (4, 128, 64, 128), (4, 128, 32, 256)


def _prefill_tiles(T: int):
    """Every (block_q, block_k) the rule offers a group of one at ``T``
    rows padded to 128s."""
    from gpustack_tpu.ops.flash_attention import candidate_tiles

    rows = -(-T // 128) * 128
    return [(t.block_q, t.block_k) for t in candidate_tiles(rows, rows, 1)]


def _prefill_operands(widths, B, T, dtype):
    from gpustack_tpu.models.transformer import _inv_freq, rope_sin_cos

    H, nope, rope, vd = widths
    keys = jax.random.split(jax.random.key(T + rope), 4)
    draw = lambda key, *shape: jax.random.normal(
        key, shape, jnp.float32
    ).astype(dtype)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return (
        draw(keys[0], B, T, H, nope + rope), draw(keys[1], B, T, H * nope),
        draw(keys[2], B, T, rope), draw(keys[3], B, T, H * vd),
        *rope_sin_cos(positions, _inv_freq(10000.0, rope)),
    )


def _attend_over_built_out_keys(widths, q, k_nope, k_pe, v, sin, cos, scale):
    """The parent's form: the query's rope part rotated outside, the keys
    built out to ``nope + rope`` a head, ``_attend`` under a causal mask,
    all in float32."""
    from gpustack_tpu.models.transformer import (
        _attend,
        apply_rope_interleaved,
    )

    H, nope, rope, vd = widths
    B, T = q.shape[:2]
    q = jnp.concatenate(
        [q[..., :nope], apply_rope_interleaved(q[..., nope:], sin, cos)], -1
    ).astype(jnp.float32)
    k = jnp.concatenate([
        k_nope.reshape(B, T, H, nope),
        jnp.broadcast_to(k_pe[:, :, None], (B, T, H, rope)),
    ], -1).astype(jnp.float32)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    return _attend(
        q[:, :, :, None, :], k,
        v.reshape(B, T, H, vd).astype(jnp.float32), mask, scale,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize(
    "widths,T,blocks",
    [
        (widths, T, blocks)
        for widths in (AXK1_WIDTHS, OTHER_WIDTHS)
        for T in (256, 200)         # a multiple of 128, and not
        for blocks in _prefill_tiles(T)
    ] + [
        # the tile the cell's buckets take, 1,024 rows against 2,048 keys,
        # and the one whose k-block is the shorter (two heads: three lane
        # tiles of queries, both places of a head in them)
        ((2, 128, 64, 128), 2048, None),
        ((2, 128, 64, 128), 2048, (1024, 1024)),
    ],
    ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x),
)
def test_the_prefill_call_is_attend_over_the_keys_built_out(
    widths, T, blocks, dtype
):
    """``mla_prefill_attention`` on operands as the projections make
    them against ``_attend`` over keys of ``nope + rope`` a head: every
    tile the rule offers, rows that are no multiple of 128 (padded and
    masked), two rows of a batch, the query's rotation done inside."""
    operands = _prefill_operands(widths, 2 if T < 2048 else 1, T, dtype)
    assert mla_prefill_takes(*widths)
    got = mla_prefill_attention(
        operands[0].reshape(*operands[0].shape[:2], -1), *operands[1:],
        0.07, interpret=True, _blocks=blocks,
    )
    want = _attend_over_built_out_keys(widths, *operands, 0.07)
    assert got.shape == want.shape and got.dtype == dtype
    # bfloat16: the result's own rounding, and the rotation's (one
    # rounding inside the call, one an operation outside on the CPU)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("widths", [
    (64, 128, 64, 192),     # a value that is no whole lane tile
    (64, 96, 64, 128),      # nor the no-position part
    (64, 128, 48, 128),     # rope parts that fill no lane tile
    (3, 128, 64, 128),      # an odd head left over
])
def test_widths_that_are_no_whole_lane_tiles_are_not_taken(widths):
    H, nope, rope, vd = widths
    assert not mla_prefill_takes(*widths)
    with pytest.raises(ValueError, match="lane tiles"):
        mla_prefill_attention(
            jnp.zeros((1, 128, H * (nope + rope))),
            jnp.zeros((1, 128, H * nope)), jnp.zeros((1, 128, rope)),
            jnp.zeros((1, 128, H * vd)), jnp.zeros((1, 128, rope // 2)),
            jnp.zeros((1, 128, rope // 2)), 0.1, interpret=True,
        )


@pytest.mark.parametrize("widths,takes", [
    ((128, 64, 128), True),     # A.X-K1's: the call of the latent's own
    ((16, 64, 16), False),      # the flash call over the keys built out
])
def test_a_two_layer_model_s_flash_prefill_is_its_xla_prefill(
    monkeypatch, widths, takes
):
    """``forward(..., attn_impl="flash_interpret")`` of a two-layer
    A.X-K1 from position 0 into a cache of its own length against
    ``attn_impl="xla"``, to the tolerance
    ``tests/models/test_transformer.py::test_prefill_flash_matches_xla``
    holds a GQA model to; at widths that are whole lane tiles through
    :func:`mla_prefill_attention`, once a layer, at others not."""
    import dataclasses

    from gpustack_tpu.models.config import config_from_hf
    from gpustack_tpu.models.transformer import KVCache, forward, init_params
    from gpustack_tpu.ops import mla_attention

    nope, rope, vd = widths
    cfg = dataclasses.replace(config_from_hf({
        **MLA_HF, "qk_nope_head_dim": nope, "qk_rope_head_dim": rope,
        "v_head_dim": vd,
    }, "tiny-axk1"), dtype="float32")
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    B, T = 1, 160   # no multiple of 128: the padding is masked
    toks = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    took = []
    real = mla_attention.mla_prefill_attention
    monkeypatch.setattr(
        mla_attention, "mla_prefill_attention",
        lambda *a, **kw: took.append(kw) or real(*a, **kw),
    )
    want, want_cache = forward(
        params, cfg, toks, positions, KVCache.create(cfg, B, T)
    )
    assert took == []
    got, got_cache = forward(
        params, cfg, toks, positions, KVCache.create(cfg, B, T),
        attn_impl="flash_interpret",
    )
    assert took == ([{"interpret": True}] * 2 if takes else [])
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0.1, atol=0.12
    )
    # in float32 the two forms are far closer than that tolerance
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3
    )
    # the first layer's rows precede its attention
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got_cache, name)[0]),
            np.asarray(getattr(want_cache, name)[0]),
        )
