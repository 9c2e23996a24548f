"""Granite 4.0-H (a Mamba-2 mixer or attention by kind, then a gated MLP,
in every layer; four scalar multipliers; heads of 64 stored two to a
row): the engine's model code against the plain float32 reference
(``perfbench/reference/granite_hybrid.py``) at a small size of the real
kinds (two periods ``MM*M``, 32 Mamba heads of 16 = 2 x hidden with a
state of 16 in one group, 4 query heads on 2 kv heads of 64 = hidden / heads), and what
``ModelConfig`` says of the catalog's row."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import FAMILIES, config_from_hf
from gpustack_tpu.models.quant import QuantW, quantize_params
from gpustack_tpu.models.transformer import (
    KVCache,
    _attend,
    attend_over_cache,
    decode_attention_impl,
    forward,
    init_params,
)
from gpustack_tpu.ops.decode_attention import gqa_walk
from perfbench.reference import granite_hybrid as ref

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HF = {
    "architectures": ["GraniteMoeHybridForCausalLM"],
    "model_type": "granitemoehybrid",
    "vocab_size": 264, "hidden_size": 256, "intermediate_size": 128,
    "shared_intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "hidden_act": "silu", "max_position_embeddings": 512,
    "attention_bias": False, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "position_embedding_type": "nope",
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "mamba_n_heads": 32, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8,
}
T = 21


def model(hf=HF, int8=False):
    """Float32 activations either way (the CPU's bf16 products accumulate
    in bf16: no model's rounding); ``int8``: the matrices quantized."""
    cfg = dataclasses.replace(
        config_from_hf(hf, "tiny-granite-hybrid"), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    # gains and a bias that are not ones and zeros, so that a norm in the
    # wrong place or a bias left out shows
    for stack, name in (
        ("layers", "attn_norm"), ("layers", "mlp_norm"),
        ("ssm_layers", "gate_norm"), ("ssm_layers", "conv_b"),
        ("ssm_layers", "D"),
    ):
        w = params[stack][name]
        params[stack][name] = w + 0.3 * jax.random.normal(
            jax.random.key(len(name)), w.shape, w.dtype
        )
    return cfg, quantize_params(params) if int8 else params


def tokens(n=T):
    return jax.random.randint(jax.random.key(1), (1, n), 0, HF["vocab_size"])


def test_the_config_reads_the_kinds_the_multipliers_and_the_state_s_shape():
    cfg = config_from_hf(HF)
    assert "GraniteMoeHybrid" in FAMILIES
    assert cfg.layer_types == (
        "mamba", "mamba", "full_attention", "mamba"
    ) * 2
    assert cfg.mixer_period == cfg.layer_types[:4]
    assert (cfg.num_mamba_layers, cfg.num_kv_layers) == (6, 2)
    assert cfg.num_linear_layers == 0 and cfg.layers_of("M") == 0
    assert cfg.state_mixer == "ssm" and not cfg.rope
    assert cfg.beside_rows.keeps == "has state-space layers"
    # the four multipliers
    assert cfg.embed_multiplier == 12.0 and cfg.residual_multiplier == 0.22
    assert cfg.logit_scale == 0.125
    assert cfg.query_pre_attn_scalar == 4096.0     # scores * 1 / 64
    assert cfg.intermediate_size == 128 and cfg.tie_word_embeddings
    assert cfg.ssm_chunk_size == 16 and cfg.mamba_n_groups == 1
    # the state a slot: [H, P, N] float32 and 3 rows of xBC
    assert cfg.mamba_conv_dim == 512 + 2 * 16
    assert cfg.state_shapes == (6, (32, 16, 16), (3 * 544,))
    assert cfg.state_bytes_per_slot(16) == 6 * (32 * 16 * 16 * 4 + 3 * 544 * 2)
    # two heads of 64 a stored row of 128: the same bytes
    assert cfg.head_dim == 64 and cfg.kv_heads_a_row == 2
    assert cfg.kv_row_shapes == ((1, 128), (1, 128))
    assert cfg.kv_cache_bytes_per_token(16) == 2 * 2 * 2 * 64 * 2
    cache = KVCache.create(cfg, 3, 32)
    assert cache.k.shape == cache.v.shape == (2, 3, 32, 1, 128)
    assert cache.ssm.shape == (6, 3, 32, 16, 16)
    assert cache.ssm.dtype == jnp.float32 and cache.conv.dtype == jnp.bfloat16
    assert cache.conv.shape == (6, 3, 3 * 544)


def test_a_head_of_64_lies_alone_on_its_row_where_a_mesh_could_divide_it():
    """Only a model that is served on one device whatever is asked stores
    two heads a row; a stack of attention alone keeps a head a row (and
    the XLA form over it)."""
    dense = config_from_hf({
        "architectures": ["LlamaForCausalLM"], "vocab_size": 264,
        "hidden_size": 256, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2,
    })
    assert dense.head_dim == 64 and dense.kv_heads_a_row == 1
    assert dense.kv_row_shapes == ((2, 64), (2, 64))
    assert decode_attention_impl(dense, 1, 2048, "tpu", None) == "xla"
    cfg = config_from_hf(HF)
    assert decode_attention_impl(cfg, 1, 2048, "tpu", None) == "kernel"
    assert decode_attention_impl(cfg, 1, 2048, "cpu", None) == "xla"
    assert decode_attention_impl(cfg, 4, 2048, "tpu", None) == "xla"


def catalog_row():
    with open(CATALOG) as f:
        return next(
            row for row in map(json.loads, f)
            if row["name"] == "granite-4.0-h-micro"
        )


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalog_s_row_counts_3_19_b_and_a_slot_of_76_4_mb():
    """``param_count`` of the catalog's config = the sum written down
    from its widths."""
    hf = {
        **catalog_row()["config"],
        "architectures": ["GraniteMoeHybridForCausalLM"],
    }
    cfg = config_from_hf(hf)
    assert cfg.num_layers == 40
    assert (cfg.num_mamba_layers, cfg.num_kv_layers) == (36, 4)
    assert cfg.mixer_period == ("mamba",) * 5 + ("full_attention",) + (
        "mamba",
    ) * 4
    d, v = 2048, 100352
    mamba = (
        d * (4096 + 4352 + 64) + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * d
    )
    attn = 2 * d * d + 2 * d * 512
    mlp = 3 * d * 8192 + 2 * d
    total = 36 * mamba + 4 * attn + 40 * mlp + v * d + d
    assert cfg.param_count() == total
    assert round(total / 1e9, 2) == 3.19
    assert round((mamba + mlp) / 1e6, 1) == 76.2
    assert round((attn + mlp) / 1e6, 1) == 60.8
    assert cfg.state_shapes == (36, (64, 64, 128), (3 * 4352,))
    assert cfg.state_shapes[2] == (13056,)
    assert round(cfg.state_bytes_per_slot(16) / 1e6, 1) == 76.4
    assert cfg.kv_row_shapes == ((4, 128), (4, 128))
    assert cfg.kv_cache_bytes_per_token(16) == 4 * 2 * 512 * 2 == 8192
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, 64, 2048))
    assert shapes.ssm.shape == (36, 64, 64, 64, 128)
    assert shapes.conv.shape == (36, 64, 13056)
    assert shapes.k.shape == shapes.v.shape == (4, 64, 2048, 4, 128)
    state = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in (shapes.ssm, shapes.conv)
    )
    assert round(state / 1e9, 2) == 4.89
    assert cfg.beside_bytes_per_slot(2048, 16) * 64 == state


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_benchmark_s_file_is_the_catalog_s_row_and_names_its_family():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    with open(os.path.join(
        root, "perfbench", "configs", "granite-4.0-h-micro-int8",
        "config.json",
    )) as f:
        ours = json.load(f)
    for key, value in catalog_row()["config"].items():
        assert ours[key] == value, key
    assert ours["architectures"] == ["GraniteMoeHybridForCausalLM"]


@pytest.mark.parametrize(
    "key,value,names",
    [
        ("num_local_experts", 64, "num_local_experts"),
        ("position_embedding_type", "rope", "position_embedding_type"),
        ("layer_types", ["mamba", "sliding_attention"] * 4,
         "sliding_attention"),
        ("num_hidden_layers", 7, "layer_types has 8 layers"),
        ("hidden_act", "gelu", "hidden_act"),
        ("mamba_n_heads", 30, "mamba_expand"),
    ],
    ids=["routed_experts", "a_rotary_embedding", "another_kind",
         "a_short_list", "another_activation", "heads_that_do_not_expand"],
)
def test_what_the_family_s_reader_does_not_serve_is_refused(key, value, names):
    with pytest.raises(ValueError, match=names):
        config_from_hf({**HF, key: value})


def test_the_state_s_layers_draw_a_decay_that_neither_dies_nor_stays():
    cfg = config_from_hf(HF)
    stack = init_params(cfg, jax.random.key(0))["ssm_layers"]
    A = np.exp(np.asarray(stack["A_log"]))
    assert (A >= 1).all() and (A <= 16).all()
    dt = np.log1p(np.exp(np.asarray(stack["dt_bias"])))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert stack["conv_w"].dtype == stack["A_log"].dtype == jnp.float32
    assert stack["conv_w"].shape == (6, 4, 544)
    assert "norm" not in stack       # the layer's norms are the block's


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_the_full_forward_is_the_reference_s(jitted):
    """Float32 on both sides, the chunked scan against the recurrence a
    position at a time: 2e-4 is the products' order of summation."""
    cfg, params = model()
    toks = tokens()
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    run = lambda p, t, q: forward(p, cfg, t, q)  # noqa: E731
    if jitted:
        run = jax.jit(run)
    logits, _ = run(params, toks, pos)
    want, _ = ref.forward(params, HF, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_fault_the_reference_can_make_moves_its_logits(fault):
    """Each is a way the engine's code could be wrong (each of the four
    multipliers read as 1 among them); the sound reference is the
    engine's (above), so a fault that moved nothing would be one the
    comparison cannot see. 0.02 is 100 times the tolerance above (the
    logits here are 0.24 at most)."""
    _, params = model()
    toks = tokens()[0].tolist()
    sound, _ = ref.forward(params, HF, toks, list(range(T)))
    pads = (13, 11) if fault == "state_after_bucket" else None
    got, readings = ref.forward(
        params, HF, toks, list(range(T)), fault=fault, pads=pads,
        states=jnp.ones((6, 32, 16, 16)),
    )
    if fault == "bf16_state":
        # over 21 positions it moves the logits by what the order of a
        # sum does (2e-4): what the state is kept in shows it, not they
        assert readings["state_narrow"] == 1.0
        return
    assert float(jnp.max(jnp.abs(got - sound))) > 0.02


@pytest.mark.parametrize(
    "field,value",
    [("embed_multiplier", 1.0), ("residual_multiplier", 1.0),
     ("logit_scale", 1.0), ("query_pre_attn_scalar", 0.0)],
)
def test_each_multiplier_of_the_program_matters(field, value):
    """The program with one multiplier at its default is not the
    reference's model: each is read, and used."""
    cfg, params = model()
    toks = tokens()
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    want, _ = ref.forward(params, HF, toks[0].tolist(), list(range(T)))
    got, _ = forward(
        params, dataclasses.replace(cfg, **{field: value}), toks, pos
    )
    assert float(jnp.max(jnp.abs(got[0] - want))) > 0.02


def test_the_int8_tree_is_read_alike_by_the_program_and_the_reference():
    cfg, params = model(int8=True)
    for stack, names in (
        ("layers", ("w_gate", "w_up", "w_down")),
        ("attn_layers", ("wq", "wk", "wv", "wo")),
        ("ssm_layers", ("w_in", "w_out")),
    ):
        for name in names:
            assert isinstance(params[stack][name], QuantW), (stack, name)
    # a tied head reads the embedding's rows as they are
    assert not isinstance(params["embed"], QuantW) and "lm_head" not in params
    for name in ("conv_w", "conv_b", "A_log", "dt_bias", "D", "gate_norm"):
        assert not isinstance(params["ssm_layers"][name], QuantW), name
    toks = tokens()
    logits, _ = forward(
        params, cfg, toks, jnp.arange(T, dtype=jnp.int32)[None]
    )
    want, _ = ref.forward(params, HF, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize(
    "update,attends",
    [("xla", "xla"), ("kernel_interpret", "kernel_interpret")],
    ids=["xla", "kernels"],
)
@pytest.mark.parametrize(
    "n,bucket",
    [(13, 16), (17, 32), (64, 64), (70, 128)],
    ids=["13_of_16", "17_of_32", "whole_chunks", "past_a_chunk"],
)
def test_a_padded_prefill_then_decode_through_the_state(
    n, bucket, update, attends
):
    """Logits, not tokens: the prefill's one kept row, then every decode
    step with the slot between two dead ones, against the reference's
    full forward at every decoded position; and the state a head at a
    time after the last step. 3e-4: float32 both sides, another order of
    summation (chunks, the kernel's blocks)."""
    cfg, params = model()
    steps = 4
    toks = tokens(n + steps)
    want, _ = ref.forward(
        params, HF, toks[0].tolist(), list(range(n - 1, n + steps))
    )
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :n].set(toks[:, :n])
    got, cache = forward(
        params, cfg, padded, jnp.arange(bucket, dtype=jnp.int32)[None],
        KVCache.create(cfg, 1, bucket, jnp.float32), true_len=jnp.array([n]),
        logits_at=jnp.array([n - 1]),
    )
    np.testing.assert_allclose(got[0, 0], want[0], rtol=3e-4, atol=3e-4)
    assert cache.k.shape[-2:] == (1, 128)
    state = KVCache.create(cfg, 3, 256, jnp.float32).with_slot(
        1, cache.k[:, 0], cache.v[:, 0], cache.slot_share()
    )
    # a dead slot's state is left as it is
    state = dataclasses.replace(state, ssm=state.ssm.at[:, 2].set(7.0))
    live = jnp.array([False, True, False])
    for i in range(steps):
        tok = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(toks[0, n + i])
        pos = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(n + i)
        got, state = forward(
            params, cfg, tok, pos, state, ssm_impl=update, live=live,
            decode_attn_impl=attends,
        )
        np.testing.assert_allclose(
            got[1, 0], want[i + 1], rtol=3e-4, atol=3e-4
        )
    if update == "kernel_interpret":
        np.testing.assert_array_equal(state.ssm[:, 2], 7.0)
    _, readings = ref.forward(
        params, HF, toks[0].tolist(), [n], states=state.ssm[:, 1],
    )
    assert readings["state_err"] < 1e-4
    # float32: a state kept in bf16 would read 1.0 here
    assert readings["state_narrow"] < 0.01


def test_a_padded_bucket_is_the_unpadded_prompt():
    cfg, params = model()
    n, bucket = 19, 32
    toks = tokens(n)
    pos = jnp.arange(bucket, dtype=jnp.int32)[None]
    padded = jnp.full((1, bucket), 9, jnp.int32).at[:, :n].set(toks)
    a, ca = forward(
        params, cfg, padded, pos, KVCache.create(cfg, 1, bucket, jnp.float32),
        true_len=jnp.array([n]), logits_at=jnp.array([n - 1]),
    )
    b, cb = forward(
        params, cfg, toks, pos[:, :n], KVCache.create(cfg, 1, n, jnp.float32),
        logits_at=jnp.array([n - 1]),
    )
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ca.ssm, cb.ssm, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ca.conv, cb.conv, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        ca.k[:, :, :n], cb.k, rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rows_of_two_heads_through_the_kernel_are_a_plain_gqa_einsum(dtype):
    """8 query heads on 4 kv heads of 64, stored two to a row of 128:
    the decode kernel (interpret mode) over the stored rows against
    ``_attend`` over the heads as the projections made them, slots of
    several lengths and a dead one."""
    B, S, Hq, Hkv, hd, L = 4, 256, 8, 4, 64, 3
    ks = jax.random.split(jax.random.key(3), 5)
    rows_k = jax.random.normal(ks[0], (B, S, Hkv, hd), jnp.float32)
    rows_v = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, 1, Hq, hd), jnp.float32).astype(dtype)
    k1 = jax.random.normal(ks[3], (B, 1, Hkv, hd), jnp.float32).astype(dtype)
    v1 = jax.random.normal(ks[4], (B, 1, Hkv, hd), jnp.float32).astype(dtype)
    lengths = jnp.array([200, 1, 0, 131], jnp.int32)
    start = jnp.maximum(lengths - 1, 0)
    layer = jnp.int32(1)
    buf = lambda rows: jnp.zeros(  # noqa: E731
        (L, B, S, Hkv // 2, 2 * hd), dtype
    ).at[layer].set(rows.astype(dtype).reshape(B, S, Hkv // 2, 2 * hd))
    buf_k, buf_v = buf(rows_k), buf(rows_v)
    positions = start[:, None]
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    scale = 1.0 / 64
    got, new_k, new_v = attend_over_cache(
        q, k1, v1, buf_k, buf_v, layer, start, positions=positions,
        mask=mask, scale=scale, decode_attn_impl="kernel_interpret",
        walk=gqa_walk(lengths, buf_k),
    )
    plain, xla_k, _ = attend_over_cache(
        q, k1, v1, buf_k, buf_v, layer, start, positions=positions,
        mask=mask, scale=scale, decode_attn_impl="xla",
    )
    np.testing.assert_array_equal(new_k, xla_k)
    # the plain einsum over the heads as the projections made them: the
    # stored rows are those heads' bytes in their order
    all_k = new_k[layer].reshape(B, S, Hkv, hd)
    all_v = new_v[layer].reshape(B, S, Hkv, hd)
    np.testing.assert_array_equal(all_k[jnp.arange(B), start], k1[:, 0])
    np.testing.assert_array_equal(all_v[jnp.arange(B), start], v1[:, 0])
    want = _attend(
        q.reshape(B, 1, Hkv, Hq // Hkv, hd), all_k, all_v, mask, scale
    )
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        np.asarray(plain, np.float32)[live], np.asarray(want, np.float32)[live],
        rtol=tol, atol=tol,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        rtol=tol, atol=tol,
    )
    # a slot nobody holds reads nothing and gives zeros
    assert not np.asarray(got, np.float32)[~live].any()


def test_a_continuation_goes_on_from_the_cache_s_state():
    """Several rows a slot over a cache (the chunked form from a carried
    state): what an ingest runs."""
    cfg, params = model()
    toks = tokens(30)
    pos = jnp.arange(30, dtype=jnp.int32)[None]
    want, _ = forward(params, cfg, toks, pos)
    cache = KVCache.create(cfg, 1, 32, jnp.float32)
    first, cache = forward(params, cfg, toks[:, :19], pos[:, :19], cache)
    rest, cache = forward(params, cfg, toks[:, 19:], pos[:, 19:], cache)
    np.testing.assert_allclose(first[0], want[0, :19], rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(rest[0], want[0, 19:], rtol=3e-4, atol=3e-4)


def test_a_state_is_not_sharded():
    cfg, params = model()
    with pytest.raises(ValueError, match="recurrent state is not sharded"):
        forward(
            params, cfg, tokens(8), jnp.arange(8, dtype=jnp.int32)[None],
            KVCache.create(cfg, 1, 8), attn_impl="ring",
        )
