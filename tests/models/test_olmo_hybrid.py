"""Olmo-Hybrid (a mixer by kind and an MLP in every layer: gated delta
rule or full attention): the engine's model code against the plain
float32 reference (``perfbench/reference/olmo_hybrid.py``) at a small size
with widths that are no tile's (2 periods, 4 heads, key width 6, value
width 12), and what ``ModelConfig`` says of the catalog's row."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import FAMILIES, config_from_hf
from gpustack_tpu.models.quant import QuantW, quantize_params
from gpustack_tpu.models.transformer import KVCache, forward, init_params
from gpustack_tpu.ops.delta_rule import state_heads
from perfbench.reference import olmo_hybrid as ref

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HF = {
    "architectures": ["OlmoHybridForCausalLM"], "model_type": "olmo_hybrid",
    "vocab_size": 264, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 256, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 6, "linear_value_head_dim": 12,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
# 18 heads of 8 on 18 key-value heads: more than a sublane tile of bf16
# and no whole number of them, so the cache holds 32 (kv_heads_stored)
HF_18 = {
    **HF, "hidden_size": 144, "num_attention_heads": 18,
    "num_key_value_heads": 18,
}
T = 21


def model(hf=HF, int8=False):
    """Float32 activations either way (the CPU's bf16 products accumulate
    in bf16: no model's rounding); ``int8``: the matrices quantized."""
    cfg = dataclasses.replace(
        config_from_hf(hf, "tiny-olmo-hybrid"), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    # gains that are not ones, so that a norm in the wrong place shows
    for stack, name in (
        ("layers", "attn_norm"), ("layers", "mlp_norm"),
        ("attn_layers", "q_norm"), ("attn_layers", "k_norm"),
        ("delta_layers", "o_norm"),
    ):
        w = params[stack][name]
        params[stack][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.key(len(name)), w.shape, w.dtype
        )
    return cfg, quantize_params(params) if int8 else params


def tokens(n=T):
    return jax.random.randint(jax.random.key(1), (1, n), 0, HF["vocab_size"])


def test_the_config_reads_the_kinds_and_says_the_state_s_shape_once():
    cfg = config_from_hf(HF)
    assert "OlmoHybrid" in FAMILIES
    assert cfg.layer_types == tuple(HF["layer_types"])
    assert cfg.mixer_period == (
        "linear_attention", "linear_attention", "linear_attention",
        "full_attention",
    )
    assert (cfg.num_linear_layers, cfg.num_kv_layers) == (6, 2)
    assert cfg.state_mixer == "delta" and not cfg.rope
    assert cfg.qk_norm_whole and cfg.norm_after == ("full_attention",)
    assert cfg.linear_allow_neg_eigval
    # the state a slot: [Dk, H * Dv] float32 and 3 rows of q | k | v
    assert cfg.linear_conv_dim == 2 * 4 * 6 + 4 * 12
    assert cfg.state_shapes == (6, (6, 48), (3 * 96,))
    assert cfg.state_bytes_per_slot(16) == 6 * (6 * 48 * 4 + 3 * 96 * 2)
    assert cfg.kv_cache_bytes_per_token(16) == 2 * 2 * 4 * 16 * 2
    cache = KVCache.create(dataclasses.replace(cfg, dtype="float32"), 3, 32)
    assert cache.k.shape == (2, 3, 32, 4, 16)
    assert cache.ssm.shape == (6, 3, 6, 48) and cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (6, 3, 3 * 96)
    assert cache.memory()["state_bytes"] == 3 * 6 * (6 * 48 * 4 + 3 * 96 * 4)
    assert cfg.beside_rows.keeps == "has linear-attention layers"


def catalog_row():
    with open(CATALOG) as f:
        return next(
            row for row in map(json.loads, f) if row["name"] == "Olmo-Hybrid-7B"
        )


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalog_s_row_counts_7_43_b_and_a_slot_of_54_7_mb():
    """``param_count`` of the catalog's config = the sum written down
    from its widths; the state is stored with no padded lane."""
    hf = {**catalog_row()["config"], "architectures": ["OlmoHybridForCausalLM"]}
    cfg = config_from_hf(hf)
    d, f, v = 3840, 11008, 100352
    linear = (
        2 * d * 2880 + 2 * d * 5760 + 5760 * d + 2 * d * 30 + 2 * 30
        + 4 * 11520 + 192
    )
    full = 4 * d * d + 2 * d
    mlp = 3 * d * f + 2 * d
    total = 24 * linear + 8 * full + 32 * mlp + 2 * v * d + d
    assert cfg.param_count() == total
    assert round(total / 1e9, 2) == 7.43
    assert round(linear / 1e6, 1) == 88.8 and round(mlp / 1e6, 1) == 126.8
    assert round((full + mlp) / 1e6, 1) == 185.8
    assert cfg.state_shapes == (24, (96, 5760), (3 * 11520,))
    assert cfg.state_bytes_per_slot(16) == 24 * (2_211_840 + 3 * 11520 * 2)
    assert round(cfg.state_bytes_per_slot(16) / 1e6, 1) == 54.7
    # 30 kv heads are stored as 32 (two whole sublane tiles of bf16): at
    # 30 the chip stores the rows transposed and the decode program
    # copies both caches every step (ModelConfig.kv_heads_stored)
    assert (cfg.num_kv_heads, cfg.kv_heads_stored) == (30, 32)
    assert cfg.kv_cache_bytes_per_token(16) == 8 * 16_384 == 128 * 1024
    # 12 slots: 0.657 GB of state (a head's [96, 192] padded to [96, 256]
    # would be 0.87)
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, 12, 2560))
    assert shapes.ssm.shape == (24, 12, 96, 5760)
    assert shapes.k.shape == shapes.v.shape == (8, 12, 2560, 32, 128)
    assert shapes.ssm.shape[-1] % 128 == 0 and shapes.ssm.shape[-2] % 8 == 0
    state = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in (shapes.ssm, shapes.conv)
    )
    assert round(state / 1e9, 3) == 0.657
    assert cfg.beside_bytes_per_slot(2560, 16) * 12 == state


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_benchmark_s_file_is_the_catalog_s_row_and_names_its_family():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    with open(os.path.join(
        root, "perfbench", "configs", "olmo-hybrid-7b-int8", "config.json"
    )) as f:
        ours = json.load(f)
    for key, value in catalog_row()["config"].items():
        assert ours[key] == value, key
    assert ours["architectures"] == ["OlmoHybridForCausalLM"]


@pytest.mark.parametrize(
    "key,value,names",
    [
        ("layer_types", ["linear_attention", "sliding_attention"] * 4,
         "sliding_attention"),
        ("num_hidden_layers", 7, "layer_types has 8 layers"),
        ("hidden_act", "gelu", "hidden_act"),
        ("attention_bias", True, "attention_bias"),
    ],
    ids=["another_kind", "a_short_list", "another_activation", "a_bias"],
)
def test_what_the_family_s_reader_does_not_serve_is_refused(key, value, names):
    with pytest.raises(ValueError, match=names):
        config_from_hf({**HF, key: value})


def test_the_state_s_layers_draw_a_decay_that_neither_dies_nor_stays():
    _, params = model()
    stack = params["delta_layers"]
    A = np.exp(np.asarray(stack["A_log"]))
    assert (A > 0).all() and (A <= 16).all()
    dt = np.log1p(np.exp(np.asarray(stack["dt_bias"])))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert stack["conv_w"].dtype == stack["A_log"].dtype == jnp.float32
    assert stack["conv_w"].shape == (6, 4, 96)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_the_full_forward_is_the_reference_s(jitted):
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
    """Each is a way the engine's code could be wrong; the sound
    reference is the engine's (above), so a fault that moved nothing
    would be one the comparison cannot see."""
    _, params = model()
    toks = tokens()[0].tolist()
    sound, _ = ref.forward(params, HF, toks, list(range(T)))
    pads = (13, 11) if fault == "state_after_bucket" else None
    got, readings = ref.forward(
        params, HF, toks, list(range(T)), fault=fault, pads=pads,
        states=jnp.ones((6, 4, 6, 12)),
    )
    if fault == "bf16_state":
        assert readings["state_narrow"] == 1.0
    assert float(jnp.max(jnp.abs(got - sound))) > 0.05


def test_the_other_reading_of_each_assumption_is_an_argument():
    _, params = model()
    toks = tokens()[0].tolist()
    sound, _ = ref.forward(params, HF, toks, [T - 1])
    for other in (
        dict(norm_after=()), dict(norm_after=("linear_attention",)),
        dict(rotary=True),
    ):
        got, _ = ref.forward(params, HF, toks, [T - 1], **other)
        assert float(jnp.max(jnp.abs(got - sound))) > 1e-3, other


def test_the_int8_tree_is_read_alike_by_the_program_and_the_reference():
    cfg, params = model(int8=True)
    for stack, names in (
        ("layers", ("w_gate", "w_up", "w_down")),
        ("attn_layers", ("wq", "wk", "wv", "wo")),
        ("delta_layers", ("wq", "wk", "wv", "wg", "wo")),
    ):
        for name in names:
            assert isinstance(params[stack][name], QuantW), (stack, name)
    assert isinstance(params["embed"], QuantW)
    assert isinstance(params["lm_head"], QuantW)
    for name in ("wa", "wb", "conv_w", "A_log", "dt_bias", "o_norm"):
        assert not isinstance(params["delta_layers"][name], QuantW), name
    toks = tokens()
    logits, _ = forward(
        params, cfg, toks, jnp.arange(T, dtype=jnp.int32)[None]
    )
    want, _ = ref.forward(params, HF, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("update", ["xla", "kernel_interpret"])
@pytest.mark.parametrize(
    "n,bucket,hf",
    [(13, 16, HF), (17, 32, HF), (64, 64, HF), (70, 128, HF),
     (17, 32, HF_18)],
    ids=["13_of_16", "17_of_32", "a_whole_chunk", "past_a_chunk",
         "18_heads_stored_as_32"],
)
def test_a_padded_prefill_then_decode_through_the_state(
    n, bucket, hf, update
):
    """Logits, not tokens: the prefill's one kept row, then every decode
    step with the slot between two dead ones, against the reference's
    full forward; and the state a head at a time after the last step."""
    cfg, params = model(hf)
    if hf is HF_18:
        assert (cfg.num_kv_heads, cfg.kv_heads_stored) == (18, 32)
    steps = 4
    toks = tokens(n + steps)
    want, _ = ref.forward(
        params, hf, toks[0].tolist(), list(range(n - 1, n + steps))
    )
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :n].set(toks[:, :n])
    got, cache = forward(
        params, cfg, padded, jnp.arange(bucket, dtype=jnp.int32)[None],
        KVCache.create(cfg, 1, bucket), true_len=jnp.array([n]),
        logits_at=jnp.array([n - 1]),
    )
    np.testing.assert_allclose(got[0, 0], want[0], rtol=3e-4, atol=3e-4)
    assert cache.k.shape[-2] == cfg.kv_heads_stored
    state = KVCache.create(cfg, 3, 160).with_slot(
        1, cache.k[:, 0], cache.v[:, 0], cache.slot_share()
    )
    # a dead slot's state is left as it is
    state = dataclasses.replace(state, ssm=state.ssm.at[:, 2].set(7.0))
    live = jnp.array([False, True, False])
    for i in range(steps):
        tok = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(toks[0, n + i])
        pos = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(n + i)
        got, state = forward(
            params, cfg, tok, pos, state, ssm_impl=update, live=live
        )
        np.testing.assert_allclose(
            got[1, 0], want[i + 1], rtol=3e-4, atol=3e-4
        )
    if update == "kernel_interpret":
        np.testing.assert_array_equal(state.ssm[:, 2], 7.0)
    _, readings = ref.forward(
        params, hf, toks[0].tolist(), [n],
        states=state_heads(state.ssm[:, 1], 4),
    )
    assert readings["state_err"] < 1e-4
    assert readings["state_narrow"] < 0.01


def test_a_continuation_goes_on_from_the_cache_s_state():
    """Several rows a slot over a cache (the chunked form from a carried
    state): what an ingest runs."""
    cfg, params = model()
    toks = tokens(30)
    pos = jnp.arange(30, dtype=jnp.int32)[None]
    want, _ = forward(params, cfg, toks, pos)
    cache = KVCache.create(cfg, 1, 32)
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
