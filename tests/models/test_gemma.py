"""Gemma-2/3 family: HF bit-parity through the config mapping and
safetensors loader — exercises (1+w) RMSNorm, scaled embeddings,
sandwich norms, gelu-tanh MLP, query_pre_attn_scalar scaling, attention
and final logit softcapping (gemma2), alternating sliding/full layers,
and dual rope thetas + qk-norm (gemma3)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models import KVCache, forward
from gpustack_tpu.models.config import ModelConfig, config_from_hf


def _load_ours(model_dir):
    from gpustack_tpu.engine.weights import load_hf_checkpoint
    from gpustack_tpu.models.config import load_hf_config

    cfg = load_hf_config(model_dir)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = load_hf_checkpoint(cfg, model_dir)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16
        else x,
        params,
    )
    return cfg, params


def _compare(model, cfg, params):
    torch = pytest.importorskip("torch")
    tokens = np.array([[3, 17, 92, 5, 44, 8, 120, 63]], dtype=np.int32)
    with torch.no_grad():
        ref = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    ours, _ = forward(
        params,
        cfg,
        jnp.asarray(tokens),
        jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        ),
    )
    # bf16 loader rounding bounds parity (see test_qwen3.py); a wrong
    # norm convention / mask schedule / softcap produces O(0.1+) errors
    np.testing.assert_allclose(np.asarray(ours), ref, atol=6e-3, rtol=3e-2)


@pytest.fixture(scope="module")
def gemma2_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(0)
    hf_cfg = tfm.Gemma2Config(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=4,       # sliding/full alternation
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        query_pre_attn_scalar=8,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        sliding_window=4,          # < seq len so the window matters
        max_position_embeddings=128,
        rope_theta=10000.0,
        attention_dropout=0.0,
    )
    model = tfm.Gemma2ForCausalLM(hf_cfg).eval()
    d = tmp_path_factory.mktemp("gemma2")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


@pytest.fixture(scope="module")
def gemma3_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(0)
    hf_cfg = tfm.Gemma3TextConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=6,       # 5 local + 1 global pattern
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        query_pre_attn_scalar=8,
        sliding_window=4,
        max_position_embeddings=128,
        rope_theta=1000000.0,
        rope_local_base_freq=10000.0,
        attention_dropout=0.0,
    )
    model = tfm.Gemma3ForCausalLM(hf_cfg).eval()
    d = tmp_path_factory.mktemp("gemma3")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


@pytest.fixture(scope="module")
def gemma1_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(0)
    hf_cfg = tfm.GemmaConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        max_position_embeddings=128,
        rope_theta=10000.0,
        attention_dropout=0.0,
        hidden_act="gelu",   # original hub configs; weights use tanh gelu
    )
    model = tfm.GemmaForCausalLM(hf_cfg).eval()
    d = tmp_path_factory.mktemp("gemma1")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def test_gemma1_logits_match_transformers(gemma1_checkpoint):
    """Gemma1 (GemmaForCausalLM) takes the (1+w)-norm + sqrt(d)
    embed-scale path WITHOUT gemma2's post-norms/softcaps — silently
    loading it llama-style produces wrong logits (round-2 advisor)."""
    model, model_dir = gemma1_checkpoint
    cfg, params = _load_ours(model_dir)
    assert cfg.norm_delta_gain
    assert cfg.embed_multiplier == math.sqrt(cfg.hidden_size)
    assert not cfg.post_norms
    assert cfg.attn_logit_softcap == 0.0
    assert cfg.hidden_act == "gelu_tanh"
    assert cfg.layer_sliding is None
    _compare(model, cfg, params)


def test_gemma2_logits_match_transformers(gemma2_checkpoint):
    model, model_dir = gemma2_checkpoint
    cfg, params = _load_ours(model_dir)
    assert cfg.post_norms and cfg.norm_delta_gain
    assert cfg.embed_multiplier == math.sqrt(cfg.hidden_size)
    assert cfg.attn_logit_softcap == 50.0
    assert cfg.final_logit_softcap == 30.0
    assert cfg.layer_sliding == (True, False, True, False)
    assert cfg.hidden_act == "gelu_tanh"
    assert not cfg.qk_norm
    _compare(model, cfg, params)


def test_gemma3_logits_match_transformers(gemma3_checkpoint):
    model, model_dir = gemma3_checkpoint
    cfg, params = _load_ours(model_dir)
    assert cfg.qk_norm and cfg.post_norms and cfg.norm_delta_gain
    assert cfg.rope_local_theta == 10000.0
    assert cfg.layer_sliding is not None and cfg.layer_sliding[-1] is False
    assert sum(cfg.layer_sliding) == 5
    _compare(model, cfg, params)


def test_gemma_prefill_decode_parity(gemma3_checkpoint):
    """Engine invariant under alternating masks + dual rope: prefill +
    decode over the cache == full causal forward."""
    _, model_dir = gemma3_checkpoint
    cfg, params = _load_ours(model_dir)
    B, T = 1, 12
    toks = jax.random.randint(
        jax.random.key(1), (B, T), 0, cfg.vocab_size, dtype=jnp.int32
    )
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    full, _ = forward(params, cfg, toks, pos)

    split = 8
    cache = KVCache.create(cfg, B, 32)
    pre, cache = forward(
        params, cfg, toks[:, :split], pos[:, :split], cache
    )
    np.testing.assert_allclose(
        np.asarray(pre), np.asarray(full[:, :split]), atol=1e-4, rtol=1e-3
    )
    for t in range(split, T):
        step, cache = forward(
            params, cfg, toks[:, t : t + 1], pos[:, t : t + 1], cache
        )
        np.testing.assert_allclose(
            np.asarray(step[:, 0]), np.asarray(full[:, t]),
            atol=1e-4, rtol=1e-3,
        )


def test_gemma_param_count_matches_init():
    from gpustack_tpu.models import init_params

    cfg = ModelConfig(
        name="tiny-gemma",
        vocab_size=64,
        hidden_size=32,
        intermediate_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=8,
        hidden_act="gelu_tanh",
        norm_delta_gain=True,
        embed_multiplier=math.sqrt(32),
        post_norms=True,
        qk_norm=True,
        tie_word_embeddings=True,
        sliding_window=4,
        layer_sliding=(True, False),
        max_position_embeddings=64,
    ).validate()
    params = init_params(cfg, jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.param_count()
