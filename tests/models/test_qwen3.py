"""Qwen3 family (QK-norm attention): HF parity + engine invariants.

Bit-level parity against the installed ``transformers`` Qwen3
implementation on a tiny random checkpoint exercises the whole path:
config_from_hf mapping → safetensors loader → qk-norm forward.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models import KVCache, forward, init_params
from gpustack_tpu.models.config import config_from_hf, get_config


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(0)
    hf_cfg = tfm.Qwen3Config(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attention_dropout=0.0,
    )
    model = tfm.Qwen3ForCausalLM(hf_cfg).eval()
    d = tmp_path_factory.mktemp("qwen3")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def test_qwen3_logits_match_transformers(hf_checkpoint):
    torch = pytest.importorskip("torch")
    model, model_dir = hf_checkpoint

    from gpustack_tpu.engine.weights import load_hf_checkpoint
    from gpustack_tpu.models.config import load_hf_config

    cfg = load_hf_config(model_dir)
    assert cfg.qk_norm, "Qwen3ForCausalLM must map to qk_norm=True"
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype="float32")
    params = load_hf_checkpoint(cfg, model_dir)
    # loader emits bf16; parity needs fp32
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16
        else x,
        params,
    )

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
    # loader stores weights in bf16 (engine serving dtype) — parity is
    # bounded by bf16 weight rounding (~1e-3 abs on tiny logits), far
    # below what a wrong qk-norm/RoPE would produce (O(0.1+))
    np.testing.assert_allclose(np.asarray(ours), ref, atol=5e-3, rtol=2e-2)


def test_qwen3_prefill_decode_parity():
    """Engine invariant: prefill + decode steps == full forward, with
    qk_norm on (the tiny-qwen3 preset)."""
    cfg = get_config("tiny-qwen3")
    params = init_params(cfg, jax.random.key(0))
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
        np.asarray(pre), np.asarray(full[:, :split]), atol=3e-2, rtol=3e-2
    )
    for t in range(split, T):
        step, cache = forward(
            params, cfg, toks[:, t : t + 1], pos[:, t : t + 1], cache
        )
        np.testing.assert_allclose(
            np.asarray(step[:, 0]),
            np.asarray(full[:, t]),
            atol=3e-2,
            rtol=3e-2,
        )


def test_config_from_hf_qwen3():
    hf = {
        "architectures": ["Qwen3ForCausalLM"],
        "hidden_size": 4096,
        "intermediate_size": 12288,
        "num_hidden_layers": 36,
        "num_attention_heads": 32,
        "num_key_value_heads": 8,
        "head_dim": 128,
        "vocab_size": 151936,
        "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-6,
        "max_position_embeddings": 40960,
    }
    cfg = config_from_hf(hf, "qwen3-8b")
    assert cfg.qk_norm and not cfg.qkv_bias
    from gpustack_tpu.models.config import PRESETS

    assert cfg.param_count() == PRESETS["qwen3-8b"].param_count()
    # ~8.2B params for Qwen3-8B
    assert 8.0e9 < cfg.param_count() < 8.4e9


def test_qwen3_int8_init_matches_tree():
    """init_quantized_params and init_params agree on tree structure for
    qk_norm configs (the ADVICE low-severity class of drift)."""
    from gpustack_tpu.models.quant import init_quantized_params

    cfg = get_config("tiny-qwen3")
    bf16 = init_params(cfg, jax.random.key(0))
    int8 = init_quantized_params(cfg, seed=0)
    assert set(bf16["layers"]) == set(int8["layers"])
    assert int8["layers"]["q_norm"].shape == (cfg.num_layers, cfg.head_dim)


def test_qwen3_int8_checkpoint_load_quantizes_leaf_by_leaf(hf_checkpoint):
    """An int8 load gives the tree quantize_params would give on the
    bf16 load — same QuantW layout, same values — without the loader
    ever returning a bf16 copy of a quantized weight."""
    from gpustack_tpu.engine.weights import load_hf_checkpoint
    from gpustack_tpu.models.config import load_hf_config
    from gpustack_tpu.models.quant import QuantW, quantize_params

    _, model_dir = hf_checkpoint
    cfg = load_hf_config(model_dir)
    ref = quantize_params(load_hf_checkpoint(cfg, model_dir))
    got = load_hf_checkpoint(cfg, model_dir, quantization="int8")
    is_q = lambda x: isinstance(x, QuantW)  # noqa: E731
    assert jax.tree.structure(got, is_leaf=is_q) == jax.tree.structure(
        ref, is_leaf=is_q
    )
    assert type(got["layers"]) is dict   # a plain pytree node again
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert isinstance(got["layers"][name], QuantW), name
    assert isinstance(got["lm_head"], QuantW)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
