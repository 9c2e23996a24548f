"""The seam between what a step shares and what a layer does
(docs/MODELS.md): a layer kind is a module-level function of (its input,
its leaves, the carried cache, where it lies in its store, the Step),
and ``forward`` and ``forward_hybrid`` run under one Step, made once by
``transformer.make_step``. One layer of every kind the tree has, at
widths the CPU runs in a second."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models import delta, hybrid, transformer
from gpustack_tpu.models.config import config_from_hf
from gpustack_tpu.models.transformer import (
    KVCache,
    after_mixer,
    forward,
    gqa_attention,
    head,
    init_params,
    make_step,
    mla_attention,
    model_norm,
    window_attention,
)
from gpustack_tpu.testing import sdar_small

T = 8
QWEN3 = {
    "architectures": ["Qwen3ForCausalLM"], "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 1,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 264, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
}
NEMOTRON = {
    "architectures": ["NemotronHForCausalLM"],
    "model_type": "nemotron_h", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 264, "num_hidden_layers": 1, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4,
    "chunk_size": 8, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
}
# kind -> (the file, the generation's file or None, the layer's function,
# the stack its mixer's leaves lie in beside ``layers``)
KINDS = {
    "gqa": (QWEN3, None, gqa_attention, None),
    "moe": ({
        **QWEN3, "architectures": ["Qwen3MoeForCausalLM"],
        "moe_intermediate_size": 32, "num_experts": 4,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
    }, None, gqa_attention, None),
    "mla": ({
        "architectures": ["DeepseekV2ForCausalLM"], "hidden_size": 32,
        "num_attention_heads": 4, "vocab_size": 64, "num_hidden_layers": 1,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8,
    }, None, mla_attention, None),
    "window": ({
        "architectures": ["Cohere2MoeForCausalLM"],
        "model_type": "cohere2_moe", "hidden_size": 64,
        "intermediate_size": 32, "num_hidden_layers": 1,
        "layer_types": ["sliding_attention"], "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 264,
        "sliding_window": 4, "num_experts": 4, "num_experts_per_tok": 2,
        "num_shared_experts": 2, "layer_norm_eps": 1e-5, "rope_theta": 50000,
        "logit_scale": 1, "tie_word_embeddings": True,
        "norm_topk_prob": True, "expert_selection_fn": "sigmoid",
        "use_parallel_block": True, "first_k_dense_replace": 0,
        "shared_expert_combination_strategy": "average",
        "position_embedding_type": "rope_gptj",
    }, None, window_attention, None),
    "delta": ({
        "architectures": ["OlmoHybridForCausalLM"],
        "model_type": "olmo_hybrid", "vocab_size": 264, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "layer_types": ["linear_attention"],
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 6, "linear_value_head_dim": 12,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }, None, delta.delta_mixer, "delta_layers"),
    "mamba": ({
        "architectures": ["GraniteMoeHybridForCausalLM"],
        "model_type": "granitemoehybrid", "vocab_size": 264,
        "hidden_size": 64, "intermediate_size": 128,
        "shared_intermediate_size": 128, "num_hidden_layers": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "hidden_act": "silu", "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "layer_types": ["mamba"], "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "num_local_experts": 0,
        "num_experts_per_tok": 0, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
        "logits_scaling": 8,
    }, None, hybrid.mamba_layer, "ssm_layers"),
    **{
        "nemotron-" + kind: (
            {**NEMOTRON, "hybrid_override_pattern": kind}, None, mixer,
            hybrid.STACKS[kind],
        ) for kind, mixer in (
            ("M", hybrid.mamba_mixer), ("E", hybrid.experts_layer),
            ("*", hybrid.attention_layer),
        )
    },
    "diffusion": (
        {**sdar_small.HF, "num_hidden_layers": 1}, sdar_small.GENERATION,
        gqa_attention, None,
    ),
}


def model(kind):
    hf, generation, mixer, stack = KINDS[kind]
    cfg = dataclasses.replace(
        config_from_hf(hf, "tiny-" + kind, generation), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (1, T), 0, cfg.vocab_size)
    return cfg, params, toks, jnp.arange(T, dtype=jnp.int32)[None]


def one_layer_alone(kind, cfg, params, toks, pos, cache):
    """What ``forward`` does for a stack of one layer, written out from
    the pieces it is made of: ``(logits, cache)``."""
    _, _, mixer, stack = KINDS[kind]
    step, x = make_step(params, cfg, toks, pos, cache)
    first = lambda tree: jax.tree.map(lambda a: a[0], tree)
    if kind.startswith("nemotron"):
        # one mixer a layer: its norm, the mixer, the residual add, and
        # the rows a state-space mixer leaves placed by its caller
        lp = first(params[stack])
        h = transformer.rms_norm(x, lp["norm"], cfg.rms_norm_eps)
        if kind == "nemotron-E":
            out, *_ = mixer(h, lp, jnp.int32(0), step)
        elif kind == "nemotron-*":
            out, cache = mixer(h, lp, cache, jnp.int32(0), step)
        else:
            out, cache, kept = mixer(h, lp, cache, jnp.int32(0), step)
            if cache is not None:
                cache = dataclasses.replace(cache, conv=kept[None])
        return head(x + out, params, cfg), cache
    lp = first(params["layers"])
    if stack:
        lp.update(first(params[stack]))
    at = jnp.int32(0)
    if kind == "window":
        at = (at, (True, 0))          # a sliding layer, its period's first
    if kind == "mla" and cache is not None:
        # the latent's cache rides a scan without its one head
        cache = KVCache(k=cache.k[:, :, :, 0], v=cache.v[:, :, :, 0])
    h = model_norm(x, lp["attn_norm"], cfg)
    out, cache = mixer(h, lp, cache, at, step)
    (x, cache, _), _ = after_mixer(
        (x, cache, jnp.int32(0)), h, out, cache, lp, step, cfg.is_moe
    )
    if kind == "mla" and cache is not None:
        cache = KVCache(k=cache.k[:, :, :, None], v=cache.v[:, :, :, None])
    return head(x, params, cfg), cache


@pytest.mark.parametrize("cached", [False, True], ids=["alone", "cache"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_layer_s_function_alone_is_a_one_layer_forward(kind, cached):
    cfg, params, toks, pos = model(kind)
    cache = KVCache.create(cfg, 1, T) if cached else None
    want = forward(params, cfg, toks, pos, cache)
    got = one_layer_alone(kind, cfg, params, toks, pos, cache)
    assert float(jnp.abs(want[0]).max()) > 1e-3
    for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(tuple(want)), strict=True
    ):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", KINDS)
def test_a_program_s_choices_are_made_once_by_the_one_maker(
    kind, monkeypatch
):
    """``forward`` makes one Step whatever the model, the layers run
    under that object (``forward_hybrid`` too, which makes none of its
    own), and each chooser is asked at most once a program."""
    cfg, params, toks, pos = model(kind)
    calls = {}

    def counted(module, name):
        real = getattr(module, name)

        def spy(*args, **kwargs):
            calls.setdefault(name, []).append(real(*args, **kwargs))
            return calls[name][-1]

        monkeypatch.setattr(module, name, spy)

    counted(transformer, "make_step")
    counted(transformer, "moe_dispatch")
    counted(transformer, "decode_attention_impl")
    counted(hybrid, "ssm_update_impl")
    seen = []
    for module, name in (
        (transformer, "after_mixer"), (hybrid, "mamba_mixer"),
        (hybrid, "experts_layer"), (hybrid, "attention_layer"),
    ):
        real = getattr(module, name)

        def spy(*args, real=real):
            seen.extend(a for a in args if isinstance(a, transformer.Step))
            return real(*args)

        monkeypatch.setattr(module, name, spy)
    forward(params, cfg, toks, pos, KVCache.create(cfg, 1, T))
    (made,) = calls["make_step"]
    assert seen and all(step is made[0] for step in seen)
    assert len(calls.get("moe_dispatch", ())) == int(cfg.is_moe)
    assert len(calls["decode_attention_impl"]) == 1
    assert len(calls.get("ssm_update_impl", ())) == int(bool(cfg.state_mixer))
    assert made[0].moe_dispatch_impl == (
        calls["moe_dispatch"][0] if cfg.is_moe else None
    )
    assert made[0].decode_attn_impl == calls["decode_attention_impl"][0]
