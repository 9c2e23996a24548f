"""A small SDAR-MoE for the tests: the family's file at widths the CPU
runs in seconds, heads of 128 so that the decode kernel takes its cache
(interpret mode), float32 so that a comparison with
``reference_sdar.py`` reads rounding of the last bit and not bf16's."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

HF: Dict[str, Any] = {
    "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 128, "intermediate_size": 256,
    "max_position_embeddings": 4096, "mlp_only_layers": [],
    "moe_intermediate_size": 128, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 512,
}
MASK_ID = 500
GENERATION: Dict[str, Any] = {
    "block_length": 4, "denoising_steps": 4,
    "remasking_strategy": "sequential", "confidence_threshold": 0.9,
    "mask_token_id": MASK_ID,
}


def small(quantized: bool = False, **generation) -> Tuple[Any, Any]:
    """``(cfg, params)``: seeded random weights (key 0), float32;
    ``generation`` over :data:`GENERATION`'s keys; ``quantized``: the
    tree in the repo's int8 weight-only scheme, as a deployment's."""
    import jax
    import jax.numpy as jnp

    from gpustack_tpu.models.config import config_from_hf
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import init_params

    cfg = dataclasses.replace(
        config_from_hf(HF, "tiny-sdar", {**GENERATION, **generation}),
        dtype="float32",
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    return cfg, quantize_params(params) if quantized else params
