"""What a kernel's call needs, from its shapes alone: operations and
bytes for the flash prefill kernel and the decode step, and the least
time a chip with the given peaks could take. Kept with the benchmark so
that no later PR can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_weight_params(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matrix parameters of one transformer layer, by part."""
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = h * cfg["num_attention_heads"] * hd
    kv = h * cfg["num_key_value_heads"] * hd
    attn = 2 * q + 2 * kv          # q, o and k, v projections
    experts = cfg.get("num_experts") or 0
    if experts:
        per_expert = 3 * h * cfg["moe_intermediate_size"]
        return {
            "attention": attn, "router": h * experts,
            "expert": per_expert, "experts": experts,
            "experts_per_token": cfg["num_experts_per_tok"],
        }
    return {"attention": attn, "mlp": 3 * h * cfg["intermediate_size"]}


def expected_distinct_experts(experts: int, per_token: int, tokens: float) -> float:
    """Experts touched by ``tokens`` tokens that each pick ``per_token``
    of ``experts`` uniformly: E * (1 - (1 - k/E)^tokens)."""
    return experts * (1.0 - (1.0 - per_token / experts) ** max(0.0, tokens))


def decode_weight_bytes(
    cfg: Dict[str, Any], bytes_per_weight: float, active_tokens: float
) -> float:
    """Weight bytes one decode step has to read: every layer's attention
    and MLP matrices once and the output head once (the embedding is read
    a row per token, which is left out). For sparse experts, the experts
    that ``active_tokens`` tokens are expected to touch. The cache's
    bytes are not counted (they depend on the live context, which the
    benchmark does not see step by step), so a share of the roofline
    computed from this is a floor."""
    lw = layer_weight_params(cfg)
    per_layer = lw["attention"]
    if "expert" in lw:
        per_layer += lw["router"] + lw["expert"] * expected_distinct_experts(
            lw["experts"], lw["experts_per_token"], active_tokens
        )
    else:
        per_layer += lw["mlp"]
    head = cfg["vocab_size"] * cfg["hidden_size"]
    return bytes_per_weight * (cfg["num_hidden_layers"] * per_layer + head)


def flash_prefill_call(
    t: int, heads: int, kv_heads: int, head_dim: int,
    bytes_per_element: float = 2.0,
) -> Dict[str, float]:
    """One call of the causal flash prefill kernel over ``t`` tokens (one
    layer): the operations of the lower triangle only (QK^T and PV, two
    operations a multiply-add) and q, k, v read and o written once."""
    pairs = t * (t + 1) / 2.0
    flops = 4.0 * pairs * head_dim * heads
    elements = 2.0 * t * heads * head_dim + 2.0 * t * kv_heads * head_dim
    return {"flops": flops, "bytes": elements * bytes_per_element}


def least_seconds(
    flops: float, bytes_: float, peaks: Dict[str, float],
    flops_key: str = "bf16_flops_per_s",
) -> Dict[str, Any]:
    """The larger of operations over peak and bytes over peak, and which
    of the two binds."""
    by_flops = flops / peaks[flops_key]
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(by_flops, by_bytes),
        "bound": "compute" if by_flops >= by_bytes else "memory",
    }
