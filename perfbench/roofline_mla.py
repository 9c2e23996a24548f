"""What the two attention kernels of a latent-attention (MLA) model need,
from their shapes alone: operations and bytes of the flash prefill call
over decompressed heads (keys and values of different widths) and of the
absorbed decode attention over the latent cache. The least time for
them is ``roofline.least_seconds``'s. Kept with the benchmark so that no
later PR can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The widths of a hub ``config.json`` of the DeepSeek-V2/V3 family."""
    return {
        "heads": cfg["num_attention_heads"],
        "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"],
        "rank": cfg["kv_lora_rank"],
        "rope": cfg["qk_rope_head_dim"],
    }


def mla_prefill_call(
    t: int, heads: int, qk: int, v: int, bytes_per_element: float = 2.0
) -> Dict[str, float]:
    """One call of the causal flash kernel over ``t`` tokens of one layer
    with keys ``qk`` and values ``v`` wide: the operations of the lower
    triangle only (QK^T over ``qk``, PV over ``v``, two operations a
    multiply-add), and q, k, v read and o written once, each at its own
    width, one key/value head a query head (decompressed)."""
    pairs = t * (t + 1) / 2.0
    flops = 2.0 * pairs * heads * (qk + v)
    elements = t * heads * (2.0 * qk + 2.0 * v)
    return {"flops": flops, "bytes": elements * bytes_per_element}


def mla_decode_call(
    rows: float, heads: int, rank: int, rope: int,
    bytes_per_element: float = 2.0,
) -> Dict[str, float]:
    """One call of the absorbed decode attention of one layer that has to
    read ``rows`` cached positions in all (the live lengths of its slots
    added up): each row's latent and rope key read once
    (``rank + rope`` values) and, a token, ``2 * heads * rows * ((rank +
    rope) + rank)`` operations (the scores over 576, the weighted sum
    over 512 at the published widths). The queries and the result, a few
    rows a slot, are left out: a floor."""
    return {
        "flops": 2.0 * heads * rows * ((rank + rope) + rank),
        "bytes": rows * (rank + rope) * bytes_per_element,
    }
