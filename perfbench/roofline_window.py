"""What attention under a sliding window needs, from its shapes alone
(``gpustack_tpu/ops/flash_attention.py`` with a band,
``gpustack_tpu/ops/decode_attention.py`` over a ring of window rows):
operations and bytes of a prefill call and of a decode call. The least
time for them is ``roofline.least_seconds``'s. Kept with the benchmark
so that no later PR can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def window_of(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """``(window, sliding layers, full layers)`` of a hub ``config.json``
    whose ``layer_types`` names its sliding layers, as far as
    ``num_hidden_layers``; ``(0, 0, layers)`` for a file without."""
    layers = int(cfg["num_hidden_layers"])
    kinds = (cfg.get("layer_types") or [])[:layers]
    sliding = sum(k == "sliding_attention" for k in kinds)
    if not sliding or not cfg.get("sliding_window"):
        return 0, 0, layers
    return int(cfg["sliding_window"]), sliding, layers - sliding


def band_pairs(t: int, window: int) -> float:
    """(query, key) pairs of ``t`` positions from 0 under a causal mask
    and a band of ``window`` (0: none): position ``i`` sees ``min(i + 1,
    window)`` keys."""
    if not window or window >= t:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def window_prefill_call(
    t: int, heads: int, kv_heads: int, head_dim: int, window: int,
    bytes_per_element: float = 2.0,
) -> Dict[str, float]:
    """One call of the flash prefill kernel over ``t`` tokens (one
    layer) with a band of ``window`` (0: the whole triangle, which is
    ``roofline.flash_prefill_call``): the band's operations (QK^T and
    PV, two a multiply-add) and q, k, v read and o written once."""
    flops = 4.0 * band_pairs(t, window) * head_dim * heads
    elements = 2.0 * t * heads * head_dim + 2.0 * t * kv_heads * head_dim
    return {"flops": flops, "bytes": elements * bytes_per_element}


def window_decode_call(
    live_rows: float, slots: float, heads: int, kv_heads: int,
    head_dim: int, bytes_per_element: float = 2.0,
) -> Dict[str, float]:
    """One call of the decode kernel over a ring (one sliding layer, one
    decode step): ``live_rows`` cached rows attended over all slots
    (``min(length, window)`` a live slot), each row's key and value of
    every kv head read once, ``slots`` queries read and outputs written.
    Two operations a multiply-add for the scores and for the values,
    every query head against its own kv head's rows."""
    row = kv_heads * head_dim
    return {
        "flops": 4.0 * live_rows * heads * head_dim,
        "bytes": bytes_per_element * (
            2.0 * live_rows * row + 2.0 * slots * heads * head_dim
        ),
    }
