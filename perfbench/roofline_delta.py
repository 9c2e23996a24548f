"""What the two forms of a gated-delta-rule mixer's recurrence need, from
their shapes alone (``gpustack_tpu/ops/delta_rule.py``): bytes and
operations of the one-step state update of a decode step, and of the
chunked form over a prompt. The least time for them is
``roofline.least_seconds``'s. Kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The widths of a hub ``config.json`` of the Olmo-hybrid family."""
    return {
        "heads": cfg["linear_num_value_heads"],
        "key": cfg["linear_key_head_dim"],
        "value": cfg["linear_value_head_dim"],
        "chunk": 64,
        "layers": list(cfg["layer_types"]).count("linear_attention"),
    }


def delta_update_call(
    live: float, heads: int, key: int, value: int, state_bytes: float = 4.0,
) -> Dict[str, float]:
    """One call of ``delta_state_update`` (one layer, one decode step)
    with ``live`` slots somebody holds: each one's state ``[key, heads *
    value]`` read and written once; its ``q`` and ``k`` (``[key, heads]``
    float32 each) and the row of decay, ``beta`` and ``v`` (``[3, heads *
    value]`` float32) read, ``o`` (``[heads * value]`` float32) written. A
    state element takes the decay's multiply, a multiply-add for ``S^T
    k``, one for the rank-one correction and one for ``S^T q``: 7
    operations. A slot nobody holds moves nothing."""
    elements = heads * key * value
    small = 2 * key * heads + 4 * heads * value
    return {
        "flops": 7.0 * live * elements,
        "bytes": live * (2.0 * elements * state_bytes + 4.0 * small),
    }


def delta_scan_call(
    t: int, heads: int, key: int, value: int, chunk: int,
    bytes_per_element: float = 4.0,
) -> Dict[str, float]:
    """The chunked form of one layer over ``t`` positions (padded to
    whole chunks of ``chunk``), a position and head: ``K K^T`` and ``Q
    K^T`` within the chunk (``2 chunk key`` each), the unit-lower-
    triangular solve as products against ``beta v`` and ``beta gamma k``
    (``2 chunk (value + key)``; forming the inverse itself is ``chunk^2 /
    3`` more, left out: a floor), the carried state's part of ``u`` and of
    ``o`` (``2 key value`` each), the chunk's own part of ``o`` (``2
    chunk value``) and the boundary state (``2 key value``). ``q``, ``k``,
    ``v`` read and ``o`` written in float32 (what the form computes in),
    ``g`` and ``beta`` float32; the boundary states are ``t / chunk``
    states, left out: a floor."""
    t = -(-t // chunk) * chunk
    a_position = (
        4.0 * chunk * key
        + 2.0 * chunk * (value + key)
        + 6.0 * key * value
        + 2.0 * chunk * value
    )
    elements = t * heads * (2.0 * key + 2.0 * value)
    return {
        "flops": t * heads * a_position,
        "bytes": elements * bytes_per_element + 8.0 * t * heads,
    }
