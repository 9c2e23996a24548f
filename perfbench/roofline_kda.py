"""What the two forms of a KDA mixer's recurrence need (the gated delta
rule with one decay a key channel: ``gpustack_tpu/ops/delta_rule.py``,
``g [.., H, Dk]``), from their shapes alone: bytes and operations of the
one-step state update of a decode step (the call ``kda_state_update``),
and of the chunked form over a prompt (scope ``kda_chunk_scan``). The
least time for them is ``roofline.least_seconds``'s. Kept with the
benchmark so that no later PR can move the yardstick;
``roofline_delta.py`` is the same for one decay a head.
"""

from __future__ import annotations

from typing import Any, Dict


def kda_layers(cfg: Dict[str, Any]) -> int:
    """How many layers of a hub ``config.json`` are KDA layers: every
    layer that ``gqa_layers`` does not list, of a file that has a
    ``linear_attn_config``; 0 for any other family's."""
    if not cfg.get("linear_attn_config"):
        return 0
    return int(cfg["num_hidden_layers"]) - len(cfg.get("gqa_layers") or ())


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The widths of a hub ``config.json`` of the Solar-Open2 family:
    keys and values alike, ``linear_attn_config.head_dim`` wide; every
    layer that ``gqa_layers`` does not list is a KDA layer."""
    linear = cfg["linear_attn_config"]
    return {
        "heads": int(linear["num_heads"]),
        "key": int(linear["head_dim"]),
        "value": int(linear["head_dim"]),
        "chunk": 64,
        "sub_block": 16,
        "layers": kda_layers(cfg),
    }


def kda_update_call(
    live: float, heads: int, key: int, value: int, state_bytes: float = 4.0,
) -> Dict[str, float]:
    """One call of ``kda_state_update`` (one layer, one decode step) with
    ``live`` slots somebody holds: each one's state ``[key, heads *
    value]`` read and written once; its ``q``, ``k`` and decay (``[key,
    heads]`` float32 each: the decay is one number a head and key
    channel) and the row of ``beta`` and ``v`` (``[2, heads * value]``
    float32) read, ``o`` (``[heads * value]`` float32) written. A state
    element takes the decay's multiply, a multiply-add for ``S^T k``, one
    for the rank-one correction and one for ``S^T q``: 7 operations, as
    with one decay a head (the decay is a multiply either way). A slot
    nobody holds moves nothing."""
    elements = heads * key * value
    small = 3 * key * heads + 3 * heads * value
    return {
        "flops": 7.0 * live * elements,
        "bytes": live * (2.0 * elements * state_bytes + 4.0 * small),
    }


def kda_scan_call(
    t: int, heads: int, key: int, value: int, chunk: int, sub_block: int,
    bytes_per_element: float = 4.0,
) -> Dict[str, float]:
    """The chunked form of one layer over ``t`` positions (padded to
    whole chunks of ``chunk``), a position and head. The two decayed
    products within the chunk (keys against keys, queries against keys):
    below the diagonal sub-blocks one matmul against every earlier row
    (``2 chunk key`` each, the rows above the diagonal computed and
    masked), on the diagonal sub-blocks the explicit differences (a
    multiply, an exponential and a multiply-add a channel: ``4 sub_block
    key`` each); the unit-lower-triangular solve as products against
    ``beta v`` and ``beta gamma k`` (``2 chunk (value + key)``; forming
    the inverse itself is ``chunk^2 / 3`` more, left out: a floor); the
    carried state's part of ``u`` and of ``o`` (``2 key value`` each),
    the chunk's own part of ``o`` (``2 chunk value``) and the boundary
    state (``2 key value``). ``q``, ``k``, ``v`` read and ``o`` written
    in float32 (what the form computes in), ``g`` float32 a key channel,
    ``beta`` float32 a head; the boundary states are ``t / chunk``
    states, left out: a floor."""
    t = -(-t // chunk) * chunk
    a_position = (
        4.0 * chunk * key + 8.0 * sub_block * key
        + 2.0 * chunk * (value + key)
        + 6.0 * key * value
        + 2.0 * chunk * value
    )
    elements = t * heads * (3.0 * key + 2.0 * value)
    return {
        "flops": t * heads * a_position,
        "bytes": elements * bytes_per_element + 4.0 * t * heads,
    }
