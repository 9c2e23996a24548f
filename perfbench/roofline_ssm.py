"""What the two forms of a Mamba-2 mixer's selective scan need, from
their shapes alone (``gpustack_tpu/ops/ssm.py``): bytes and operations
of the one-step state update of a decode step, and of the chunked scan
over a prompt. The least time for them is ``roofline.least_seconds``'s.
Kept with the benchmark so that no later PR can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def widths(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The widths of a hub ``config.json`` of the Nemotron-H family."""
    return {
        "heads": cfg["mamba_num_heads"], "head_dim": cfg["mamba_head_dim"],
        "state": cfg["ssm_state_size"], "groups": cfg["n_groups"],
        "chunk": cfg.get("chunk_size", 128),
        "layers": cfg["hybrid_override_pattern"].count("M"),
    }


def ssm_update_call(
    live: float, heads: int, head_dim: int, state: int, groups: int,
    state_bytes: float = 4.0,
) -> Dict[str, float]:
    """One call of ``ssm_state_update`` (one layer, one decode step) with
    ``live`` slots somebody holds: each one's state ``[heads, head_dim,
    state]`` read and written once, and its ``x * dt`` and decay
    (``[head_dim, heads]`` float32 each), ``B`` and ``C`` (``[groups,
    state]`` float32) read and ``y`` written. A state element takes a
    multiply-add for the update and one for ``y``, and the decay's
    multiply: 5 operations. A slot nobody holds moves nothing."""
    elements = heads * head_dim * state
    small = 3 * head_dim * heads + 2 * groups * state
    return {
        "flops": 5.0 * live * elements,
        "bytes": live * (2.0 * elements * state_bytes + 4.0 * small),
    }


def ssm_scan_call(
    t: int, heads: int, head_dim: int, state: int, groups: int, chunk: int,
    bytes_per_element: float = 2.0,
) -> Dict[str, float]:
    """The chunked scan of one layer over ``t`` positions (padded to
    whole chunks of ``chunk``): within a chunk ``C B^T`` a group
    (``2 chunk state`` a position and group) and the decay-masked product
    against the inputs (``2 chunk head_dim`` a position and head, and the
    mask's multiply); each chunk's state (``2 head_dim state`` a
    position and head) and what the carried state gives each position
    (the same again). ``x``, ``B``, ``C`` read and ``y`` written in the
    activations' width, ``dt`` in float32; the carried state is
    ``t / chunk`` states, left out: a floor."""
    t = -(-t // chunk) * chunk
    flops = t * (
        2.0 * chunk * state * groups
        + (2.0 * head_dim + 1.0) * chunk * heads
        + 4.0 * head_dim * state * heads
    )
    elements = t * (2.0 * heads * head_dim + 2.0 * groups * state)
    return {
        "flops": flops,
        "bytes": elements * bytes_per_element + 4.0 * t * heads,
    }
