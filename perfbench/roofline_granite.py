"""Which layers of a hub ``config.json`` keep a Mamba-2 state, for either
family that has one: the Nemotron-H file says so in
``hybrid_override_pattern`` (``perfbench/roofline_ssm.py`` reads that
alone), the Granite 4.0-H file in ``layer_types``. Kept with the
benchmark so that no later PR can move the yardstick.
"""

from __future__ import annotations

from typing import Any, Dict


def mamba_layers(cfg: Dict[str, Any]) -> int:
    """How many layers of the file keep a Mamba-2 state; 0 for a file of
    neither family."""
    return (cfg.get("hybrid_override_pattern") or "").count("M") + list(
        cfg.get("layer_types") or ()
    ).count("mamba")
