"""The one-step state update's share of the decode program, in a cell
whose end-to-end metric is the gap between tokens: the device time of
the calls ``%ssm_state_update.N`` (``ops/ssm.py``, one a Mamba-2 layer a
decode step) over the device time of the programs ``jit__decode_impl``
in the traced stretch (``XLA Modules`` line), in percent. Both are added
up over the same stretch, a program that an end of the trace cuts
included with the part that was traced.

A reader of its own beside ``ssm.state_update_share_pct.py``: that one
knows a state-space layer by a ``hybrid_override_pattern`` and reads 0.0
for a file that says ``layer_types`` (``perfbench/roofline_granite.py
mamba_layers`` knows both).

0.0 where the configuration has no state-space layer: that is the truth
of it. **Nothing** where it has such layers and the stretch holds no
such call, so that the capture is retaken and the run fails by name: a
renamed kernel, or a decode step that took the XLA form, must not read
0."""

import re

from perfbench import roofline_granite

KERNEL = re.compile(r"^%ssm_state_update[\w.\-]* = .* custom-call\(")
PROGRAM = "jit__decode_impl"


def read(ctx):
    if not roofline_granite.mamba_layers(ctx["model_config"]):
        return 0.0
    devices = [d for t in (ctx.get("traces") or []) for d in t["devices"]]
    kernel = sum(
        v["total_ns"] for d in devices for name, v in d["ops"].items()
        if KERNEL.match(name)
    )
    program = sum(
        m[2] for d in devices for m in d["module_events"] if m[0] == PROGRAM
    )
    if not kernel or not program:
        return None
    return 100.0 * kernel / program
