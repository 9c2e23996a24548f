"""The one-step KDA update's share of the decode program: the device
time of the calls ``%kda_state_update.N`` (``ops/delta_rule.py`` with a
decay a key channel, one a KDA layer a decode step) over the device time
of the programs ``jit__decode_impl`` in the traced stretch (``XLA
Modules`` line), in percent. Both are added up over the same stretch, a
program that an end of the trace cuts included with the part that was
traced. ``delta.state_update_share_pct`` is the same for the call with
one decay a head (``%delta_state_update``), which this one does not
match.

0.0 where the configuration has no KDA layer (no ``linear_attn_config``,
or every layer in ``gqa_layers``): that is the truth of it. **Nothing**
where it has such layers and the stretch holds no such call, so that the
capture is retaken and the run fails by name: a renamed kernel, or a
decode step that took the XLA form, must not read 0. A program from
before the kernel existed cannot start this configuration at all."""

import re

from perfbench import roofline_kda

KERNEL = re.compile(r"^%kda_state_update[\w.\-]* = .* custom-call\(")
PROGRAM = "jit__decode_impl"


def read(ctx):
    if not roofline_kda.kda_layers(ctx["model_config"]):
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
