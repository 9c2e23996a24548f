"""The one-step delta update's share of its roofline: the least time the
chip could take for one call of ``%delta_state_update.N``
(``perfbench/roofline_delta.py``: the live slots' float32 state read and
written at the HBM peak; its 7 operations a state element bind nothing)
over the median device time of the calls in the traced stretch. One call
is one linear-attention layer of one decode step. The live slots are the
median ``slots_used`` over the window's decode steps (the kernel moves
nothing for a slot nobody holds).

Nothing to read in a stretch without such a call (any other
configuration's, and a program from before the kernel existed)."""

import re

from perfbench import roofline, roofline_delta
from perfbench.loadgen import flight_records, percentile

KERNEL = re.compile(r"^%delta_state_update[\w.\-]* = .* custom-call\(")


def read(ctx):
    took = [
        v["median_ns"] for t in (ctx.get("traces") or [])
        for d in t["devices"] for name, v in d["ops"].items()
        if KERNEL.match(name)
    ]
    live = [
        r["slots_used"] for r in flight_records(ctx) if r["mode"] == "decode"
    ]
    if not took or not live:
        return None
    w = roofline_delta.widths(ctx["model_config"])
    call = roofline_delta.delta_update_call(
        percentile(live, 0.5), w["heads"], w["key"], w["value"]
    )
    # the memory binds by far (7 operations to 8 bytes a state element)
    least = roofline.least_seconds(
        call["flops"], call["bytes"], ctx["peaks"]
    )["seconds"]
    return 100.0 * least / (percentile(took, 0.5) / 1e9)
