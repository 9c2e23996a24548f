"""The one-step KDA update's share of its roofline: the least time the
chip could take for one call of ``%kda_state_update.N``
(``perfbench/roofline_kda.py``: the live slots' float32 state read and
written at the HBM peak; its 7 operations a state element bind nothing)
over the median device time of the calls in the traced stretch. One call
is one KDA layer of one decode step. The live slots are the median
``slots_used`` over the window's decode steps (the kernel moves nothing
for a slot nobody holds); the cell's batch is full, so the window's
count and the traced stretch's agree.

0.0 where the configuration has no KDA layer (no ``linear_attn_config``,
or every layer in ``gqa_layers``), as ``kda.state_update_share_pct``
reads there: no such call exists to be timed. **Nothing** where it has
such layers and the stretch holds no such call or the window no decode
step, so that the capture is retaken and the run fails by name (a
renamed kernel, a decode step that took the XLA form)."""

import re

from perfbench import roofline, roofline_kda
from perfbench.loadgen import flight_records, percentile

KERNEL = re.compile(r"^%kda_state_update[\w.\-]* = .* custom-call\(")


def read(ctx):
    cfg = ctx["model_config"]
    if not roofline_kda.kda_layers(cfg):
        return 0.0
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
    w = roofline_kda.widths(cfg)
    call = roofline_kda.kda_update_call(
        percentile(live, 0.5), w["heads"], w["key"], w["value"]
    )
    # the memory binds by far (7 operations to 8 bytes a state element)
    least = roofline.least_seconds(
        call["flops"], call["bytes"], ctx["peaks"]
    )["seconds"]
    return 100.0 * least / (percentile(took, 0.5) / 1e9)
