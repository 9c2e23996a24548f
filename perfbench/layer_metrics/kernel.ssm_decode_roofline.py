"""The one-step state update's share of its roofline: the least time the
chip could take for one call of ``%ssm_state_update.N``
(``perfbench/roofline_ssm.py``: the live slots' float32 state read and
written at the HBM peak; its 5 operations a state element bind
nothing) over the
median device time of the calls in the traced stretch. One call is one
state-space layer of one decode step. The live slots are the median
``slots_used`` over the window's decode steps (the kernel moves nothing
for a slot nobody holds).

Not declared in ``BENCHMARK.json`` (PERF.md section 7): the stretch test
builds a Qwen3-8B trace for every declared ``device_trace`` metric,
where this has nothing to read."""

import re

from perfbench import roofline, roofline_ssm
from perfbench.loadgen import flight_records, percentile

KERNEL = re.compile(r"^%ssm_state_update[\w.\-]* = .* custom-call\(")


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
    w = roofline_ssm.widths(ctx["model_config"])
    call = roofline_ssm.ssm_update_call(
        percentile(live, 0.5), w["heads"], w["head_dim"], w["state"],
        w["groups"],
    )
    # the memory binds by far (5 operations to 8 bytes a state element)
    least = roofline.least_seconds(
        call["flops"], call["bytes"], ctx["peaks"]
    )["seconds"]
    return 100.0 * least / (percentile(took, 0.5) / 1e9)
