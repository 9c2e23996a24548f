"""A sliding layer's decode attention as a share of its roofline: the
least time the chip could take for one call of
``%gqa_window_decode_attention.N`` (``perfbench/roofline_window.py``: the
live rows' keys and values read once at the HBM peak; its operations
bind nothing) over the median device time of the calls in the traced
stretch. One call is one sliding layer of one decode step. The live rows
are the median, over the window's decode steps, of the flight records'
``window_rows`` a sliding layer: ``min(length, window)`` a live slot.

Not declared in ``BENCHMARK.json`` (PERF.md section 7): the stretch test
builds a Qwen3-8B trace for every declared ``device_trace`` metric,
where this has nothing to read."""

import re

from perfbench import roofline, roofline_window
from perfbench.loadgen import flight_records, percentile

KERNEL = re.compile(r"^%gqa_window_decode_attention[\w.\-]* = .* custom-call\(")


def read(ctx):
    cfg = ctx["model_config"]
    took = [
        v["median_ns"] for t in (ctx.get("traces") or [])
        for d in t["devices"] for name, v in d["ops"].items()
        if KERNEL.match(name)
    ]
    sliding = roofline_window.window_of(cfg)[1]
    steps = [
        r for r in flight_records(ctx)
        if r["mode"] == "decode" and r.get("window_rows")
    ]
    if not took or not steps or not sliding:
        return None
    call = roofline_window.window_decode_call(
        percentile([r["window_rows"] / sliding for r in steps], 0.5),
        percentile([r["slots_used"] for r in steps], 0.5),
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    least = roofline.least_seconds(
        call["flops"], call["bytes"], ctx["peaks"]
    )["seconds"]
    return 100.0 * least / (percentile(took, 0.5) / 1e9)
