"""The absorbed decode attention's share of its roofline (MLA): the least
time the chip could take for one call of ``%mla_decode_attention.N``
(``perfbench/roofline_mla.py``: the latent and rope bytes of the cached
rows it has to read at the HBM peak, or ``2 * heads * rows * (576 +
512)`` operations at the bf16 peak, whichever is longer) over the median
device time of the calls in the traced stretch. One call is one layer
of one decode step.

The rows it has to read are the live slots' contexts added up. The
benchmark does not see a context step by step, so it counts **the
prompts only**, from the window's flight records: the median number of
slots in use over the decode steps, times the mean prompt of the
window's prefills (``prompt_tokens`` over the requests admitted). The
tokens decoded so far are left out and so is what idle slots cost, so
the share is a floor, as ``kernel.decode_hbm_roofline`` is."""

import re

from perfbench import roofline, roofline_mla
from perfbench.loadgen import flight_records, percentile

KERNEL = re.compile(r"^%mla_decode_attention[\w.\-]* = .* custom-call\(")


def read(ctx):
    took = [
        v["median_ns"] for t in (ctx.get("traces") or [])
        for d in t["devices"] for name, v in d["ops"].items()
        if KERNEL.match(name)
    ]
    records = flight_records(ctx)
    prompts = sum(r["prompt_tokens"] for r in records)
    admitted = sum(len(r.get("admitted") or []) for r in records)
    live = [r["slots_used"] for r in records if r["mode"] == "decode"]
    if not took or not admitted or not live:
        return None
    w = roofline_mla.widths(ctx["model_config"])
    rows = percentile(live, 0.5) * prompts / admitted
    call = roofline_mla.mla_decode_call(rows, w["heads"], w["rank"], w["rope"])
    least = roofline.least_seconds(
        call["flops"], call["bytes"], ctx["peaks"]
    )["seconds"]
    return 100.0 * least / (percentile(took, 0.5) / 1e9)
