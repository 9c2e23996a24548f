"""The decode program's share of its HBM roofline: the least time the
chip could take to read the weights one decode step needs
(``perfbench/roofline.py``; int8, one byte a weight; the live cache's
bytes are not counted, so this is a floor) over the median device time of
the program ``jit__decode_impl`` in the trace (``XLA Modules`` line)."""

from perfbench import roofline
from perfbench.loadgen import flight_records, percentile

PROGRAM = "jit__decode_impl"


def read(ctx):
    durs = [
        m[2] for t in (ctx.get("traces") or []) for d in t["devices"]
        for m in d["module_events"] if m[0] == PROGRAM
    ]
    if not durs:
        return None
    active = [
        r["slots_used"] for r in flight_records(ctx) if r["mode"] == "decode"
    ]
    tokens = percentile(active, 0.5) if active else ctx["max_slots"]
    quant = ctx["spec"].get("quantization")
    need = roofline.decode_weight_bytes(
        ctx["model_config"], 1.0 if quant == "int8" else 2.0, tokens
    )
    least = need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (percentile(durs, 0.5) / 1e9)
