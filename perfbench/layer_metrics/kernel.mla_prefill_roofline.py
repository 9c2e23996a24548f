"""The flash prefill kernel's share of its roofline in a latent-attention
(MLA) model, whose prefill decompresses keys 192 and values 128 wide (at
A.X-K1's widths): over every call in the traced stretch, the least time
the chip could take for the call's shapes (``perfbench/roofline_mla.py``:
the lower triangle's operations at the bf16 peak, ``2 * pairs * heads *
(qk + v)``, or q, k, v read and o written once at their own widths at
the HBM peak, whichever is longer) over the device time the calls took.
The rows, the heads and the value width are read from the operation's
own text in the trace, ``%flash_attention_prefill.N = bf16[batch, heads,
tokens, v] custom-call(...)`` (the kernel's result is as wide as a
value); the key width is the configuration's. A stretch without a call
gives nothing to read and the harness captures again; a configuration
that is no MLA model gives nothing either."""

import re

from perfbench import roofline, roofline_mla

KERNEL = re.compile(
    r"^%flash_attention_prefill[\w.\-]* = \w+\[(\d+),(\d+),(\d+),(\d+)\].* custom-call\("
)


def read(ctx):
    if not ctx["model_config"].get("kv_lora_rank"):
        return None
    w = roofline_mla.widths(ctx["model_config"])
    least = took = 0.0
    for t in ctx.get("traces") or []:
        for d in t["devices"]:
            for name, v in d["ops"].items():
                m = KERNEL.match(name)
                if not m:
                    continue
                batch, heads, tokens, v_width = map(int, m.groups())
                call = roofline_mla.mla_prefill_call(
                    tokens, heads, w["qk"], v_width
                )
                one = roofline.least_seconds(
                    batch * call["flops"], batch * call["bytes"], ctx["peaks"]
                )["seconds"]
                least += one * v["count"]
                took += v["total_ns"] / 1e9
    return 100.0 * least / took if took else None
