"""The flash prefill kernel's share of its roofline where some of its
calls run under a band: over every call in the traced stretch, the least
time the chip could take for the call's shapes
(``perfbench/roofline_window.py``: the band's operations at the bf16
peak, or q, k, v, o moved once at the HBM peak, whichever is longer)
over the device time the calls took. A call with a band is
``%flash_attention_window.N = bf16[batch, heads, tokens, head_dim]
custom-call(...)`` and counts ``min(i + 1, window)`` keys a query, the
window the configuration's ``sliding_window``; a call without one is
``%flash_attention_prefill.N`` and counts the whole triangle, as
``kernel.flash_prefill_roofline`` does (a stack of window and full
layers makes both, and a bucket no longer than the window only the
second). For a file without a window the two metrics are one number. A
stretch without a call gives nothing to read, and the harness then
captures again."""

import re

from perfbench import roofline, roofline_window

KERNEL = re.compile(
    r"^%flash_attention_(prefill|window)[\w.\-]* = "
    r"\w+\[(\d+),(\d+),(\d+),(\d+)\].* custom-call\("
)


def read(ctx):
    least = took = 0.0
    cfg = ctx["model_config"]
    kv_heads = cfg["num_key_value_heads"]
    window = roofline_window.window_of(cfg)[0]
    for t in ctx.get("traces") or []:
        for d in t["devices"]:
            for name, v in d["ops"].items():
                m = KERNEL.match(name)
                if not m:
                    continue
                batch, heads, tokens, head_dim = map(int, m.groups()[1:])
                call = roofline_window.window_prefill_call(
                    tokens, heads, kv_heads, head_dim,
                    window if m.group(1) == "window" else 0,
                )
                one = roofline.least_seconds(
                    batch * call["flops"], batch * call["bytes"], ctx["peaks"]
                )["seconds"]
                least += one * v["count"]
                took += v["total_ns"] / 1e9
    return 100.0 * least / took if took else None
