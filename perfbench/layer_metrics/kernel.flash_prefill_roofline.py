"""The Pallas flash prefill kernel's share of its roofline: over every
call in the traced stretch, the least time the chip could take for the
call's shapes (``perfbench/roofline.py``: the lower triangle's operations
at the bf16 peak, or q, k, v, o moved once at the HBM peak, whichever is
longer) over the device time the calls took. The shapes are read from the
operation's own text in the trace, ``%flash_attention_prefill.N =
bf16[batch, heads, tokens, head_dim] custom-call(...)``. A stretch without
a call gives nothing to read, and the harness then captures again; a cell
whose prompts stay under the 1024 bucket never runs the kernel
(``engine/runner.py``: a smaller bucket takes the XLA path) and must not
declare the metric."""

import re

from perfbench import roofline

KERNEL = re.compile(
    r"^%flash_attention_prefill[\w.\-]* = \w+\[(\d+),(\d+),(\d+),(\d+)\].* custom-call\("
)


def read(ctx):
    least = took = 0.0
    kv_heads = ctx["model_config"]["num_key_value_heads"]
    for t in ctx.get("traces") or []:
        for d in t["devices"]:
            for name, v in d["ops"].items():
                m = KERNEL.match(name)
                if not m:
                    continue
                batch, heads, tokens, head_dim = map(int, m.groups())
                call = roofline.flash_prefill_call(
                    tokens, heads, kv_heads, head_dim
                )
                one = roofline.least_seconds(
                    batch * call["flops"], batch * call["bytes"], ctx["peaks"]
                )["seconds"]
                least += one * v["count"]
                took += v["total_ns"] / 1e9
    return 100.0 * least / took if took else None
