"""The chunked scan's share of its roofline: the least time the chip
could take for one layer's scan over a prompt bucket
(``perfbench/roofline_ssm.py ssm_scan_call``: its products at the bf16
peak or its activations at the HBM peak) over the median device time of
the calls ``%ssm_chunk_scan.N`` in the traced stretch, for the largest
bucket the window's prefills ran.

The scan is plain einsums today (``ops/ssm.py``: a kernel of that name
only once a trace shows it at more than twice what its operations ask),
and a trace names XLA's fusions by number, not by the scope they came
from: so today this reads **nothing**, and the scan's share is measured
by ``hack/ssm_bench.py`` on the chip (PERF.md section 5). Not declared in
``BENCHMARK.json`` (PERF.md section 7)."""

import re

from perfbench import roofline, roofline_ssm
from perfbench.loadgen import percentile

KERNEL = re.compile(r"^%ssm_chunk_scan[\w.\-]* = .* custom-call\(")


def read(ctx):
    took = [
        v["median_ns"] for t in (ctx.get("traces") or [])
        for d in t["devices"] for name, v in d["ops"].items()
        if KERNEL.match(name)
    ]
    if not took or not ctx.get("buckets"):
        return None
    w = roofline_ssm.widths(ctx["model_config"])
    call = roofline_ssm.ssm_scan_call(
        max(ctx["buckets"]), w["heads"], w["head_dim"], w["state"],
        w["groups"], w["chunk"],
    )
    least = roofline.least_seconds(
        call["flops"], call["bytes"], ctx["peaks"]
    )["seconds"]
    return 100.0 * least / (percentile(took, 0.5) / 1e9)
