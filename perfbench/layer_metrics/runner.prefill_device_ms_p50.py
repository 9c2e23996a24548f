"""Median device time of one prefill program at the largest bucket the
traced stretch ran: the events of the trace's ``XLA Modules`` line named
``jit_prefill_<bucket>`` (the runner names each prefill program by its
bucket). The flight records' prefill step is host time round a step
that also dispatches a decode; this is the prefill alone, on the
device's clock."""

import re

from perfbench.loadgen import percentile

PROGRAM = re.compile(r"^jit_prefill_(\d+)$")


def read(ctx):
    by_bucket = {}
    for t in ctx.get("traces") or []:
        for d in t["devices"]:
            for name, _start, dur_ns in d["module_events"]:
                m = PROGRAM.match(name)
                if m:
                    by_bucket.setdefault(int(m.group(1)), []).append(dur_ns)
    if not by_bucket:
        return None
    return percentile(by_bucket[max(by_bucket)], 0.5) / 1e6
