"""Median device time of one whole prefill program at the largest bucket
of the plan: the largest the cell's planned requests reach
(``ctx["buckets"]``, what the run warmed up; every run of a cell then
reads the same program), from the events of the trace's ``XLA Modules``
line named ``jit_prefill_<bucket>`` (the runner names each prefill
program by its bucket). The flight records' prefill step is host time
round a step that also dispatches a decode; this is the prefill alone, on
the device's clock. A program that either end of the trace cuts is left
out (``stretch.whole_programs``: its event is only as long as the part
that was traced; PR 27's runs read 208.18, 83.35 and 0.01 ms from such
events where the whole program takes 268.1), and a stretch without a
whole one gives nothing to read: the harness then captures again.

The buckets are the plan's, not the engine's. Where a trace names a
prefill program at a bucket the plan does not reach, the two cut prompts
differently and no capture would ever serve: the run fails at once and
says so."""

import re

from perfbench import stretch
from perfbench.cluster import BenchFailure
from perfbench.loadgen import percentile

PROGRAM = re.compile(r"^jit_prefill_(\d+)$")


def buckets_in(events):
    """``{bucket: [duration_ns, ...]}`` of the prefill programs among
    ``[name, start_ns, duration_ns]`` events."""
    by_bucket = {}
    for name, _start, dur_ns in events:
        m = PROGRAM.match(name)
        if m:
            by_bucket.setdefault(int(m.group(1)), []).append(dur_ns)
    return by_bucket


def read(ctx):
    devices = [d for t in ctx.get("traces") or [] for d in t["devices"]]
    planned = ctx.get("buckets")
    ran = buckets_in(m for d in devices for m in d["module_events"])
    if planned and set(ran) - set(planned):
        raise BenchFailure(
            f"the trace names jit_prefill_<bucket> for the buckets "
            f"{sorted(ran)}, the plan's requests reach {sorted(planned)} "
            f"(loadgen.buckets_of): runner.prefill_device_ms_p50 reads the "
            f"plan's largest and would wait for it in vain"
        )
    whole = buckets_in(m for d in devices for m in stretch.whole_programs(d))
    bucket = max(planned or whole, default=None)
    if bucket not in whole:
        return None
    return percentile(whole[bucket], 0.5) / 1e6
