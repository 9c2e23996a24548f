"""Median time from when a request was due to its first content chunk, at
the client. End to end for a user, and unbounded in the open-loop cell: a
median over 60-odd requests whose arrival falls at a random phase of a
42 ms decode step spreads by 7 % from seed to seed, more than a bound may
cover (PERF.md section 2). In the closed-loop cells it repeats and is the
end-to-end ``ttft_ms_p50``."""

from perfbench.loadgen import percentile


def read(ctx):
    ttft = (ctx.get("loadgen") or {}).get("ttft_ms")
    return percentile(ttft, 0.5) if ttft else None
