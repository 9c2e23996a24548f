"""90th percentile of the time from when a request was due to its first
content chunk, at the client. End to end for a user, and unbounded here:
over the 60-odd scored requests of a one-chip window it is set by where
the window's two or three bursts fall, and those the seed draws (300 /
414 / 1,119 ms in three seeds; PERF.md section 2). It can carry a bound
only in a cell with some hundreds of requests a window."""

from perfbench.loadgen import percentile


def read(ctx):
    ttft = (ctx.get("loadgen") or {}).get("ttft_ms")
    return percentile(ttft, 0.9) if ttft else None
