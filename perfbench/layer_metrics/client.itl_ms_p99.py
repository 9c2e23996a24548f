"""99th percentile of the gaps between consecutive content chunks, pooled
over all requests, at the client: reported here in the cells where it
flips between two kinds of stall from run to run and so cannot carry a
bound (PERF.md section 2)."""

from perfbench.loadgen import percentile


def read(ctx):
    gaps = (ctx.get("loadgen") or {}).get("gaps_ms")
    return percentile(gaps, 0.99) if gaps else None
