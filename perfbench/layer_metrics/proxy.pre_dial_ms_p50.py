"""Median time a request spends in the server's hop before it starts to
wait for the upstream's first byte (auth, schedule, connect): the offset
of the ``ttft`` phase in the server's hop trace (``GET /v2/debug/traces``,
component ``server``)."""

from perfbench.loadgen import percentile


def read(ctx):
    offsets = [
        s["offset_ms"] for h in (ctx.get("hops") or []) for s in h.get("spans", [])
        if s.get("phase") == "ttft"
    ]
    return percentile(offsets, 0.5) if offsets else None
