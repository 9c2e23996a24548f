"""Median, over the window's requests, of the time from the engine's
``submit`` to the scheduler handing the request's first token on (flight
records, ``first_tokens``: one ``[trace_id, ms]`` a request, in the step
that delivered it): the engine's share of a first token's time, to set
beside the proxy's share before it and the way back after it."""

from perfbench.loadgen import flight_records, percentile

def read(ctx):
    times = [
        ms for r in flight_records(ctx)
        for _trace_id, ms in r.get("first_tokens") or []
    ]
    return percentile(times, 0.5) if times else None
