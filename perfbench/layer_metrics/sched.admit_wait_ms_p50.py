"""Median, over the window's requests, of the time from the engine's
``submit`` to the scheduler taking the request from its queue (flight
records, ``admitted``: one ``[trace_id, wait_ms]`` a request, in the
step that admitted it): the wait behind other requests' prefills and for
a free slot, before the request's own prefill starts."""

from perfbench.loadgen import flight_records, percentile

def read(ctx):
    waits = [
        wait_ms for r in flight_records(ctx)
        for _trace_id, wait_ms in r.get("admitted") or []
    ]
    return percentile(waits, 0.5) if waits else None
