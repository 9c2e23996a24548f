"""Median, over the window's scheduler steps, of how long the head of
the engine's queue had been waiting (flight records, ``oldest_wait_ms``)."""

from perfbench.loadgen import flight_records, percentile

def read(ctx):
    waits = [r["oldest_wait_ms"] for r in flight_records(ctx)]
    return percentile(waits, 0.5) if waits else None
