"""Median wall time of the scheduler steps whose mode was ``decode``
(flight records, host clock round one ``Engine.step``; a prefill step
also holds the decode that follows it in the same step)."""

from perfbench.loadgen import flight_records, percentile

def read(ctx):
    durs = [r["dur_ms"] for r in flight_records(ctx) if r["mode"] == "decode"]
    return percentile(durs, 0.5) if durs else None
