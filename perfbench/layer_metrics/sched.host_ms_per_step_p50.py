"""Median, over the window's ``decode`` steps, of the host's own work in
a step: the step's wall time less the time its thread stood blocked on
the device (flight records, ``dur_ms - wait_ms``). ``dur_ms`` alone reads
the device's step time, because the scheduler waits for the device at
its sync point; this is what is left when the device gets faster."""

from perfbench.loadgen import flight_records, percentile

def read(ctx):
    host = [
        r["dur_ms"] - r["wait_ms"] for r in flight_records(ctx)
        if r["mode"] == "decode" and "wait_ms" in r
    ]
    return percentile(host, 0.5) if host else None
