"""Median share of the engine's slots in use, over the window's
scheduler steps (flight records), in percent."""

from perfbench.loadgen import flight_records, percentile

def read(ctx):
    occ = [100.0 * r["slots_used"] / ctx["max_slots"] for r in flight_records(ctx)]
    return percentile(occ, 0.5) if occ else None
