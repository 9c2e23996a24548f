"""Programs the engine process lowered during the window (flight
records, ``traced``, summed over the window's steps; the engine counts
``jax.monitoring``'s lowering events). Every shape was warmed up before
the window, so this is 0; a retrace that *hits* the persistent compile
cache adds no cache file and still stalls the scheduler, and shows
here."""

from perfbench.loadgen import flight_records

def read(ctx):
    counts = [r["traced"] for r in flight_records(ctx) if "traced" in r]
    return sum(counts) if counts else None
