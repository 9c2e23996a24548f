"""Share of the tokens the engine dispatched that were padding: bucket
padding in prefill, idle slots in decode (flight records, 1 -
tokens_real / tokens_padded over the window)."""

from perfbench.loadgen import flight_records


def read(ctx):
    real = sum(r["tokens_real"] for r in flight_records(ctx))
    padded = sum(r["tokens_padded"] for r in flight_records(ctx))
    return 100.0 * (1.0 - real / padded) if padded else None
