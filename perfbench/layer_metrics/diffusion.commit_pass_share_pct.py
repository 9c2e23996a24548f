"""Share of the window's slot-passes that were commit passes, per cent:
the flight records' ``passes_commit`` over ``passes_denoise +
passes_commit``. A commit pass computes a block's rows once more over
its final tokens and decides nothing: 20 where a block takes four
denoise passes and one commit, and what fusing the commit with the next
block's first pass takes away. Nothing to read from a program that
counts no passes."""

from perfbench.loadgen import flight_records


def read(ctx):
    records = [r for r in flight_records(ctx) if "passes_denoise" in r]
    passes = sum(r["passes_denoise"] + r["passes_commit"] for r in records)
    if not passes:
        return None
    return 100.0 * sum(r["passes_commit"] for r in records) / passes
