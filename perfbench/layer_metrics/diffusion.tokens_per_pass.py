"""Positions decided a slot-pass, over the window: the flight records'
``tokens_decided`` over ``passes_denoise + passes_commit`` (the passes
whose results the scheduler fetched for live slots, by kind). 0.8 where
every block takes four denoise passes of one position and a commit pass;
what a commit fused with the next block's first pass, or a rule that
decides several positions a pass, moves. Nothing to read from a program
that counts no passes (any other model, or one from before the
counters)."""

from perfbench.loadgen import flight_records


def read(ctx):
    records = [r for r in flight_records(ctx) if "passes_denoise" in r]
    passes = sum(r["passes_denoise"] + r["passes_commit"] for r in records)
    if not passes:
        return None
    return sum(r["tokens_decided"] for r in records) / passes
