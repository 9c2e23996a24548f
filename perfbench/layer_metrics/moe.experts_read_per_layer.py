"""Held experts whose weights a block pass read, a layer: the median,
over the window's ``denoise`` steps, of the step's own count
(``moe_read_pct`` of the flight record: the pass program's int32 beside
its tokens, summed over the layers with experts, over held x layers)
times the experts the replica holds. Whether 32 slots x 4 rows of 8
pairs touch all 128: the touched experts' kernel reads what it counts,
so this is the pass's weight traffic in experts. Nothing to read where no
step was a block pass of a model with experts."""

from perfbench.loadgen import flight_records, percentile


def read(ctx):
    shares = [
        r["moe_read_pct"] for r in flight_records(ctx)
        if r["mode"] == "denoise" and "moe_read_pct" in r
    ]
    held = int(ctx["model_config"].get("num_experts") or 0)
    if not shares or not held:
        return None
    return percentile(shares, 0.5) / 100.0 * held
