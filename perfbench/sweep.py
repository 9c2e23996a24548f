#!/usr/bin/env python3
"""Find an open-loop cell's knee once: one server start, the cell's mix
offered at rising rates, a table of what the system did at each.

    python perfbench/sweep.py --workload <cell> [--seconds 20] [--start 0.4]
        [--factor 1.25] [--steps 9] [--seed 1]

The knee is the highest rate at which completions keep up with arrivals
and the engine's queue (``waiting`` in its health) does not grow; the
cell then offers four fifths of it (``perfbench/cells/<cell>.json``). The
benchmark itself never searches for a rate: this is run once by whoever
defines the cell, and its table is kept in ``perfbench/sweeps/``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench import cluster as cl, loadgen, run as bench  # noqa: E402


async def watch(insts, workers, samples, window):
    """The engines' queue and slots, once a second through the window."""
    import aiohttp

    async with aiohttp.ClientSession() as session:
        while time.perf_counter() < window.t0 + window.seconds:
            waiting = used = 0
            for inst in insts:
                base, hdrs = cl.engine_url(workers, inst)
                async with session.get(f"{base}/healthz", headers=hdrs) as r:
                    h = await r.json()
                waiting += h["waiting"]
                used += h["slots_used"]
            samples.append((waiting, used))
            await asyncio.sleep(1.0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--start", type=float, default=0.4)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--steps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    setup = bench.Setup(args)
    rates = [args.start * args.factor ** k for k in range(args.steps)]
    run_dir = os.path.join(
        ROOT, "chiprun_out", "perfbench", "runs", f"sweep-{setup.cell['name']}"
    )
    rows = []
    # the highest rate reaches every bucket any rate reaches
    widest = loadgen.plan_open(
        setup.mix, rates[-1], args.seconds, args.seed, setup.scale
    )
    with bench.serving(setup, run_dir, widest) as (
        cluster, hdrs, insts, workers, engines, buckets
    ):
        for rate in rates:
            planned = loadgen.plan_open(
                setup.mix, rate, args.seconds, args.seed, setup.scale
            )
            samples = []
            window = asyncio.run(loadgen.drive(
                cluster.base, hdrs, setup.spec["name"], setup.mix, planned,
                args.seconds,
                lambda w: watch(insts, workers, samples, w),
            ))
            red = loadgen.reduce_window(window, setup.mix)
            t_end = window.t0 + window.seconds
            first = sum(
                1 for r in window.results
                if r.chunk_times and r.chunk_times[0] <= t_end
            )
            cl.poll(
                "the engines to drain", time.time() + 120,
                lambda: all(
                    h["slots_used"] == 0 and h["waiting"] == 0
                    for h in (cl.engine_health(workers, i) for i in insts)
                ),
            )
            half = samples[len(samples) // 2:] or [(0, 0)]
            row = {
                "rate_rps": round(rate, 4),
                "arrivals": red["attempted"],
                "first_tokens_in_window": first,
                "completed_in_window": red["completed"],
                "failed": red["failed"],
                "offered_tok_s": round(sum(
                    p.output_tokens for p in planned) / args.seconds, 1),
                "output_tok_s": round(red["tokens"] / args.seconds, 1),
                "ttft_ms_p50": round(loadgen.percentile(red["ttft_ms"], 0.5), 1)
                if red["ttft_ms"] else None,
                "ttft_ms_p90": round(loadgen.percentile(red["ttft_ms"], 0.9), 1)
                if red["ttft_ms"] else None,
                "itl_ms_p99": round(loadgen.percentile(red["gaps_ms"], 0.99), 1)
                if red["gaps_ms"] else None,
                "waiting_max": max(w for w, _ in samples) if samples else None,
                "waiting_mean_2nd_half": round(
                    sum(w for w, _ in half) / len(half), 2),
                "waiting_end": samples[-1][0] if samples else None,
                "slots_used_mean": round(
                    sum(u for _, u in samples) / max(1, len(samples)), 2),
                "late_ms_max": round(red["late_ms_max"], 2),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {
        "workload": setup.cell["name"], "seconds_per_rate": args.seconds,
        "seed": args.seed, "slots": setup.spec["max_slots"],
        "replicas": setup.replicas, "rows": rows,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
