#!/usr/bin/env python3
"""How far check 3 of ``checks.py`` (prefill against the cache) reads on a
sound system: one server start for a cell, the check made ``--n`` times
with other prompts in the smallest and the largest bucket the cell warms.

    python perfbench/check_noise.py --workload <cell> [--n 40] [--seed 1]

The tolerance a configuration states in its ``deployment.json``
(``prefill_vs_cache_tol``) is set from this table, which is kept in
``perfbench/check_noise/<cell>.json``: run once, on the chip, by whoever
adds the configuration. One run of the benchmark draws one prompt a bucket
from its seed, so the tolerance has to hold for any prompt, not for the
few a handful of runs happened to draw.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench import checks, loadgen, run as bench  # noqa: E402


def summary(values) -> dict:
    xs = sorted(values)
    return {
        "n": len(xs), "median": loadgen.percentile(xs, 0.5),
        "p90": loadgen.percentile(xs, 0.9), "max": xs[-1], "sorted": xs,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--n", type=int, default=40)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    # the buckets are those of a whole window's requests
    args.trace, args.seconds = 0, 4.0 if args.rehearse else float(
        bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    )
    setup = bench.Setup(args)
    run_dir = os.path.join(
        ROOT, "chiprun_out", "perfbench", "runs",
        f"check_noise-{setup.cell['name']}",
    )
    table = {}
    with bench.serving(setup, run_dir, setup.plan()) as (
        cluster, hdrs, insts, workers, engines, buckets
    ):
        for bucket in sorted({min(buckets), max(buckets)}):
            pairs = []
            for i in range(args.n):
                by_decode, by_prefill, problems = checks.prefill_vs_cache(
                    cluster.base, hdrs, setup.spec["name"],
                    random.Random(args.seed * 1000 + i), bucket,
                    int(setup.spec["max_seq_len"]),
                )
                if problems:
                    raise bench.BenchFailure("; ".join(problems))
                pairs.append((by_decode, by_prefill))
            # the same prompt by both programs, and, for what the check
            # reads when an answer is wrong altogether, one prompt's
            # decode against the next prompt's prefill
            turned = pairs[1:] + pairs[:1]
            table[str(bucket)] = {
                "ranked": summary(
                    checks.ranked_diff(a, b) for a, b in pairs),
                "ranked_other_prompt": summary(
                    checks.ranked_diff(a, o[1]) for (a, _), o in zip(pairs, turned)),
                "pairs": pairs,
            }
            print(json.dumps({"bucket": bucket, **{
                k: v for k, v in table[str(bucket)].items() if k != "pairs"
            }}), flush=True)
    print(json.dumps({
        "workload": setup.cell["name"], "config": setup.cell["config"],
        "seed": args.seed, "unit": "nats", "buckets": table,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
