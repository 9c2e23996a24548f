#!/usr/bin/env python3
"""Where a replica's start goes: one cell of the benchmark brought up as
``perfbench/run.py`` brings it up (server, deployment, every bucket warm),
then the worker's ``instance_start`` span, the engine's ``/debug/startup``
and its flight records read and laid out as PERF.md's table wants them.

    python hack/start_report.py --workload <cell> [--seed n] [--tag cold]

On the chip it is a measurement (chiprun; an empty
``JAX_COMPILATION_CACHE_DIR`` in the environment makes it a cold one);
``--rehearse`` runs the same path on the CPU with the tiny model and says
only whether every piece is there. Never imports JAX. The whole answer is
kept in ``chiprun_out/start/<cell>[-tag].json``; the last line of the
output is its summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from gpustack_tpu.observability.startup import brief  # noqa: E402 (no JAX)
from perfbench import cluster as cl, run as bench  # noqa: E402

LOWER, LOAD = 1, 2      # of ``brief``: [name, lower_ms, load_ms, cached]


def longest(programs, which, n=5):
    """The ``n`` programs longest in lowering (with the Python tracing
    before it) or in load: name, seconds, cached, phase."""
    ranked = sorted(programs, key=lambda r: brief(r)[which], reverse=True)
    return [
        [r["name"], round(brief(r)[which] / 1e3, 3), r.get("cached"), r["phase"]]
        for r in ranked[:n]
    ]


def spent(programs) -> dict:
    return {
        "n": len(programs),
        "lower_s": round(sum(brief(r)[LOWER] for r in programs) / 1e3, 3),
        "load_s": round(sum(brief(r)[LOAD] for r in programs) / 1e3, 3),
    }


def report(startup, span, at_warm: float) -> dict:
    """One replica's start, from the harness's T0 to every bucket warm."""
    s = startup["summary"]
    t0 = s["t0"]
    listen_end = sum(s["phases"].values())
    programs = startup["programs"]
    first_token_at = t0 + (s["first_token_s"] or 0.0)

    def after_first(r):
        return (r.get("load") or r["lower"])[1] > first_token_at

    later = [r for r in programs if after_first(r)]
    before_listen = [r for r in programs if r["phase"] != "step"]
    out = {
        "trace_id": startup["trace_id"],
        "worker_span": span and {
            "trace_id": span["trace_id"], "child_ok": (
                span["trace_id"] == startup["trace_id"]
                and span["span_id"] == startup["parent_id"]),
            "started_at_s": round(span["started_at"] - T0, 3),
            **{p["phase"]: round(p["duration_ms"] / 1e3, 3)
               for p in span["spans"]},
        },
        "engine_created_at_s": round(t0 - T0, 3),
        "phases": s["phases"],
        "phases_sum_s": round(listen_end, 3),
        "ready_s": s["ready_s"],
        "ready_after_listen_s": round(s["ready_s"] - listen_end, 3),
        "first_token_s": s["first_token_s"],
        "running_leaves_out_s": round(s["first_token_s"] - s["ready_s"], 3),
        "programs": s["programs"],
        "programs_in_start_phases": spent(before_listen),
        "programs_after_first_token": {
            **spent(later),
            "last_end_s": round(max(
                [(r.get("load") or r["lower"])[1] for r in later],
                default=first_token_at) - t0, 3),
        },
        "cached_false": sum(1 for r in programs if r.get("cached") is False),
        "longest_lower": longest(programs, LOWER),
        "longest_load": longest(programs, LOAD),
        "warm_at_s": round(at_warm - T0, 3),
    }
    # T0 -> warm = (T0 -> engine created) + first_token_s + the later
    # buckets' programs + what is left (harness polls, the requests
    # themselves through the server's port)
    out["rest_s"] = round(
        out["warm_at_s"] - out["engine_created_at_s"] - s["first_token_s"]
        - out["programs_after_first_token"]["lower_s"]
        - out["programs_after_first_token"]["load_s"], 3)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=4200000001)
    ap.add_argument("--tag", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.trace, args.seconds = 0, 4.0 if args.rehearse else float(
        bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"])
    setup = bench.Setup(args)
    name = setup.cell["name"] + (f"-{args.tag}" if args.tag else "")
    run_dir = os.path.join(ROOT, "chiprun_out", "perfbench", "runs", f"start-{name}")
    with bench.serving(setup, run_dir, setup.plan()) as (
        cluster, hdrs, insts, workers, engines, buckets
    ):
        at_warm = time.time()
        spans = cl.expect(cl.http(
            "GET", f"{cluster.base}/v2/debug/traces?component=worker&limit=200",
            headers=hdrs), 200, "worker traces")["items"]
        spans = [t for t in spans if t["name"] == "instance_start"]
        replicas = []
        for inst in insts:
            startup = cl.engine_get(workers, inst, "/debug/startup")
            health = cl.engine_health(workers, inst)
            flight = cl.engine_get(workers, inst, "/debug/flight?limit=2048")
            span = next((t for t in spans if t.get("attrs", {}).get(
                "instance_id") == inst["id"]), None)
            replicas.append({
                "instance": inst["id"], "startup": startup, "worker_span": span,
                "healthz_startup": health.get("startup"),
                "device": health.get("device"),
                "steps_with_programs": [
                    {k: r[k] for k in ("ts", "mode", "dur_ms", "admit_ms",
                                       "dispatch_ms", "programs")}
                    for r in flight["records"] if "programs" in r],
                "report": report(startup, span, at_warm),
            })
    out_dir = os.path.join(ROOT, "chiprun_out", "start")
    os.makedirs(out_dir, exist_ok=True)
    whole = {"cell": setup.cell["name"], "tag": args.tag, "seed": args.seed,
             "buckets": buckets, "t0": T0, "replicas": replicas,
             "cache_dir_set": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}
    with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
        json.dump(whole, f, indent=1)
    line = {"cell": setup.cell["name"], "tag": args.tag, "buckets": buckets,
            "device": replicas[0]["device"],
            "reports": [r["report"] for r in replicas]}
    if args.rehearse:
        # a CPU run's seconds are no one's numbers: what is there, only
        line = {"cell": setup.cell["name"], "rehearsal": True, "replicas": [{
            "child_ok": r["report"]["worker_span"]["child_ok"],
            "phases": sorted(r["report"]["phases"]),
            "programs": r["report"]["programs"]["lowered"],
            "cached_false": r["report"]["cached_false"],
            "steps_with_programs": len(r["steps_with_programs"]),
        } for r in replicas]}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except bench.BenchFailure as e:
        print(json.dumps({"failed": str(e)}))
        sys.exit(1)
