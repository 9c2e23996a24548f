#!/usr/bin/env python3
"""The spreads of the runs ``sets.sh`` made, as the driver computes them.

    python3 perfbench/spread.py chiprun_out/perfbench/sets [<cell> ...]

For every metric of every cell: each set's median and spread (the distance
between the first and third quartile of ``statistics.quantiles(values,
n=4)`` as a share of the median), the wider spread, the mean of the sets'
spreads with each set's run farthest from its median left out (what a
bound must be twice of), and five times the widest (what a bound is set
to, never under 1 %). A set's first run is left out of ``setup_s``: it may
compile. Numbers marked ``log`` come from the ``window`` log line of each
run, which carries every client-side number whether the cell reports it
end to end or not.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List


def spread(values: List[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    if median == 0:
        # a count that is 0 in every run (runner.programs_traced) spreads
        # by 0; one that is 0 in most has no share of its median
        return 0.0 if q3 == q1 else float("inf")
    return (q3 - q1) / median


def trimmed(values: List[float]) -> List[float]:
    """Without the run farthest from the median."""
    if len(values) < 3:
        return values
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def read_run(path: str) -> Dict[str, float]:
    """The last line's metrics, and the window line's client numbers and
    stalls under ``log:`` names."""
    out: Dict[str, float] = {}
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    if not lines:
        return out
    last = json.loads(lines[-1])
    if "correct" not in last:
        return out
    for name, m in last["metrics"].items():
        out[name] = m["value"]
    out["log:correct"] = float(bool(last["correct"]) and last["failed"] == 0)
    for ln in lines:
        rec = json.loads(ln)
        if rec.get("phase") == "window":
            for name, v in {**rec.get("client", {}), **rec.get("stalls", {})}.items():
                if name not in out:
                    out["log:" + name] = v
    return out


def main(argv: List[str]) -> int:
    if len(argv) < 2:
        sys.stderr.write(__doc__)
        return 2
    runs: Dict[str, Dict[str, Dict[int, Dict[str, float]]]] = {}
    for path in sorted(glob.glob(os.path.join(argv[1], "*.out"))):
        m = re.match(r"(.+)\.S(\d+)\.(\d+)\.out$", os.path.basename(path))
        if not m or (argv[2:] and m.group(1) not in argv[2:]):
            continue
        got = read_run(path)
        if got:
            runs.setdefault(m.group(1), {}).setdefault(m.group(2), {})[
                int(m.group(3))] = got
    for cell, sets in runs.items():
        print(f"== {cell}: " + ", ".join(
            f"set {s}: {len(r)} runs" for s, r in sorted(sets.items())))
        names = sorted({n for r in sets.values() for x in r.values() for n in x})
        for name in names:
            meds, spreads, trims = [], [], []
            for s, r in sorted(sets.items()):
                vals = [x[name] for i, x in sorted(r.items())
                        if name in x and not (name == "setup_s" and i == 1)]
                if not vals:
                    continue
                meds.append(statistics.median(vals))
                spreads.append(spread(vals))
                trims.append(spread(trimmed(vals)))
            if not meds:
                continue
            wide = max(spreads)
            print(
                f"{name:34s} medians " + " / ".join(f"{m:.4f}" for m in meds)
                + "  spreads " + " / ".join(f"{100 * s:.2f}%" for s in spreads)
                + f"  trimmed mean {100 * statistics.mean(trims):.2f}%"
                + f"  5x widest {100 * max(0.01, 5 * wide):.1f}%"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
