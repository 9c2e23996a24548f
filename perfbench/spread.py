#!/usr/bin/env python3
"""The spreads of the runs ``sets.sh`` made, as the driver computes them.

    python3 perfbench/spread.py chiprun_out/perfbench/sets [--judge] [<cell> ...]

For every metric of every cell: each set's median and spread (the distance
between the first and third quartile of ``statistics.quantiles(values,
n=4)`` as a share of the median), the wider spread, the mean of the sets'
spreads with each set's run farthest from its median left out (what a
bound must be twice of), five times the widest (the contract's guide), and
the bound that ``rule_bound`` makes of it: that, rounded up to a whole
percent, never under 1 % and never over the contract's ceiling of 10 %. One bound serves all of
a metric's cells, so the bound in ``BENCHMARK.json`` is ``rule_bound`` of
the widest spread over the cells (``calibrate.py`` keeps the sets it was
taken from in ``calibration/<cell>.json``). A set's first run is left out of
``setup_s``: it may compile. With ``--judge`` the first set is a parent's
runs and the second a change's (``pairs.sh``), and every metric that
``BENCHMARK.json`` bounds is marked ``inside`` or ``outside`` its bound
(``inside``). Numbers marked ``log`` come from the ``window`` log line of
each run, which carries every client-side number whether the cell reports
it end to end or not.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import statistics
import sys
from typing import Dict, List


FACTOR, FLOOR, CEILING = 5, 0.01, 0.1


def rule_bound(widest: float) -> float:
    """The bound a metric gets from the widest spread of any set in any of
    its cells: ``FACTOR`` times it, rounded up to a whole percent, inside
    [``FLOOR``, ``CEILING``] (the contract allows no bound outside them)."""
    if math.isnan(widest):
        raise ValueError("no spread to set a bound from")
    if math.isinf(widest):
        return CEILING
    # 5 * 0.014 is 0.07 and a little: round the product before the ceiling
    percent = math.ceil(round(FACTOR * widest * 100, 6))
    return min(CEILING, max(FLOOR, percent / 100))


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


def inside(parent: List[float], change: List[float], bound: float,
           median_only: bool = False) -> bool:
    """ISSUE 45's two comparisons for runs of one tree on both sides: the
    medians apart by less than ``bound`` of the parent's, and both sides'
    spreads under it. ``median_only`` is for ``setup_s``, which is judged by
    its median alone. What a check makes of a pair is the driver's to say
    (the ``choosing-metrics`` guide, section 6, step 5), not this file's."""
    med = statistics.median(parent)
    if abs(statistics.median(change) - med) > bound * abs(med):
        return False
    return median_only or not any(spread(v) > bound for v in (parent, change))


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


def read_sets(sets_dir: str) -> Dict[str, Dict[int, Dict[int, Dict[str, float]]]]:
    """cell -> set -> run -> what ``read_run`` reads of
    ``<cell>.S<set>.<run>.out``; only runs that printed a last line."""
    runs: Dict[str, Dict[int, Dict[int, Dict[str, float]]]] = {}
    for path in sorted(glob.glob(os.path.join(sets_dir, "*.out"))):
        m = re.match(r"(.+)\.S(\d+)\.(\d+)\.out$", os.path.basename(path))
        got = read_run(path) if m else None
        if got:
            runs.setdefault(m.group(1), {}).setdefault(int(m.group(2)), {})[
                int(m.group(3))] = got
    return runs


def values_of(name: str, runs: Dict[int, Dict[str, float]]) -> List[float]:
    """One set's readings of one metric, in the runs' order. A set's first
    run may compile: not in ``setup_s``, as the driver leaves it out."""
    return [x[name] for i, x in sorted(runs.items())
            if name in x and not (name == "setup_s" and i == 1)]


def main(argv: List[str]) -> int:
    if len(argv) < 2:
        sys.stderr.write(__doc__)
        return 2
    bounds: Dict[str, dict] = {}
    if "--judge" in argv:
        argv = [a for a in argv if a != "--judge"]
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bounds = {m["name"]: m for m in json.load(f)["end_to_end"]}
    runs = {cell: sets for cell, sets in read_sets(argv[1]).items()
            if not argv[2:] or cell in argv[2:]}
    for cell, sets in runs.items():
        print(f"== {cell}: " + ", ".join(
            f"set {s}: {len(r)} runs" for s, r in sorted(sets.items())))
        names = sorted({n for r in sets.values() for x in r.values() for n in x})
        for name in names:
            kept = [v for _, r in sorted(sets.items()) if (v := values_of(name, r))]
            if not kept:
                continue
            meds = [statistics.median(v) for v in kept]
            spreads = [spread(v) for v in kept]
            trims = [spread(trimmed(v)) for v in kept]
            wide = max(spreads)
            print(
                f"{name:34s} medians " + " / ".join(f"{m:.4f}" for m in meds)
                + "  spreads " + " / ".join(f"{100 * s:.2f}%" for s in spreads)
                + f"  trimmed mean {100 * statistics.mean(trims):.2f}%"
                + f"  5x widest {500 * wide:.1f}%"
                + ("" if math.isnan(wide) else f"  bound {rule_bound(wide):.2f}")
                + (f"  at {bounds[name]['bound']}: " + ("inside" if inside(
                    kept[0], kept[1], bounds[name]["bound"],
                    median_only=name == "setup_s") else "outside")
                   if name in bounds and len(kept) >= 2 else "")
            )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
