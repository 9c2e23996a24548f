#!/usr/bin/env python3
"""Keeps the runs a cell's bounds were set from, or were found to hold
under, as data: one file a cell, ``perfbench/calibration/<cell>.json``.

    python3 perfbench/calibrate.py chiprun_out/perfbench/sets \\
        --commit <the commit the runs were made on> --date <yyyy-mm-dd> \\
        --pr <n> [--sets-bounds] [--also <per-layer metric>,...] [<cell> ...]

Reads what ``sets.sh`` left (``<cell>.S<set>.<run>.out``) and writes the
file of every cell found there (or of those named): for every end-to-end
metric the cell reports, each set's values, median and spread as
``spread.py`` computes them, and the seeds. A set whose runs all had one
seed stands apart under ``one_seed``: it says how much of a spread is the
machine's and not the schedule's. ``--also`` names per-layer metrics to keep
run by run beside them (a cell whose sets ran with ``--trace 2`` has them in
every run's last line). Then it prints what ``rule_bounds`` makes of the
directory.

``--sets-bounds`` is for a ``benchmark`` PR that sets the bounds again: it
measures every cell whose file says ``"sets_bounds": true`` and writes those
files again, and ``BENCHMARK.json``'s bounds are ``spread.rule_bound`` of the
widest spread among them (``tests/perfbench/test_perfbench_calibration.py``).
A PR that adds a cell under the bounds as they stand brings that cell's file
without the flag, or none: it changes no file that is here and no bound.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench import spread as sp  # noqa: E402

CAL_DIR = os.path.join(HERE, "calibration")


def seed_of(path: str) -> int:
    with open(path) as f:
        for ln in f:
            if ln.startswith('{"phase": "plan"'):
                return json.loads(ln)["seed"]
    raise ValueError(f"{path}: no plan line")


def read_sets(sets_dir: str) -> Dict[str, Dict[int, Dict[int, dict]]]:
    """``spread.read_sets`` with each run's seed beside its metrics."""
    return {cell: {s: {i: {"seed": seed_of(os.path.join(
                               sets_dir, f"{cell}.S{s}.{i}.out")), "metrics": got}
                       for i, got in runs.items()}
                   for s, runs in sets.items()}
            for cell, sets in sp.read_sets(sets_dir).items()}


def reading(run: dict, name: str):
    """A run's reading of one metric. A client-side number that the cell did
    not report end to end when the run was made is taken from the run's
    ``window`` line, which always has it (``spread.read_run``'s ``log:``)."""
    return run["metrics"].get(name, run["metrics"].get("log:" + name))


def summary(name: str, runs: Dict[int, dict]) -> dict:
    """One set's readings of one metric, as ``spread.py`` reads them."""
    values = sp.values_of(name, {i: {name: reading(r, name)} for i, r in runs.items()
                                 if reading(r, name) is not None})
    return {"values": values, "median": statistics.median(values),
            "spread": sp.spread(values),
            "spread_trimmed": sp.spread(sp.trimmed(values))}


def calibrate_cell(bench: dict, cell: str, found: Dict[int, Dict[int, dict]],
                   also: List[str], head: dict) -> dict:
    """One cell's file from its sets (set -> run -> seed and metrics)."""
    out = dict(head, cell=cell, run_seconds=bench["run_seconds"],
               sets={}, one_seed={}, metrics={}, per_layer={})
    for s, runs in sorted(found.items()):
        seeds = [r["seed"] for _, r in sorted(runs.items())]
        kind = "one_seed" if len(runs) > 1 and len(set(seeds)) == 1 else "sets"
        out[kind][str(s)] = {"seeds": seeds}
    if len(out["sets"]) < 2:
        raise ValueError(f"{cell}: a bound wants two sets of runs or more")
    mine = [m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]
    for name, into in [(n, "metrics") for n in mine] + [(n, "per_layer") for n in also]:
        got = {kind: {s: summary(name, found[int(s)]) for s in out[kind]
                      if any(reading(r, name) is not None
                             for r in found[int(s)].values())}
               for kind in ("sets", "one_seed")}
        if len(got["sets"]) < 2 and into == "metrics":
            raise ValueError(f"{cell}: {name} is not in two sets of its runs")
        out[into][name] = {k: v for k, v in got.items() if v}
    return out


def rule_bounds(cal_dir: str = CAL_DIR) -> Dict[str, Tuple[float, float, str]]:
    """metric -> (the widest spread of any set in any file that sets the
    bounds, ``spread.rule_bound`` of it, that file's cell); ``setup_s``
    keeps the ceiling, since it is judged by its median alone."""
    widest: Dict[str, Tuple[float, str]] = {}
    for path in sorted(glob.glob(os.path.join(cal_dir, "*.json"))):
        with open(path) as f:
            cal = json.load(f)
        if not cal["sets_bounds"]:
            continue
        for name, kept in cal["metrics"].items():
            wide = max(sp.spread(s["values"]) for s in kept["sets"].values())
            if name not in widest or wide > widest[name][0]:
                widest[name] = (wide, cal["cell"])
    return {name: (wide, sp.CEILING if name == "setup_s" else sp.rule_bound(wide), cell)
            for name, (wide, cell) in widest.items()}


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("sets_dir")
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--commit", required=True)
    ap.add_argument("--date", required=True)
    ap.add_argument("--pr", type=int, required=True)
    ap.add_argument("--sets-bounds", action="store_true")
    ap.add_argument("--also", default="")
    args = ap.parse_args(argv[1:])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = read_sets(args.sets_dir)
    os.makedirs(CAL_DIR, exist_ok=True)
    for cell in args.cells or sorted(found):
        out = calibrate_cell(
            bench, cell, found[cell], [n for n in args.also.split(",") if n],
            {"pr": args.pr, "commit": args.commit, "date": args.date,
             "sets_bounds": args.sets_bounds})
        with open(os.path.join(CAL_DIR, cell + ".json"), "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
    for name, (wide, bound, cell) in sorted(rule_bounds().items()):
        print(f"{name:14s} widest {100 * wide:.2f}% ({cell})  bound {bound:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
