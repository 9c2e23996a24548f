"""The bounds of BENCHMARK.json are what one rule makes of the runs kept in
perfbench/calibration/<cell>.json (PR 45): ``spread.rule_bound`` of the
widest spread of any set in any file that says it sets the bounds. A cell
added later brings a file that does not, or none, and moves no bound."""

import glob
import json
import math
import os
import re
import shutil
import statistics

import pytest

from perfbench import calibrate
from perfbench import spread as sp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOE_RAG = "qwen3-30b-a3b-int8-l12.rag-closed"

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _load(path):
    with open(path) as f:
        return json.load(f)


FILES = {os.path.basename(p)[:-len(".json")]: _load(p)
         for p in sorted(glob.glob(os.path.join(calibrate.CAL_DIR, "*.json")))}
KEPT = [(cell, metric) for cell, cal in FILES.items() for metric in cal["metrics"]]


@pytest.mark.parametrize("widest,bound", [
    (0.0, 0.01), (0.002, 0.01), (0.0021, 0.02), (0.014, 0.07),
    (0.0141, 0.08), (0.0154, 0.08), (0.02, 0.1), (0.0627, 0.1),
    (float("inf"), 0.1),
])
def test_the_rule(widest, bound):
    assert sp.rule_bound(widest) == bound
    assert sp.FLOOR <= sp.rule_bound(widest) <= sp.CEILING


def test_the_rule_wants_a_spread():
    with pytest.raises(ValueError):
        sp.rule_bound(float("nan"))


@pytest.mark.parametrize("metric", sorted(E2E))
def test_a_bound_is_the_rule_of_the_widest_spread_kept(metric):
    wide, bound, cell = calibrate.rule_bounds()[metric]
    assert FILES[cell]["sets_bounds"] and metric in FILES[cell]["metrics"]
    assert wide == max(s["spread"] for s in FILES[cell]["metrics"][metric]["sets"].values())
    assert bound == (sp.CEILING if metric == "setup_s" else sp.rule_bound(wide))
    assert E2E[metric]["bound"] == bound


@pytest.mark.parametrize("cell,metric", KEPT)
def test_a_kept_pair_has_two_sets_of_runs_on_the_same_seeds(cell, metric):
    cal = FILES[cell]
    sets = cal["metrics"][metric]["sets"]
    assert len(sets) >= 2
    seeds = [cal["sets"][s]["seeds"] for s in sets]
    assert all(s == seeds[0] for s in seeds)
    assert len(set(seeds[0])) == len(seeds[0]) >= 5
    for s in sets.values():
        # a set's first run may compile and is not in setup_s
        assert len(s["values"]) >= (4 if metric == "setup_s" else 5)
        assert all(v > 0 and math.isfinite(v) for v in s["values"])
        assert s["median"] == pytest.approx(statistics.median(s["values"]))
        assert s["spread"] == pytest.approx(sp.spread(s["values"]))
        assert s["spread_trimmed"] == pytest.approx(sp.spread(sp.trimmed(s["values"])))


@pytest.mark.parametrize("cell", sorted(FILES))
def test_a_file_says_what_was_measured_where_and_when(cell):
    cal = FILES[cell]
    assert cal["cell"] == cell and cell in CELLS
    assert re.match(r"^[0-9a-f]{40}$", cal["commit"])
    assert re.match(r"^\d{4}-\d{2}-\d{2}$", cal["date"])
    assert cal["run_seconds"] == BENCH["run_seconds"] and cal["pr"] >= 45
    assert cal["sets_bounds"] in (True, False)
    # a file is whole: every end-to-end metric its cell reports, no other
    assert set(cal["metrics"]) == {
        m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", CELLS)}


def test_no_seed_serves_two_cells():
    """So no cell's schedule is another's."""
    seeds = [s for cal in FILES.values()
             for s in next(iter(cal["sets"].values()))["seeds"]]
    assert len(seeds) == len(set(seeds))


def test_the_cells_the_bounds_were_set_from_are_the_four_of_pr_45():
    assert {c for c, cal in FILES.items() if cal["sets_bounds"]} >= {
        "qwen3-8b-int8.chat-open", MOE_RAG, "qwen3-8b-int8.rag-closed",
        "ax-k1-int8-ep16-l12.longdoc-closed"}


def test_a_cell_added_under_the_bounds_brings_its_file_or_none_and_moves_no_bound(tmp_path):
    """What a later PR does: it adds ``calibration/<its cell>.json`` written
    without ``--sets-bounds``, however wide its runs spread, or no file at
    all; no file here is edited and every bound stays."""
    for path in glob.glob(os.path.join(calibrate.CAL_DIR, "*.json")):
        shutil.copy(path, tmp_path)
    before = calibrate.rule_bounds(str(tmp_path))
    assert before == calibrate.rule_bounds()
    late = dict(FILES["qwen3-8b-int8.rag-closed"], cell="new-model.rag-closed",
                pr=46, sets_bounds=False)
    late["metrics"] = {
        name: {"sets": {s: dict(x, values=[v * (1 + 0.2 * i) for i, v in enumerate(x["values"])])
                        for s, x in kept["sets"].items()}}
        for name, kept in late["metrics"].items()}
    for kept in late["metrics"].values():
        for x in kept["sets"].values():
            x["spread"] = sp.spread(x["values"])
    assert max(x["spread"] for x in late["metrics"]["ttft_ms_p50"]["sets"].values()) > 0.2
    (tmp_path / "new-model.rag-closed.json").write_text(json.dumps(late))
    assert calibrate.rule_bounds(str(tmp_path)) == before
    # and the same file, had a benchmark PR written it to set the bounds again
    (tmp_path / "new-model.rag-closed.json").write_text(
        json.dumps(dict(late, sets_bounds=True)))
    assert calibrate.rule_bounds(str(tmp_path))["ttft_ms_p50"][2] == "new-model.rag-closed"


def test_one_seed_run_over_and_over_stands_beside_the_moe_rag_cells_sets():
    """What splits the schedule's part of that cell's spread from the
    machine's; it sets no bound."""
    cal = FILES[MOE_RAG]
    (one,) = cal["one_seed"].values()
    assert len(one["seeds"]) >= 5 and len(set(one["seeds"])) == 1
    for metric in ("ttft_ms_p50", "output_tok_s"):
        (kept,) = cal["metrics"][metric]["one_seed"].values()
        assert len(kept["values"]) == len(one["seeds"])
    assert "sched.first_token_ms_p50.closed" in cal["per_layer"]


def _write_run(path, seed, metrics, client=None):
    path.write_text(
        json.dumps({"phase": "plan", "cell": "c", "seed": seed}) + "\n"
        + json.dumps({"phase": "window", "client": client or {}}) + "\n"
        + json.dumps({"correct": True, "failed": 0, "seed": seed, "metrics": {
            k: {"value": v, "unit": "x"} for k, v in metrics.items()}}) + "\n")


BENCH_AB = {
    "run_seconds": 50,
    "workloads": [{"name": "a.x"}, {"name": "b.x"}],
    "end_to_end": [
        {"name": "ttft", "bound": 0.01, "workloads": ["a.x"]},
        {"name": "itl", "bound": 0.01, "workloads": ["a.x"]},
        {"name": "setup_s", "bound": 0.1},
    ],
}
HEAD = {"pr": 45, "commit": "c" * 40, "date": "2026-10-01", "sets_bounds": True}


def _made_up_sets(tmp_path):
    for cell, scale in (("a.x", 1.0), ("b.x", 3.0)):
        for s in (1, 2):
            for i, v in enumerate([100.0, 101.0, 102.0, 103.0, 104.0, 104.5 + s], 1):
                # set 2 was run when the cell did not report itl end to end
                e2e = {"ttft": scale * v, "setup_s": 30.0 + i, "layer.ms": v / 2}
                _write_run(tmp_path / f"{cell}.S{s}.{i}.out", 7000 + i,
                           dict(e2e, itl=v / 4) if s == 1 else e2e, {"itl": v / 4})
    for i in range(1, 4):
        _write_run(tmp_path / f"a.x.S3.{i}.out", 7001, {"ttft": 100.0 + i, "setup_s": 31.0})
    (tmp_path / "a.x.T.out").write_text("{}\n")
    return calibrate.read_sets(str(tmp_path))


def test_calibrate_keeps_what_a_cells_sets_left(tmp_path):
    found = _made_up_sets(tmp_path)
    out = calibrate.calibrate_cell(BENCH_AB, "a.x", found["a.x"], ["layer.ms"], HEAD)
    assert out["cell"] == "a.x" and out["sets_bounds"] is True and out["run_seconds"] == 50
    assert out["sets"] == {s: {"seeds": [7001, 7002, 7003, 7004, 7005, 7006]} for s in ("1", "2")}
    assert out["one_seed"] == {"3": {"seeds": [7001] * 3}}
    assert set(out["metrics"]) == {"ttft", "itl", "setup_s"}
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 106.5]
    assert out["metrics"]["ttft"]["sets"]["2"]["values"] == values
    assert out["metrics"]["ttft"]["sets"]["2"]["spread"] == pytest.approx(sp.spread(values))
    assert out["metrics"]["ttft"]["one_seed"]["3"]["values"] == [101.0, 102.0, 103.0]
    # the window line's number where the last line has none
    assert out["metrics"]["itl"]["sets"]["2"]["values"] == [v / 4 for v in values]
    assert out["metrics"]["setup_s"]["sets"]["1"]["values"] == [32.0, 33.0, 34.0, 35.0, 36.0]
    assert out["per_layer"]["layer.ms"]["sets"]["1"]["median"] == 51.25


def test_calibrate_keeps_only_what_the_cell_reports_and_wants_two_sets(tmp_path):
    found = _made_up_sets(tmp_path)
    out = calibrate.calibrate_cell(BENCH_AB, "b.x", found["b.x"], [], dict(HEAD, sets_bounds=False))
    assert set(out["metrics"]) == {"setup_s"} and out["sets_bounds"] is False
    with pytest.raises(ValueError):
        calibrate.calibrate_cell(BENCH_AB, "a.x", {1: found["a.x"][1]}, [], HEAD)


@pytest.mark.parametrize("parent,change,bound,ok", [
    # PR 44's cell: medians 1.3 % apart, either side spread by 3-5 %
    ([82.2, 80.0, 84.0, 85.0, 79.0, 83.0, 81.0], [81.1, 80.0, 83.0, 85.0, 78.0, 82.0, 84.0],
     0.01, False),
    ([82.2, 80.0, 84.0, 85.0, 79.0, 83.0, 81.0], [81.1, 80.0, 83.0, 85.0, 78.0, 82.0, 84.0],
     0.1, True),
    ([100.0, 101.0], [111.0, 112.0], 0.1, False),
    ([100.0, 101.0], [89.0, 90.0], 0.1, False),
    ([100.0, 100.2, 100.4, 100.6], [100.1, 100.3, 100.5, 100.6], 0.01, True),
])
def test_inside_is_issue_45s_two_comparisons(parent, change, bound, ok):
    assert sp.inside(parent, change, bound) is ok


def test_set_up_is_held_by_its_median_alone():
    wide = ([30.0, 38.0, 36.0, 31.0], [37.0, 30.5, 36.5, 31.5])
    assert not sp.inside(*wide, 0.1)
    assert sp.inside(*wide, 0.1, median_only=True)
    assert not sp.inside([30.0, 31.0], [34.0, 35.0], 0.1, median_only=True)


def test_spread_prints_the_bound_the_rule_gives(tmp_path, capsys):
    for s in (1, 2):
        for i, v in enumerate([100.0, 100.2, 100.4, 100.6, 100.8, 101.0 + s], 1):
            _write_run(tmp_path / f"a.x.S{s}.{i}.out", 7000 + i, {"ttft": v})
    assert sp.main(["spread.py", str(tmp_path)]) == 0
    row = next(ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("ttft"))
    wide = sp.spread([100.0, 100.2, 100.4, 100.6, 100.8, 103.0])
    assert f"5x widest {500 * wide:.1f}%" in row
    assert row.endswith(f"bound {sp.rule_bound(wide):.2f}")


def test_spread_holds_a_parents_and_a_changes_runs_to_the_bounds(tmp_path, capsys):
    """``pairs.sh`` keeps the parent's runs as set 1 and the change's as
    set 2; ``--judge`` holds them to BENCHMARK.json's bounds."""
    # two pairs: one run a side is left of setup_s, which has no spread then
    for s, ttft in ((1, [80.0, 80.4]), (2, [80.1, 80.5])):
        for i, v in enumerate(ttft, 1):
            _write_run(tmp_path / f"a.x.S{s}.{i}.out", 7000 + i, {
                "ttft_ms_p50": v, "setup_s": 70.0 if i == 1 else 30.0 + s,
                "layer.ms": v})
    assert sp.main(["spread.py", str(tmp_path), "--judge"]) == 0
    rows = {ln.split()[0]: ln for ln in capsys.readouterr().out.splitlines()}
    bound = E2E["ttft_ms_p50"]["bound"]
    assert rows["ttft_ms_p50"].endswith(f"at {bound}: inside")
    assert rows["setup_s"].endswith("5x widest nan%  at 0.1: inside")   # 31 against 32
    assert " at " not in rows["layer.ms"]
