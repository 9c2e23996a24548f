"""BENCHMARK.json against the contract's own rules, the files its names
point at, and the last line a run prints (through the CPU rehearsal)."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer", "trace_in_run",
    }
    # the per-layer metrics come from --trace 2 (measure, then trace, in
    # one process), not from a run of their own
    assert bench["trace_in_run"] is True
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench", "tests/perfbench"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("perfbench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            assert isinstance(json.load(f), dict)
        dep = os.path.join(os.path.dirname(os.path.join(ROOT, c["file"])), "deployment.json")
        with open(dep) as f:
            d = json.load(f)
        assert d["reduced"] == c["reduced"] and d["source"] == c["source"]
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate|head)", key)


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        with open(os.path.join(PB, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        if mix["loop"] == "open":
            with open(os.path.join(PB, "cells", w["name"] + ".json")) as f:
                assert json.load(f)["rate_rps"] > 0
        else:
            assert mix["clients"] >= 1 and mix["pool"] >= mix["clients"]
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert 1 <= len(bench["workloads"]) <= 24


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    names = set()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    layers = set()
    for group, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in bench[group]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and m["name"] not in names
            names.add(m["name"])
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            assert set(m.get("workloads", cells)) <= cells
            if group == "end_to_end":
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.1
            else:
                layers.add(m["layer"])
                assert 1 <= len(m["layer"]) <= 200
                moved = e2e[m["moves"]]
                assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
                assert any(os.path.exists(os.path.join(PB, "layer_metrics", n + ".py")) for n in (m["name"], m["name"].rsplit(".", 1)[0]))
                if m["name"].endswith("_roofline"):
                    assert m["unit"] == "%"
    for cell in cells:
        mine = lambda g: [m for m in bench[g] if cell in m.get("workloads", cells)]  # noqa: E731
        assert len(mine("end_to_end")) >= 2 and mine("per_layer")
    assert len(layers) >= 4


def test_every_file_under_paths_has_a_plain_name(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_name_their_source():
    with open(os.path.join(PB, "peaks.json")) as f:
        peaks = json.load(f)
    assert "Google Cloud" in peaks["_source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def rehearse(cell, trace, cache_dir):
    # a compile cache of the run's own: a run is ``correct`` only if no
    # file was added to its cache during the window, and the suite's
    # other workers add theirs to the shared one all the time (both
    # rehearsals failed so under six workers and passed alone, PR 26)
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "run.py"), "--workload", cell,
         "--rehearse", "--seed", "3000000001", "--seconds", "3",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("which,trace", [("open", 1), ("closed", 0)])
def test_rehearsal_prints_the_contracts_last_line(bench, which, trace, tmp_path):
    """The whole path on the CPU with the tiny model: start, deploy from
    files, warm-up, window, checks, shutdown. Counts only: a CPU run
    carries no metric at all."""
    cell = next(
        w["name"] for w in bench["workloads"]
        if json.load(open(os.path.join(PB, "traffic", w["traffic"] + ".json")))["loop"] == which
    )
    last = rehearse(cell, trace, tmp_path / "jax_cache")
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0, last
    assert last["attempted"] >= 1 and last["completed"] >= 1
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    assert "memory_peak_bytes" in last["device"]
    assert last["compiled_in_window"] == 0
    assert last["counts"]["tokens"] > 0
    if trace:
        assert last["counts"]["flight_records"] > 0
        assert last["counts"]["hops"] == last["attempted"]


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no system to test: no result, another code than 0."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(PB, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "2", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert not any(
        line.startswith("{") and '"correct"' in line
        for line in proc.stdout.splitlines()
    )
