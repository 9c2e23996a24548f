"""The six ``start.*`` per-layer metrics (ISSUE 42): each reader against a
hand-made ``ctx["healths"]``, and the CPU rehearsal of ``--trace 2``,
whose engines are real and so carry the ``startup`` object."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402

START = {
    "start.engine_ready_s": ("s", "program_span"),
    "start.weights_s": ("s", "program_span"),
    "start.lower_s": ("s", "program_span"),
    "start.load_s": ("s", "program_span"),
    "start.cache_misses": ("count", "program_counter"),
    "start.first_token_s": ("s", "program_span"),
}


def health(ready, first, weights, lower, load, misses):
    return {"status": "ok", "startup": {
        "t0": 1790000000.0, "ready_s": ready, "first_token_s": first,
        "phases": {"import": 3.0, "backend": 2.0, "config": 0.1,
                   "weights": weights, "engine": 1.0, "listen": 0.01},
        "programs": {"lowered": 30, "lower_s": lower, "load_s": load,
                     "cache_misses": misses, "retrieval_s": 0.5},
    }}


ONE = health(19.5, 71.0, 9.25, 12.5, 40.0, 5)
OTHER = health(17.0, 80.5, 11.0, 11.0, 44.5, 0)
WANT = {   # name: (one replica, the larger of two)
    "start.engine_ready_s": (19.5, 19.5),
    "start.weights_s": (9.25, 11.0),
    "start.lower_s": (12.5, 12.5),
    "start.load_s": (40.0, 44.5),
    "start.cache_misses": (5.0, 5.0),
    "start.first_token_s": (71.0, 80.5),
}


def reader(name):
    return bench.load_reader(name).read


@pytest.mark.parametrize("name", sorted(START))
def test_a_reader_gives_its_number_and_the_largest_over_replicas(name):
    one, two = WANT[name]
    assert reader(name)({"healths": [ONE]}) == one
    assert reader(name)({"healths": [ONE, OTHER]}) == two
    assert reader(name)({"healths": [OTHER, ONE]}) == two
    # a replica from before the object beside one that has it
    assert reader(name)({"healths": [{"status": "ok"}, ONE]}) == one


@pytest.mark.parametrize("name", sorted(START))
def test_a_reader_gives_nothing_without_the_object_and_never_raises(name):
    for ctx in ({}, {"healths": None}, {"healths": []},
                {"healths": [{"status": "ok"}]},
                {"healths": [{"startup": None}, {"startup": {}}]},
                {"healths": [{"startup": {"phases": None, "programs": 3}}]}):
        assert reader(name)(ctx) is None, ctx


def test_no_misses_is_a_number_not_a_metric_left_out():
    got = reader("start.cache_misses")({"healths": [OTHER]})
    assert got == 0.0 and got is not None


def test_the_six_are_declared_for_every_cell_under_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    cells = {w["name"] for w in b["workloads"]}
    for name, (unit, source) in START.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["better"]) == (unit, source, "lower")
        assert m["moves"] == "setup_s"
        assert m["layer"] == (
            "replica start (worker/serve_manager.py, engine/api_server.py)")
        assert set(m.get("workloads", cells)) == cells
        assert os.path.exists(os.path.join(PB, "layer_metrics", name + ".py"))
    # the last six entries: an entry put in the middle reads as a change
    assert [m["name"] for m in b["per_layer"][-6:]] == list(START)
    # and every cell reports them through the harness's own lookup
    for cell in cells:
        mine = {m["name"] for m in bench.metrics_of(b, "per_layer", cell)}
        assert set(START) <= mine


def test_the_rehearsal_of_trace_2_reads_all_six(tmp_path):
    cell = "qwen3-8b-int8.rag-closed"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "run.py"), "--workload", cell,
         "--rehearse", "--seed", "3000000042", "--seconds", "3",
         "--trace", "2"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    log = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    read = set(next(l for l in log if l.get("phase") == "metrics_read")["names"])
    left_out = {l["name"] for l in log if l.get("phase") == "metric_left_out"}
    assert set(START) <= read and not set(START) & left_out
    assert log[-1]["correct"] is True and log[-1]["metrics"] == {}
