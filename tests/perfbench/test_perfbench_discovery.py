"""Data-driven, as a requirement: a configuration, a traffic mix, a cell
and a per-layer metric are added as files and entries, and the harness
finds each by its name without a file that is there being edited."""

import argparse
import json
import os
import shutil

import pytest

from perfbench import loadgen, run as bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture()
def checkout(tmp_path, monkeypatch):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    monkeypatch.setattr(bench, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench, "HERE", str(tmp_path / "perfbench"))
    return tmp_path


def add_files(root):
    pb = root / "perfbench"
    before = {
        str(p): p.read_bytes() for p in pb.rglob("*") if p.is_file()
    }
    cfg = pb / "configs" / "new-model"
    cfg.mkdir()
    shutil.copy(pb / "rehearsal" / "tiny-qwen3" / "config.json", cfg)
    (cfg / "deployment.json").write_text(json.dumps({
        "name": "new-model", "source": "https://example.org/new-model",
        "chips": 1, "reduced": [], "assumed": {},
        "model": {"max_seq_len": 512, "max_slots": 6, "replicas": 1},
    }))
    (pb / "traffic" / "new-mix.json").write_text(json.dumps({
        "name": "new-mix", "loop": "open",
        "arrivals": {"process": "gamma", "cv": 2.0},
        "prompt_tokens": {"dist": "lognormal", "median": 100, "sigma": 0.1, "min": 90, "max": 110},
        "output_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.1, "min": 8, "max": 12},
        "template_tokens": 24, "tail_s": 1.0,
    }))
    (pb / "cells" / "new-model.new-mix.json").write_text(
        json.dumps({"rate_rps": 2.5})
    )
    (pb / "layer_metrics" / "new.metric.py").write_text(
        '"""A reader added as a file."""\n\n\n'
        "def read(ctx):\n    return ctx.get('answer')\n"
    )
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({
        "name": "new-model", "source": "https://example.org/new-model",
        "file": "perfbench/configs/new-model/config.json", "reduced": [],
        "why": "added by a test",
    })
    b["workloads"].append({
        "name": "new-model.new-mix", "config": "new-model",
        "traffic": "new-mix", "chips": 1, "why": "added by a test",
    })
    b["per_layer"].append({
        "name": "new.metric", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "a new layer",
        "moves": "ttft_ms_p50", "workloads": ["new-model.new-mix"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    after = {str(p): p.read_bytes() for p in pb.rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items()), "a file was edited"


def args(workload, seconds=10.0):
    return argparse.Namespace(
        workload=workload, seed=5, seconds=seconds, trace=1, rehearse=False,
    )


def test_added_files_are_found_by_name(checkout):
    add_files(checkout)
    setup = bench.Setup(args("new-model.new-mix"))
    assert setup.spec["local_path"] == str(
        checkout / "perfbench" / "configs" / "new-model"
    )
    assert setup.spec["name"] == "new-model" and setup.spec["max_slots"] == 6
    assert setup.model_config["hidden_size"] == 64
    planned = setup.plan()
    assert len(planned) == 25            # 2.5 a second for 10 seconds
    # the mix's own arrival process: bursty gaps, not the exponential's
    gaps = sorted(b.due_s - a.due_s for a, b in zip(planned, planned[1:]))
    assert gaps[-1] > 5 * gaps[len(gaps) // 2]
    assert all(90 <= p.prompt_tokens <= 110 for p in planned)
    assert loadgen.buckets_of(planned, 512) == [128]
    got = bench.read_layer_metrics(setup, {"answer": 42.0, "loadgen": {"late_ms_max": 1.5}})
    assert got["new.metric"] == {"value": 42.0, "unit": "ms"}
    # a reader that finds nothing to read leaves its metric out
    assert "new.metric" not in bench.read_layer_metrics(
        setup, {"loadgen": {"late_ms_max": 1.5}}
    )
    # the metric that lists other cells is not read in this one
    assert "loadgen.late_ms_max" not in got


def test_cells_that_were_there_do_not_see_the_new_metric(checkout):
    add_files(checkout)
    first = json.loads((checkout / "BENCHMARK.json").read_text())["workloads"][0]
    setup = bench.Setup(args(first["name"]))
    names = [m["name"] for m in bench.metrics_of(setup.bench, "per_layer", first["name"])]
    assert "new.metric" not in names and any(n.startswith("device.idle_pct") for n in names)


def test_an_open_cell_without_a_rate_is_refused(checkout):
    add_files(checkout)
    os.remove(checkout / "perfbench" / "cells" / "new-model.new-mix.json")
    with pytest.raises(bench.BenchFailure):
        bench.Setup(args("new-model.new-mix")).plan()


def test_an_unknown_cell_is_refused(checkout):
    with pytest.raises(bench.BenchFailure):
        bench.Setup(args("no-such.cell"))
