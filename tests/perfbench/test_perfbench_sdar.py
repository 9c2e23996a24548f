"""The SDAR-30B-A3B-Chat configuration and its closed-loop cell (PR 63):
``config.json`` is the catalog's row with one key cut, the directory's
``generation_config.json`` says what the deployment generates with, the
cell is among the workloads with the traffic as it stands and on the
lists whose readers read a number there, each of the five new metrics
has a reader that reads the flight records' new fields (and nothing from
a program that lacks them), the two copies of the reference agree, and
the comparison with the reference judges through one function, sound
under its limits and each fault over one. Every entry is found **by
name**, never by its place in a list (ROADMAP B0)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)
NAME = "sdar-30b-a3b-chat-int8-l12"
DIRECTORY = os.path.join(PB, "configs", NAME)
CELL = NAME + ".reason-closed"
SIBLING = "qwen3-30b-a3b-int8-l12"
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
NEW = {
    "check.sdar_logit_err": ("program_counter", "nats", "lower"),
    "runner.denoise_pass_ms_p50": ("program_span", "ms", "lower"),
    "diffusion.tokens_per_pass": ("program_counter", "tokens", "higher"),
    "diffusion.commit_pass_share_pct": ("program_counter", "%", "lower"),
    "moe.experts_read_per_layer": ("program_counter", "count", "lower"),
}
# the standing lists whose readers read a number in this cell's traced run
LISTS = (
    "client.itl_ms_p99", "sched.occupancy_p50.closed",
    "device.idle_pct.closed", "device.peak_mem_gb.closed",
)

from gpustack_tpu.testing import sdar_small  # noqa: E402
from perfbench import loadgen  # noqa: E402
from perfbench import reference_check_sdar as check  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.reference import sdar_moe as reference  # noqa: E402


def load(path):
    with open(path) as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
DEPLOYMENT = load(DIRECTORY + "/deployment.json")


def named(group, name):
    found = [e for e in BENCH[group] if e["name"] == name]
    assert len(found) == 1, (group, name)
    return found[0]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(PB, "layer_metrics", name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- the configuration ------------------------------------------------------


def test_the_configuration_is_among_the_configs_under_its_source():
    entry = named("configs", NAME)
    assert entry["source"] == SOURCE == DEPLOYMENT["source"]
    assert entry["file"] == f"perfbench/configs/{NAME}/config.json"
    assert entry["reduced"] == DEPLOYMENT["reduced"] == ["num_hidden_layers"]
    assert DEPLOYMENT["name"] == NAME and DEPLOYMENT["chips"] == 1
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}


def test_config_json_is_the_catalog_s_config_with_one_key_cut():
    published = load(DIRECTORY + "/config.json")
    assert published.pop("architectures") == ["SDARMoeForCausalLM"]
    assert published == {**CATALOG, "num_hidden_layers": 12}
    # the same cut as its sibling's, so that the two cells differ in the
    # mechanism alone: every number the two files share is the same but
    # for the context the hub files state
    sibling = load(os.path.join(PB, "configs", SIBLING, "config.json"))
    differ = {
        k for k in published
        if k in sibling and published[k] != sibling[k]
    }
    assert differ == {"model_type", "max_position_embeddings"}


def test_the_directory_says_what_the_deployment_generates_with():
    from gpustack_tpu.models.config import load_hf_config

    assert load(DIRECTORY + "/generation_config.json") == {
        "block_length": 4, "denoising_steps": 4,
        "remasking_strategy": "sequential", "confidence_threshold": 0.9,
        "mask_token_id": 151669,
    }
    cfg = load_hf_config(DIRECTORY)
    twin = load_hf_config(os.path.join(PB, "configs", SIBLING))
    assert (
        cfg.diffusion_block, cfg.denoising_steps, cfg.remasking_strategy,
        cfg.mask_token_id,
    ) == (4, 4, "sequential", 151669)
    # every weight is the sibling's: the same tree, leaf for leaf
    for key in (
        "num_layers", "hidden_size", "num_heads", "num_kv_heads", "head_dim",
        "num_experts", "num_experts_per_tok", "moe_intermediate_size",
        "vocab_size", "qk_norm", "rope_theta", "norm_topk_prob",
        "tie_word_embeddings",
    ):
        assert getattr(cfg, key) == getattr(twin, key), key
    assert cfg.param_count() == twin.param_count()
    assert twin.diffusion_block == 0


def test_deployment_json_says_what_is_assumed_and_why_sequential():
    assumed = DEPLOYMENT["assumed"]
    for key in (
        "block_length", "denoising_steps", "remasking_strategy",
        "confidence_threshold", "mask_token_id", "architectures", "weights",
        "tokenizer", "quantization", "dropped_from_hub_file",
    ):
        assert key in assumed, key
    why = assumed["remasking_strategy"]
    for part in ("perfbench/checks.py", "2 mod 4", "left to right",
                 "low_confidence_dynamic", "4 + 1 passes"):
        assert part in why, part
    assert DEPLOYMENT["model"] == {
        "quantization": "int8", "max_seq_len": 2560, "max_slots": 32,
        "replicas": 1,
    }
    limits = DEPLOYMENT["sdar_check"]
    assert limits["buckets"] == [512, 1024] and limits["tails"] == [0, 2]
    assert set(tol for _, tol, _ in check.LIMITS) <= set(limits)
    assert all(limits[tol] > 0 for _, tol, _ in check.LIMITS)
    assert "TO BE MEASURED" not in json.dumps(DEPLOYMENT)
    for name in os.listdir(DIRECTORY):
        assert name in (
            "config.json", "generation_config.json", "deployment.json",
            "README.md",
        )


# ---- the cell -----------------------------------------------------------------


def test_the_cell_is_among_the_workloads_with_the_traffic_as_it_stands():
    cell = named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reason-closed", 1
    )
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    mix = loadgen.load_traffic("reason-closed", PB)
    assert mix["loop"] == "closed"
    assert int(mix["clients"]) == int(DEPLOYMENT["model"]["max_slots"]) == 32
    assert float(mix["temperature"]) == 1.0
    assert (
        mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
        <= int(DEPLOYMENT["model"]["max_seq_len"]) == 2560
    )
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert len(BENCH["workloads"]) >= 10


def test_the_cell_is_on_the_lists_whose_readers_read_a_number_there():
    def cells_of(group, name):
        return named(group, name).get("workloads") or ()

    assert CELL in cells_of("end_to_end", "output_tok_s")
    for name in ("ttft_ms_p50", "itl_ms_p99"):
        assert CELL not in cells_of("end_to_end", name)
    for name in LISTS:
        assert CELL in cells_of("per_layer", name), name
        assert named("per_layer", name)["moves"] == "output_tok_s"
    # its steps are of mode ``denoise``: the decode step's reader would
    # read nothing, and a declared metric missing from the line fails
    assert CELL not in cells_of("per_layer", "runner.decode_step_ms_p50.closed")
    assert CELL not in cells_of("per_layer", "kernel.decode_hbm_roofline.closed")
    for m in BENCH["per_layer"]:
        if CELL in (m.get("workloads") or ()):
            assert m["moves"] in ("output_tok_s", "setup_s"), m["name"]
    for m in bench_run.metrics_of(BENCH, "per_layer", CELL):
        assert bench_run.reader_path(m["name"]), m["name"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_declared_for_this_cell_only_with_a_reader(name):
    entry = named("per_layer", name)
    source, unit, better = NEW[name]
    assert (entry["source"], entry["unit"], entry["better"]) == (
        source, unit, better
    )
    assert entry["moves"] == "output_tok_s"
    assert entry["workloads"] == [CELL]
    assert set(entry) == {
        "name", "unit", "better", "source", "layer", "moves", "workloads"
    }
    # the layer is one that the benchmark already names
    assert sum(m["layer"] == entry["layer"] for m in BENCH["per_layer"]) > 5
    assert os.path.exists(os.path.join(PB, "layer_metrics", name + ".py"))


# ---- the readers ----------------------------------------------------------------


def a_window():
    """Flight records as the engine writes them for this model: a step of
    mode ``denoise`` fetches one pass's result, 32 live slots; every
    block takes four denoise passes and a commit."""
    steps = []
    for i in range(20):
        commit = i % 5 == 4
        steps.append({
            "mode": "denoise", "dur_ms": 10.0 + (i % 3), "slots_used": 32,
            "tokens_real": 128, "tokens_out": 0 if commit else 32,
            "passes_denoise": 0 if commit else 32,
            "passes_commit": 32 if commit else 0,
            "tokens_decided": 0 if commit else 32,
            "blocks_done": 32 if commit else 0,
            "moe_read_pct": 99.22,
        })
    steps.append({
        "mode": "prefill", "dur_ms": 60.0, "slots_used": 32,
        "passes_denoise": 0, "passes_commit": 0, "tokens_decided": 0,
        "blocks_done": 0, "moe_read_pct": 100.0,
    })
    return {
        "flights": [steps], "max_slots": 32,
        "model_config": load(DIRECTORY + "/config.json"),
    }


@pytest.mark.parametrize("name,want", [
    ("runner.denoise_pass_ms_p50", 11.0),
    ("diffusion.tokens_per_pass", 0.8),
    ("diffusion.commit_pass_share_pct", 20.0),
    ("moe.experts_read_per_layer", 127.0016),
])
def test_a_reader_reads_the_flight_records_new_fields(name, want):
    assert reader(name).read(a_window()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(set(NEW) - {"check.sdar_logit_err"}))
def test_a_reader_returns_nothing_from_a_program_without_the_fields(name):
    """The parent's records: steps of mode ``decode``, no pass counts.
    The line then leaves the metric out, and nothing raises."""
    parent = {
        "flights": [[
            {"mode": "decode", "dur_ms": 7.0, "slots_used": 32,
             "moe_read_pct": 90.0},
            {"mode": "prefill", "dur_ms": 50.0, "slots_used": 32},
        ]],
        "max_slots": 32, "model_config": {"num_experts": 128},
    }
    assert reader(name).read(parent) is None
    assert reader(name).read({"flights": [], "model_config": {}}) is None


def test_the_check_s_reader_reads_nothing_off_the_chip(tmp_path):
    read = reader("check.sdar_logit_err").read
    on_cpu = {
        "spec": {"local_path": DIRECTORY},
        "healths": [{"device": {"platform": "cpu"}}],
    }
    assert read(on_cpu) is None
    (tmp_path / "deployment.json").write_text(json.dumps({"name": "x"}))
    assert read({
        "spec": {"local_path": str(tmp_path)},
        "healths": [{"device": {"platform": "tpu"}}],
    }) is None
    assert read({"spec": {"local_path": str(tmp_path / "none")}}) is None


# ---- the reference and its check ---------------------------------------------------


def test_the_two_copies_of_the_reference_agree():
    with open(os.path.join(PB, "reference", "sdar_moe.py")) as f:
        theirs = f.read()
    with open(os.path.join(
        ROOT, "gpustack_tpu", "testing", "reference_sdar.py"
    )) as f:
        assert f.read() == theirs
    assert len(reference.FAULTS) == 6


SOUND = {
    "err": 0.2, "rows_err": 0.02, "score_err": 0.02, "score_narrow": 1e-4,
    "rerun": {"tokens_differ": 0},
}


@pytest.mark.parametrize("change,says", [
    ({}, None),
    ({"err": 5.0}, "logits"), ({"err": None}, "logits"),
    ({"rows_err": 1.4}, "key row"), ({"score_err": 0.5}, "scores"),
    ({"score_narrow": 1.0}, "bf16"),
    ({"rerun": {"tokens_differ": 2}}, "decided otherwise"),
])
def test_judge_holds_each_reading_to_its_limit(change, says):
    problems = check.judge({**SOUND, **change}, DEPLOYMENT)
    if says is None:
        assert problems == []
    else:
        assert len(problems) == 1 and says in problems[0]


@pytest.fixture(scope="module")
def small_check(tmp_path_factory):
    """``reference_check_sdar.py`` on the CPU with the tests' small
    configuration, sound and under every fault, in a child as the reader
    starts it."""
    d = tmp_path_factory.mktemp("sdar_small")
    (d / "config.json").write_text(json.dumps(sdar_small.HF))
    (d / "generation_config.json").write_text(
        json.dumps(sdar_small.GENERATION)
    )
    (d / "deployment.json").write_text(json.dumps({
        "name": "sdar-small",
        "model": {"quantization": "int8", "max_seq_len": 128, "max_slots": 4},
        "sdar_check": {
            "buckets": [64, 128], "tails": [0, 2], "passes": 11,
            "logit_tol": 0.5, "rows_tol": 0.3, "score_tol": 0.15,
            "narrow_tol": 0.05,
        },
    }))
    out = d / "out.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "reference_check_sdar.py"),
         "--config-dir", str(d), "--seed", "5", "--out", str(out),
         "--any-platform", "--fault", "," + ",".join(reference.FAULTS)],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(d / "cache")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return load(str(out))


def test_sound_programs_are_inside_every_limit(small_check):
    sound = small_check["by_fault"][""]
    assert sound["problems"] == [], sound
    assert sound["rerun"]["tokens_differ"] == 0
    assert sound["rerun"]["prefill"] == sound["rerun"]["logprob"] == 0.0
    # four cases: two buckets, P mod 4 of 0 and of 2; eleven passes each
    assert [c["prompt"] for c in sound["cases"]] == [48, 50, 112, 114]
    assert all(len(c["passes"]) == 11 for c in sound["cases"])
    assert 0 < sound["err"] < 0.5 and 0 < sound["rows_err"] < 0.3


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_is_outside_a_limit(small_check, fault):
    got = small_check["by_fault"][fault]
    assert got["problems"], (fault, got)
    if fault == "bf16_scores":
        # a computation in fewer bits than stated reads like a sound
        # program everywhere but in what it is kept in
        assert len(got["problems"]) == 1 and "bf16" in got["problems"][0]


def test_the_check_refuses_anything_but_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "reference_check_sdar.py"),
         "--config-dir", DIRECTORY, "--seed", "1",
         "--out", str(tmp_path / "o.json")],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 3 and "not a TPU" in proc.stderr
