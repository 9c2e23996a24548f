"""The Granite 4.0-H Micro configuration and its open-loop cell (PR 56):
the file is the catalog's row with nothing cut, the cell is among the
workloads and on the lists the issue names, every new metric has a
reader, each reader on a reduced stretch of this model's programs reads
a number and on Qwen3-8B's reads 0.0, and the comparison with the
reference judges a small model on the CPU as it judges the deployment on
the chip. "Among", never "last" or "exactly these": a later cell must
not flip this file (ROADMAP B0)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)
NAME = "granite-4.0-h-micro-int8"
DIRECTORY = os.path.join(PB, "configs", NAME)
CELL = NAME + ".chat-open"
SIBLING = "qwen3-8b-int8.chat-open"
SOURCE = (
    "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
    "config.json"
)
NEW = (
    "check.granite_logit_err", "ssm.state_update_share_pct.open",
    "cache.state_share_pct.open",
)
# neither declared nor shipped (PERF.md section 7: a reader cannot know
# how many slots were live at the traced calls of an open-loop cell, so
# the share could read over 100 %; a ``benchmark`` PR's)
UNDECLARED = "kernel.ssm_decode_roofline.open"
# the cell is held to tokens/s, not to the tail of the gaps (PERF.md
# section 2), so it stands on the lists of what moves tokens/s, as the
# other cells held to it, and on none of what moves ``itl_ms_p99``
TOKENS_LISTS = (
    "client.itl_ms_p99", "sched.occupancy_p50.closed",
    "runner.decode_step_ms_p50.closed", "device.idle_pct.closed",
    "device.peak_mem_gb.closed", "sched.host_ms_per_step_p50.closed",
)

from perfbench import loadgen, roofline_granite  # noqa: E402
from perfbench import reference_check_granite as check  # noqa: E402
from perfbench import run as bench_run  # noqa: E402


def load(path):
    with open(path) as f:
        return json.load(f)


def reader(name):
    return bench_run.load_reader(name)


# the catalog's entry (SOURCE), every scalar key of its config
PUBLISHED = {
    "model_type": "granitemoehybrid", "vocab_size": 100352,
    "hidden_size": 2048, "intermediate_size": 8192,
    "shared_intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "hidden_act": "silu", "max_position_embeddings": 131072,
    "attention_bias": False, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": True, "position_embedding_type": "nope",
    "layer_types": (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    ) * 4,
    "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8,
    "rope_scaling": None, "rope_theta": 10000,
}


def test_config_json_is_the_published_file_with_nothing_cut():
    cfg, dep = load(DIRECTORY + "/config.json"), load(DIRECTORY + "/deployment.json")
    assert dep["reduced"] == [] and dep["published"] == {}
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    # nothing else but the restored name
    assert set(cfg) - set(PUBLISHED) == {"architectures"}
    assert cfg["architectures"] == ["GraniteMoeHybridForCausalLM"]
    assert dep["source"] == SOURCE and dep["name"] == NAME
    assert dep["model"] == {
        "quantization": "int8", "max_seq_len": 2048, "max_slots": 64,
        "replicas": 1,
    }
    assert "one chip serving the whole model, one replica" in dep["stands_for"]
    assert dep["chips"] == 1 and set(dep["assumed"]) >= {
        "architectures", "state_dtype", "time_step_limit", "initialisation",
        "tokenizer", "quantization", "kv_rows_stored",
    }
    assert "other reading" in dep["assumed"]["state_dtype"]
    assert {"stands_for", "held_here", "sizes"} <= set(dep)
    assert "memory_analysis" in dep["sizes"]
    # the other models' readers start their own children: this file must
    # not ask for them
    assert not {
        "reference", "hybrid_check", "window_check", "linear_check"
    } & set(dep)
    assert "granite_check" in dep
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    (entry,) = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert entry["reduced"] == [] and "nothing cut" in entry["why"]
    assert entry["file"] == f"perfbench/configs/{NAME}/config.json"


def test_config_json_loads_to_the_published_widths():
    import dataclasses

    from gpustack_tpu.models.config import load_hf_config

    loaded = load_hf_config(DIRECTORY)
    cfg = dataclasses.asdict(loaded)
    want = {
        "hidden_size": 2048, "num_heads": 32, "num_kv_heads": 8,
        "head_dim": 64, "intermediate_size": 8192, "num_layers": 40,
        "vocab_size": 100352, "rms_norm_eps": 1e-5, "rope": False,
        "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
        "mamba_n_groups": 1, "conv_kernel": 4, "ssm_chunk_size": 256,
        "embed_multiplier": 12.0, "residual_multiplier": 0.22,
        "logit_scale": 0.125, "query_pre_attn_scalar": 4096.0,
        "tie_word_embeddings": True,
    }
    assert {k: cfg[k] for k in want} == want
    assert (loaded.num_mamba_layers, loaded.num_kv_layers) == (36, 4)
    assert round(loaded.param_count() / 1e9, 2) == 3.19
    assert loaded.kv_row_shapes == ((4, 128), (4, 128))


def test_the_traffic_is_the_open_loop_chat_mix_as_it_stands():
    mix = loadgen.load_traffic("chat-open", PB)
    dep = load(DIRECTORY + "/deployment.json")
    assert mix["loop"] == "open" and mix["arrivals"] == {"process": "poisson"}
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
        "max": 1024,
    }
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.8, "min": 16,
        "max": 512,
    }
    assert (mix["template_tokens"], mix["temperature"]) == (24, 1.0)
    assert (mix["tail_s"], mix["trace_steps"]) == (8.0, 16)
    cell = load(os.path.join(PB, "cells", CELL + ".json"))
    # four fifths of the swept knee, rounded down to a tenth, as the
    # issue named it; there and at three fifths the tail of the gaps
    # spread too widely to be bound, so the cell is held to tokens/s
    # (the file says what was tried, and what it is held to)
    assert cell["rate_rps"] == int(cell["knee_rps"] * 0.8 * 10 + 1e-9) / 10
    assert {"10.6", "7.9"} <= set(cell["tried"])
    assert "output_tok_s" in cell["held_to"] and "itl_ms_p99" in cell["held_to"]
    planned = loadgen.plan_open(mix, cell["rate_rps"], 50.0, 5600000001, 1.0)
    assert max(
        p.prompt_tokens + p.output_tokens for p in planned
    ) <= dep["model"]["max_seq_len"]
    assert loadgen.buckets_of(planned, 2048) == [32, 64, 128, 256, 512, 1024]
    assert set(dep["granite_check"]["buckets"]) <= set(
        loadgen.buckets_of(planned, 2048)
    )
    sweep = load(os.path.join(PB, "sweeps", CELL + ".json"))
    assert sweep["workload"] == CELL and sweep["slots"] == 64
    assert cell["knee_rps"] in [r["rate_rps"] for r in sweep["rows"]]


def test_the_cell_is_among_the_workloads_and_on_the_lists_the_issue_names():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {
        "name": CELL, "config": NAME, "traffic": "chat-open", "chips": 1,
        "why": cell["why"],
    }
    assert len(cell["why"]) <= 200 and "nothing cut" in cell["why"]
    assert "prefill" in cell["why"] and "stalls all" in cell["why"]
    assert "10.6 req/s = 4/5 of the knee" in cell["why"]
    mine = lambda g: {  # noqa: E731
        m["name"] for m in bench[g] if CELL in m.get("workloads", [CELL])
    }
    assert mine("end_to_end") == {"output_tok_s", "setup_s"}
    start = {m["name"] for m in bench["per_layer"] if m["moves"] == "setup_s"}
    assert mine("per_layer") - start == set(TOKENS_LISTS) | set(NEW)
    # a listed cell reports the end-to-end metric the list's metric
    # moves: none of its sibling open-loop cell's lists, which all move
    # the tail of the gaps (the window's log line still has every
    # client-side number)
    theirs = {
        m["name"] for m in bench["per_layer"]
        if SIBLING in m.get("workloads", [])
    }
    assert not theirs & mine("per_layer")
    assert {
        m["moves"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [])
    } == {"output_tok_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "output_tok_s"
        assert m["layer"] in layers
        assert callable(reader(name).read)
    # the full name's file is found before the sibling's, which knows a
    # state-space layer by another family's key; what /healthz says of
    # the slots' bytes is one reader for both parts of the quantity
    for name in NEW[:2]:
        assert bench_run.reader_path(name).endswith(name + ".py")
    assert bench_run.reader_path(NEW[2]).endswith("cache.state_share_pct.py")
    assert UNDECLARED not in by_name
    assert not os.path.exists(
        os.path.join(PB, "layer_metrics", UNDECLARED + ".py")
    )
    # one configuration, one cell
    assert [w["name"] for w in bench["workloads"] if w["config"] == NAME] == [CELL]


# ---- which layers keep a state, from either family's file ----

@pytest.mark.parametrize("name,layers", [
    (NAME, 36), ("nemotron-3-nano-30b-a3b-int8-ep8", 23),
    ("olmo-hybrid-7b-int8", 0), ("qwen3-8b-int8", 0),
])
def test_the_mamba_layers_are_counted_from_either_family_s_file(name, layers):
    hf = load(os.path.join(PB, "configs", name, "config.json"))
    assert roofline_granite.mamba_layers(hf) == layers


# ---- the readers ----

UPDATE = (
    "%ssm_state_update.7 = (f32[36,64,64,64,128]{4,3,2,1,0:T(8,128)}, "
    "f32[64,64,64]{2,1,0:T(8,128)}) custom-call(%a, %b, %c, %d, %e, %f, %g, %h)"
)
QWEN = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))


def stretch(decode_ms, kernel_ms_a_call, steps=4, kernel=UPDATE):
    ops = {}
    if kernel:
        ops[kernel] = {
            "count": steps, "total_ns": steps * kernel_ms_a_call * 1e6,
            "median_ns": kernel_ms_a_call * 1e6,
        }
    return {"devices": [{
        "ops": ops,
        "module_events": [
            ["jit__decode_impl", i * decode_ms * 1e6, decode_ms * 1e6]
            for i in range(steps)
        ] + [["jit_prefill_512", 1e9, 50e6]],
    }]}


def ctx_of(trace, config=None):
    return {
        "model_config": config or load(DIRECTORY + "/config.json"),
        "traces": [trace], "flights": [[]], "buckets": [256, 512, 1024],
    }


def test_the_update_s_share_is_its_calls_over_the_decode_programs():
    read = reader("ssm.state_update_share_pct.open").read
    # one call of 0.5 ms in each decode program of 10 ms
    assert read(ctx_of(stretch(10.0, 0.5))) == pytest.approx(5.0)
    # a model without state-space layers: 0.0, the truth of it
    assert read(ctx_of(stretch(10.0, 0.5, kernel=None), QWEN)) == 0.0
    assert read(ctx_of(stretch(10.0, 0.5), QWEN)) == 0.0
    # this model with no such call in the stretch (a renamed kernel, the
    # XLA form, no decode step): nothing, so the capture is retaken
    assert read(ctx_of(stretch(10.0, 0.5, kernel=None))) is None
    renamed = UPDATE.replace("ssm_state_update", "ssm_update")
    assert read(ctx_of(stretch(10.0, 0.5, kernel=renamed))) is None
    assert read({"model_config": load(DIRECTORY + "/config.json")}) is None
    # the sibling's reader knows a state-space layer by another key and
    # reads 0.0 of this model: why this cell has a reader of its own
    theirs = reader("ssm.state_update_share_pct").read
    assert theirs(ctx_of(stretch(10.0, 0.5))) == 0.0


def test_the_state_s_share_of_the_slots_memory_is_healthz_s():
    read = reader("cache.state_share_pct.open").read
    state = 64 * 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    rows = 64 * 4 * 2 * 4 * 128 * 2 * 2048
    health = {"cache": {
        "kv_bytes": rows, "state_bytes": state, "state_dtype": "float32",
    }}
    assert round(state / 1e9, 2) == 4.89 and round(rows / 1e9, 2) == 1.07
    assert round(read({"healths": [health]}), 1) == 82.0
    assert read({"healths": [{}]}) is None
    dense = {"cache": {"kv_bytes": rows, "state_bytes": 0}}
    assert read({"healths": [dense]}) == 0.0


def test_the_reference_check_starts_nothing_off_the_chip(monkeypatch):
    mod = reader("check.granite_logit_err")

    def no_child(*a, **k):
        raise AssertionError("started a child")

    monkeypatch.setattr(mod.subprocess, "run", no_child)
    cpu = {"device": {"platform": "cpu"}}
    tpu = {"device": {"platform": "tpu"}}
    assert mod.read({"spec": {"local_path": DIRECTORY}, "healths": [cpu]}) is None
    for other in ("ax-k1-int8-ep16-l12", "nemotron-3-nano-30b-a3b-int8-ep8",
                  "command-a-plus-int8-ep8-l8", "olmo-hybrid-7b-int8"):
        theirs = os.path.join(PB, "configs", other)
        assert mod.read({"spec": {"local_path": theirs}, "healths": [tpu]}) is None
    rehearsal = os.path.join(PB, "rehearsal", "tiny-qwen3")
    assert mod.read({"spec": {"local_path": rehearsal}, "healths": [tpu]}) is None
    # and the other models' readers start nothing for this configuration
    for name in ("check.reference_logit_err", "check.hybrid_logit_err",
                 "check.window_logit_err", "check.delta_logit_err"):
        theirs = reader(name)
        monkeypatch.setattr(theirs.subprocess, "run", no_child)
        assert theirs.read(
            {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}
        ) is None


SOUND = {"err": 0.02, "state_err": 0.1, "state_narrow": 0.0001}


@pytest.mark.parametrize("change,says", [
    ({}, None),
    ({"err": 5.0}, "logits"),
    ({"err": float("nan")}, "logits"),
    ({"err": None}, "logits"),
    ({"state_err": 0.9}, "recurrent state"),
    ({"state_narrow": 1.0}, "not kept in float32"),
])
def test_the_judge_holds_each_reading_to_its_limit(change, says):
    dep = load(DIRECTORY + "/deployment.json")
    problems = check.judge({**SOUND, **change}, dep)
    if says is None:
        assert problems == []
    else:
        assert len(problems) == 1 and says in problems[0]


def test_the_reader_fails_the_run_outside_a_limit(monkeypatch, tmp_path):
    from perfbench.cluster import BenchFailure

    mod = reader("check.granite_logit_err")
    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    monkeypatch.setattr(mod.sys, "argv", ["run.py", "--seed", "5600000123"])
    tpu = {"device": {"platform": "tpu"}}
    ctx = {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}

    def child(got):
        def run(argv, **kw):
            assert argv[1].endswith("reference_check_granite.py")
            out = argv[argv.index("--out") + 1]
            assert "5600000123" in out and argv[argv.index("--seed") + 1] == "5600000123"
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                json.dump({**got, "seconds": {"all": 1.0}}, f)
            return type("P", (), {"returncode": 0, "stderr": ""})()
        return run

    monkeypatch.setattr(mod.subprocess, "run", child(SOUND))
    assert mod.read(ctx) == 0.02
    monkeypatch.setattr(mod.subprocess, "run", child({**SOUND, "state_err": 0.9}))
    with pytest.raises(BenchFailure, match="recurrent state"):
        mod.read(ctx)
    failed = lambda argv, **kw: type(  # noqa: E731
        "P", (), {"returncode": 3, "stderr": "on cpu"}
    )()
    monkeypatch.setattr(mod.subprocess, "run", failed)
    with pytest.raises(BenchFailure, match="exited with 3"):
        mod.read(ctx)


def check_faults():
    from perfbench.reference import granite_hybrid

    return tuple(granite_hybrid.FAULTS)


def test_the_reference_imports_no_model_code_and_no_other_reference():
    with open(os.path.join(PB, "reference", "granite_hybrid.py")) as f:
        text = f.read()
    lines = [ln for ln in text.splitlines() if ln.startswith(("import", "from"))]
    assert lines and all(
        "gpustack_tpu" not in ln and "perfbench" not in ln for ln in lines
    ), lines
    assert {
        "bf16_state", "no_embedding_multiplier", "no_residual_multiplier",
        "no_logits_scaling", "attention_scale_sqrt", "bc_a_head",
    } <= set(check_faults())


def test_every_fault_measured_on_the_chip_fails_through_the_judge():
    """``perfbench/check_noise/``'s table for this configuration: the
    sound readings pass the judge under the limits ``deployment.json``
    states, and each of the faults fails it."""
    dep = load(DIRECTORY + "/deployment.json")
    table = load(os.path.join(PB, "check_noise", NAME + ".reference.json"))
    assert table["config"] == NAME and table["platform"] == "tpu"
    assert len(table["sound"]) >= 3
    for run in table["sound"]:
        assert check.judge(run, dep) == [], run
    faults = table["faults"]
    assert set(faults) == set(check_faults())
    for name, readings in faults.items():
        for got in readings:
            assert check.judge(got, dep), name
            assert got["problems"] == check.judge(got, dep)


def test_the_table_shows_why_a_sound_state_reads_a_tenth():
    """The controls of ``perfbench/check_noise/``'s table (the review of
    PR 56 asked for the cause shown, not asserted): the equations with
    bf16 activations alone are as far from the plain ones as the sound
    program reads, and the program with float32 activations is closer by
    more than half."""
    dep = load(DIRECTORY + "/deployment.json")
    table = load(os.path.join(PB, "check_noise", NAME + ".reference.json"))
    runs = table["controls"]["runs"]
    assert len(runs) >= 2
    for run in runs:
        sound = run["program"]
        assert check.judge(sound, dep) == []
        alone = run["rounded_equations_against_plain"]
        assert 0.8 < alone["state_err"] / sound["state_err"] < 1.25
        assert 0.8 < alone["err"] / sound["err"] < 1.25
        wide = run["program_with_float32_activations"]
        assert wide["state_err"] < 0.5 * sound["state_err"]
        assert wide["err"] < 0.5 * sound["err"]
        assert check.judge(wide, dep) == []
    assert table["limits"] == {
        k: dep["granite_check"][k]
        for k in ("logit_tol", "state_tol", "narrow_tol")
    }


def test_the_check_compares_the_runner_with_the_reference_on_a_small_model(
    tmp_path, monkeypatch
):
    """``reference_check_granite.py`` whole, on the CPU: a small stack in
    float32 (the CPU's bf16 products accumulate in bf16, which is no
    chip's rounding), two padded prompts a bucket through the runner's
    prefill, insert with the state, eight decode steps; sound, every
    fault over a limit, and the control (the reference with bf16
    activations) as far from this float32 program as the chip's bf16
    program is from the float32 reference."""
    hf = {
        "architectures": ["GraniteMoeHybridForCausalLM"],
        "model_type": "granitemoehybrid",
        "vocab_size": 264, "hidden_size": 256, "intermediate_size": 128,
        "shared_intermediate_size": 128, "num_hidden_layers": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "hidden_act": "silu", "max_position_embeddings": 512,
        "attention_bias": False, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
        "mamba_n_heads": 32, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 16, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "normalization_function": "rmsnorm",
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8,
    }
    dep = {
        "name": "tiny-granite-hybrid",
        "model": {"quantization": "", "max_seq_len": 128, "max_slots": 4},
        "granite_check": {
            "buckets": [32, 64], "prompts": 2, "steps": 8,
            "logit_tol": 0.002, "state_tol": 0.002, "narrow_tol": 0.05,
        },
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with open(tmp_path / "deployment.json", "w") as f:
        json.dump(dep, f)
    out = tmp_path / "out.json"
    faults = ",".join(("",) + check_faults() + ("bf16_activations",))
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "5600000007", "--out",
        str(out), "--any-platform", "--fault", faults,
        "--activations", "float32",
    ]) == 0
    got = load(out)
    assert got["activations"] == "float32"
    assert got["problems"] == [], got
    assert len(got["cases"]) == 4 and got["steps"] == 8
    assert {c["bucket"] for c in got["cases"]} == {32, 64}
    assert all(c["n"] < c["bucket"] for c in got["cases"])     # padded
    for name in check_faults():
        assert got["by_fault"][name]["problems"], name
    # the control: bf16 activations alone, round a state that stays
    # float32, move it as far from the float32 equations as the chip's
    # sound program reads (``state_err`` a tenth), and show in nothing
    # that is kept: this float32 program is that far from a reference
    # whose activations are rounded
    control = got["by_fault"]["bf16_activations"]
    assert control["state_err"] > 20 * max(got["state_err"], 1e-4), control
    assert control["state_narrow"] < 0.05
    # ... and with no program between them: the rounded equations
    # against the plain ones read the same distance
    alone = control["vs_reference"]
    assert alone["state_err"] == pytest.approx(control["state_err"], rel=0.05)
    # (its logits over the whole vocabulary at every position, the
    # program's a step's 20 highest: not the same number)
    assert alone["err"] > 20 * got["err"]
    # off a TPU, and not asked otherwise: no number under this name
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "1", "--out", str(out),
    ]) == 3


def test_the_calibration_file_moves_no_bound():
    cal = load(os.path.join(PB, "calibration", CELL + ".json"))
    assert cal["cell"] == CELL and cal["pr"] == 56
    assert not cal.get("sets_bounds")
    assert len(cal["sets"]) == 2
    seeds = [s for k in cal["sets"].values() for s in k["seeds"]]
    assert len(seeds) == 12 and len(set(seeds)) >= 6
    assert set(cal["metrics"]) == {"output_tok_s", "setup_s"}
