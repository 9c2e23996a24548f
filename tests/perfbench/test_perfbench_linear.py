"""The Olmo-Hybrid-7B configuration and its cell (PR 53): the file is the
catalog's row with nothing cut, the cell is on the lists the issue names
as far as the tests that were here let it be, its readers read what its
traffic can give and nothing from what it cannot, ``roofline_delta.py``
counts on hand-worked shapes, and the comparison with the reference
judges a small model on the CPU as it judges the deployment on the
chip."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)
NAME = "olmo-hybrid-7b-int8"
DIRECTORY = os.path.join(PB, "configs", NAME)
CELL = NAME + ".reason-closed-12"
SIBLING = "nemotron-3-nano-30b-a3b-int8-ep8.reason-closed"

from perfbench import loadgen, roofline, roofline_delta  # noqa: E402
from perfbench import reference_check_linear as check  # noqa: E402


def load(path):
    with open(path) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(PB, "layer_metrics", name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the catalog's entry (source_url below), every key of its config
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"


def test_config_json_is_the_published_file_with_nothing_cut():
    cfg, dep = load(DIRECTORY + "/config.json"), load(DIRECTORY + "/deployment.json")
    assert dep["reduced"] == [] and dep["published"] == {}
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    # nothing else but the restored name
    assert set(cfg) - set(PUBLISHED) == {"architectures"}
    assert cfg["architectures"] == ["OlmoHybridForCausalLM"]
    assert dep["source"] == SOURCE and dep["name"] == NAME
    assert dep["model"] == {
        "quantization": "int8", "max_seq_len": 2560, "max_slots": 12,
        "replicas": 1,
    }
    assert dep["chips"] == 1 and set(dep["assumed"]) >= {
        "norm_placement", "rotary", "state_dtype", "initialisation",
        "architectures", "kv_heads_stored",
    }
    # each assumption names its other reading
    for key in ("norm_placement", "rotary", "state_dtype"):
        assert "other reading" in dep["assumed"][key], key
    assert {"stands_for", "held_here", "sizes"} <= set(dep)
    assert "memory_analysis" in dep["sizes"]
    # the other models' readers start their own children: this file must
    # not ask for them
    assert not {"reference", "hybrid_check", "window_check"} & set(dep)
    assert "linear_check" in dep
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = bench["configs"][-1]
    assert entry["name"] == NAME and len(bench["configs"]) == 6
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert entry["reduced"] == []
    assert entry["file"] == f"perfbench/configs/{NAME}/config.json"


def test_config_json_loads_to_the_published_widths():
    import dataclasses

    from gpustack_tpu.models.config import load_hf_config

    loaded = load_hf_config(DIRECTORY)
    cfg = dataclasses.asdict(loaded)
    want = {
        "hidden_size": 3840, "num_heads": 30, "num_kv_heads": 30,
        "head_dim": 128, "intermediate_size": 11008, "num_layers": 32,
        "vocab_size": 100352, "rms_norm_eps": 1e-6, "rope": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "qk_norm_whole": True, "norm_after": ("full_attention",),
        "tie_word_embeddings": False,
    }
    assert {k: cfg[k] for k in want} == want
    assert (loaded.num_linear_layers, loaded.num_kv_layers) == (24, 8)
    assert round(loaded.param_count() / 1e9, 2) == 7.43


def test_the_traffic_is_reason_closed_with_a_client_a_slot():
    mix = loadgen.load_traffic("reason-closed-12", PB)
    theirs = loadgen.load_traffic("reason-closed", PB)
    dep = load(DIRECTORY + "/deployment.json")
    assert (mix["clients"], mix["pool"], mix["round"]) == (12, 64, 16)
    assert mix["clients"] == dep["model"]["max_slots"]
    # reason-closed as it stands, but for the clients
    same = set(theirs) - {"name", "clients", "schedule"}
    assert {k: mix[k] for k in same} == {k: theirs[k] for k in same}
    planned = loadgen.plan_requests(mix, 64, seed=5300000001)
    prompts = [p.prompt_tokens for p in planned]
    outputs = [p.output_tokens for p in planned]
    assert min(prompts) >= 128 and max(prompts) <= 1000
    assert min(outputs) >= 384 and max(outputs) <= 1536
    assert max(prompts) + max(outputs) < dep["model"]["max_seq_len"]
    assert loadgen.buckets_of(planned, 2560) == [256, 512, 1024]
    assert dep["linear_check"]["buckets"] == [512, 1024]
    other = loadgen.plan_requests(mix, 64, seed=7)
    for r in range(4):
        assert sorted(prompts[16 * r:16 * r + 16]) == sorted(
            p.prompt_tokens for p in other[16 * r:16 * r + 16]
        )


def test_the_cell_is_on_the_lists_of_what_moves_its_tokens():
    """A full batch in a closed loop is judged on its tokens per second,
    as its sibling is. ``cache.state_share_pct`` is left to the sibling
    alone: ``test_perfbench_hybrid.py`` holds that list to one cell, and
    no file that is there is edited (the share is ``/healthz``'s, 14.0 at
    12 slots of 2,560: PERF.md section 4)."""
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench["workloads"][-1]
    assert cell == {
        "name": CELL, "config": NAME, "traffic": "reason-closed-12",
        "chips": 1, "why": cell["why"],
    }
    assert len(bench["workloads"]) == 7
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert len(cell["why"]) <= 200 and "nothing cut" in cell["why"]
    assert "12 clients on 12 slots" in cell["why"]
    mine = lambda g: {  # noqa: E731
        m["name"] for m in bench[g] if CELL in m.get("workloads", [CELL])
    }
    assert mine("end_to_end") == {"output_tok_s", "setup_s"}
    start = {m["name"] for m in bench["per_layer"] if m["moves"] == "setup_s"}
    assert mine("per_layer") - start == {
        "client.itl_ms_p99", "sched.occupancy_p50.closed",
        "runner.decode_step_ms_p50.closed", "device.idle_pct.closed",
        "device.peak_mem_gb.closed", "check.delta_logit_err",
        "delta.state_update_share_pct",
    }
    assert {
        m["moves"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [])
    } == {"output_tok_s"}
    # appended: every list that has the cell has it last
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if CELL in m.get("workloads", []):
                assert m["workloads"][-1] == CELL, m["name"]
    new = bench["per_layer"][-2:]
    assert [m["name"] for m in new] == [
        "check.delta_logit_err", "delta.state_update_share_pct"
    ]
    layers = {m["layer"] for m in bench["per_layer"][:-2]}
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "output_tok_s"
        assert m["layer"] in layers
        assert callable(reader(m["name"]).read)
    # the update's roofline has its reader and is not declared (the
    # stretch test holds every declared device_trace metric to Qwen3-8B's
    # trace: PERF.md section 7), beside the MLA, SSM and window pairs
    by_name = {m["name"] for m in bench["per_layer"]}
    assert "kernel.delta_decode_roofline" not in by_name
    assert callable(reader("kernel.delta_decode_roofline").read)
    # one configuration, one cell
    assert [w["name"] for w in bench["workloads"] if w["config"] == NAME] == [CELL]


# ---- roofline_delta.py on hand-worked shapes ----

def test_the_update_moves_each_live_slot_s_state_in_and_out():
    w = roofline_delta.widths(load(DIRECTORY + "/config.json"))
    assert w == {
        "heads": 30, "key": 96, "value": 192, "chunk": 64, "layers": 24,
    }
    call = roofline_delta.delta_update_call(12, 30, 96, 192)
    state = 30 * 96 * 192
    assert state * 4 == 2_211_840
    assert call["bytes"] == 12 * (
        2 * state * 4 + 4 * (2 * 96 * 30 + 4 * 30 * 192)
    )
    assert call["flops"] == 7 * 12 * state
    assert round(call["bytes"] / 1e6, 1) == 54.5
    none = roofline_delta.delta_update_call(0, 30, 96, 192)
    assert none == {"flops": 0.0, "bytes": 0.0}
    peaks = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]
    least = roofline.least_seconds(call["flops"], call["bytes"], peaks)
    assert least["bound"] == "memory" and 65e-6 < least["seconds"] < 68e-6


def test_the_chunked_form_counts_whole_chunks():
    a = roofline_delta.delta_scan_call(1024, 30, 96, 192, 64)
    b = roofline_delta.delta_scan_call(1000, 30, 96, 192, 64)
    assert a == b
    per_position = (
        4 * 64 * 96 + 2 * 64 * (192 + 96) + 6 * 96 * 192 + 2 * 64 * 192
    )
    assert a["flops"] == 1024 * 30 * per_position
    assert a["bytes"] == 1024 * 30 * (2 * 96 + 2 * 192) * 4 + 8 * 1024 * 30
    peaks = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]
    # 6.1 GFLOP against 71 MB of float32: the memory binds
    least = roofline.least_seconds(a["flops"], a["bytes"], peaks)
    assert least["bound"] == "memory" and 80e-6 < least["seconds"] < 95e-6


# ---- the readers ----

UPDATE = (
    "%delta_state_update.7 = (f32[24,12,96,5760]{3,2,1,0:T(8,128)}, "
    "f32[12,1,5760]{2,1,0:T(1,128)}) custom-call(%a, %b, %c, %d, %e, %f, %g)"
)
PEAKS = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]


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


def ctx_of(trace, config=None, records=()):
    return {
        "model_config": config or load(DIRECTORY + "/config.json"),
        "peaks": PEAKS, "traces": [trace], "flights": [list(records)],
        "buckets": [256, 512, 1024],
    }


def test_the_update_s_share_is_its_calls_over_the_decode_programs():
    read = reader("delta.state_update_share_pct").read
    # one call of 0.5 ms in each decode program of 10 ms
    assert read(ctx_of(stretch(10.0, 0.5))) == pytest.approx(5.0)
    # a model without linear-attention layers: 0.0, the truth of it
    for other in ("qwen3-8b-int8", "nemotron-3-nano-30b-a3b-int8-ep8"):
        theirs = load(os.path.join(PB, "configs", other, "config.json"))
        assert read(ctx_of(stretch(10.0, 0.5, kernel=None), theirs)) == 0.0
        assert read(ctx_of(stretch(10.0, 0.5), theirs)) == 0.0
    # this model with no such call in the stretch (a renamed kernel, the
    # XLA form, no decode step): nothing, so the capture is retaken
    assert read(ctx_of(stretch(10.0, 0.5, kernel=None))) is None
    renamed = UPDATE.replace("delta_state_update", "delta_update")
    assert read(ctx_of(stretch(10.0, 0.5, kernel=renamed))) is None
    assert read({"model_config": load(DIRECTORY + "/config.json")}) is None
    # and the sibling's reader reads 0.0 of this model, not nothing
    theirs = reader("ssm.state_update_share_pct").read
    assert theirs(ctx_of(stretch(10.0, 0.5))) == 0.0


def record(mode, slots):
    return {"mode": mode, "slots_used": slots, "prompt_tokens": 0, "admitted": []}


def test_the_undeclared_roofline_reads_this_model_s_programs():
    decode = reader("kernel.delta_decode_roofline").read
    least = roofline_delta.delta_update_call(12, 30, 96, 192)["bytes"] / 819e9
    ctx = ctx_of(stretch(15.0, 2 * least * 1e3), records=[record("decode", 12)] * 5)
    assert decode(ctx) == pytest.approx(50.0, rel=1e-3)
    # half the slots live: half the bytes to move
    half = ctx_of(stretch(15.0, 2 * least * 1e3), records=[record("decode", 6)] * 5)
    assert decode(half) == pytest.approx(25.0, rel=1e-3)
    assert decode(ctx_of(stretch(15.0, 1.0, kernel=None),
                         records=[record("decode", 12)])) is None
    assert decode(ctx_of(stretch(15.0, 1.0))) is None        # no decode record


def test_the_state_s_share_of_the_slots_memory_is_healthz_s():
    """Not declared for this cell (the list is the sibling's alone); the
    reader reads this model's ``/healthz`` all the same."""
    read = reader("cache.state_share_pct").read
    state = 12 * 24 * (96 * 5760 * 4 + 3 * 11520 * 2)
    rows = 12 * 8 * 2 * 32 * 128 * 2 * 2560
    health = {"cache": {
        "kv_bytes": rows, "state_bytes": state, "state_dtype": "float32",
    }}
    assert round(state / 1e9, 3) == 0.657 and round(rows / 1e9, 2) == 4.03
    assert round(read({"healths": [health]}), 1) == 14.0


def test_the_reference_check_starts_nothing_off_the_chip(monkeypatch):
    mod = reader("check.delta_logit_err")

    def no_child(*a, **k):
        raise AssertionError("started a child")

    monkeypatch.setattr(mod.subprocess, "run", no_child)
    cpu = {"device": {"platform": "cpu"}}
    tpu = {"device": {"platform": "tpu"}}
    assert mod.read({"spec": {"local_path": DIRECTORY}, "healths": [cpu]}) is None
    for other in ("ax-k1-int8-ep16-l12", "nemotron-3-nano-30b-a3b-int8-ep8",
                  "command-a-plus-int8-ep8-l8"):
        theirs = os.path.join(PB, "configs", other)
        assert mod.read({"spec": {"local_path": theirs}, "healths": [tpu]}) is None
    # and the other models' readers start nothing for this configuration
    for name in ("check.reference_logit_err", "check.hybrid_logit_err",
                 "check.window_logit_err"):
        theirs = reader(name)
        monkeypatch.setattr(theirs.subprocess, "run", no_child)
        assert theirs.read(
            {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}
        ) is None


SOUND = {"err": 0.1, "state_err": 0.005, "state_narrow": 0.0001}


@pytest.mark.parametrize("change,says", [
    ({}, None),
    ({"err": 0.9}, "logits"),
    ({"err": float("nan")}, "logits"),
    ({"err": None}, "logits"),
    ({"state_err": 0.5}, "recurrent state"),
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

    mod = reader("check.delta_logit_err")
    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    monkeypatch.setattr(mod.sys, "argv", ["run.py", "--seed", "5300000123"])
    tpu = {"device": {"platform": "tpu"}}
    ctx = {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}

    def child(got):
        def run(argv, **kw):
            assert argv[1].endswith("reference_check_linear.py")
            out = argv[argv.index("--out") + 1]
            assert "5300000123" in out and argv[argv.index("--seed") + 1] == "5300000123"
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                json.dump({**got, "seconds": {"all": 1.0}}, f)
            return type("P", (), {"returncode": 0, "stderr": ""})()
        return run

    monkeypatch.setattr(mod.subprocess, "run", child(SOUND))
    assert mod.read(ctx) == 0.1
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
    from perfbench.reference import olmo_hybrid

    return tuple(olmo_hybrid.FAULTS)


def test_every_fault_measured_on_the_chip_fails_through_the_judge():
    """``perfbench/check_noise/``'s table for this configuration: the
    sound readings pass the judge under the limits ``deployment.json``
    states, and each of the nine faults fails it."""
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


def test_the_check_compares_the_runner_with_the_reference_on_a_small_model(
    tmp_path, monkeypatch
):
    """``reference_check_linear.py`` whole, on the CPU: a small stack in
    float32 (the CPU's bf16 products accumulate in bf16, which is no
    chip's rounding: 0.85 nats at these widths), two padded prompts a
    bucket through the runner's prefill, insert with the state, eight
    decode steps; sound, and every fault over a limit."""
    import dataclasses

    from gpustack_tpu.models import config as models_config

    read = models_config.load_hf_config
    monkeypatch.setattr(
        models_config, "load_hf_config",
        lambda d: dataclasses.replace(read(d), dtype="float32"),
    )
    hf = {
        "architectures": ["OlmoHybridForCausalLM"], "model_type": "olmo_hybrid",
        "vocab_size": 264, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 4, "hidden_act": "silu",
        "max_position_embeddings": 256, "attention_bias": False,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_key_head_dim": 6, "linear_value_head_dim": 12,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    dep = {
        "name": "tiny-olmo-hybrid",
        "model": {"quantization": "", "max_seq_len": 128, "max_slots": 4},
        "linear_check": {
            "buckets": [32, 64], "prompts": 2, "steps": 8,
            "logit_tol": 0.02, "state_tol": 0.02, "narrow_tol": 0.05,
        },
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with open(tmp_path / "deployment.json", "w") as f:
        json.dump(dep, f)
    out = tmp_path / "out.json"
    faults = ",".join(("",) + check_faults())
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "5300000007", "--out",
        str(out), "--any-platform", "--fault", faults,
    ]) == 0
    got = load(out)
    assert got["problems"] == [], got
    assert len(got["cases"]) == 4 and got["steps"] == 8
    assert {c["bucket"] for c in got["cases"]} == {32, 64}
    assert all(c["n"] < c["bucket"] for c in got["cases"])     # padded
    for name in check_faults():
        assert got["by_fault"][name]["problems"], name
    # off a TPU, and not asked otherwise: no number under this name
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "1", "--out", str(out),
    ]) == 3


def test_the_calibration_file_moves_no_bound():
    cal = load(os.path.join(PB, "calibration", CELL + ".json"))
    assert cal["cell"] == CELL and cal["pr"] == 53
    assert not cal.get("sets_bounds")
    assert len(cal["sets"]) == 2
    seeds = [s for k in cal["sets"].values() for s in k["seeds"]]
    assert len(seeds) == 12
    assert set(cal["metrics"]) == {"output_tok_s", "setup_s"}
