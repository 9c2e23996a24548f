"""The Nemotron-3-Nano configuration and its cell (PR 46): the file is
the published model with only the listed cuts, the cell is on the lists
the issue names and no others, its readers read what its traffic can give
and nothing from what it cannot, ``roofline_ssm.py`` counts on hand-worked
shapes, and the comparison with the reference judges a small model on the
CPU as it judges the deployment on the chip."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)
NAME = "nemotron-3-nano-30b-a3b-int8-ep8"
DIRECTORY = os.path.join(PB, "configs", NAME)
CELL = NAME + ".reason-closed"

from perfbench import loadgen, roofline, roofline_ssm  # noqa: E402
from perfbench import reference_check_hybrid as check  # noqa: E402


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
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
CUT = {"n_routed_experts": 16, "vocab_size": 16384}
SOURCE = (
    "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
    "blob/main/config.json"
)


def test_config_json_is_the_published_file_but_for_the_listed_cuts():
    cfg, dep = load(DIRECTORY + "/config.json"), load(DIRECTORY + "/deployment.json")
    assert sorted(dep["reduced"]) == sorted(CUT)
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    # nothing else but the restored name and the share
    assert set(cfg) - set(PUBLISHED) == {"architectures", "experts_held"}
    assert cfg["architectures"] == ["NemotronHForCausalLM"]
    assert cfg["experts_held"] == {"of": 128, "first": 0}
    assert dep["published"] == {k: PUBLISHED[k] for k in CUT}
    assert dep["source"] == SOURCE and dep["name"] == NAME
    assert dep["model"] == {
        "quantization": "int8", "max_seq_len": 4096, "max_slots": 32,
        "replicas": 1,
    }
    assert dep["chips"] == 1 and set(dep["assumed"]) >= {
        "router", "rotary", "state_dtype", "time_step_limit",
    }
    assert {"stands_for", "held_here", "sizes"} <= set(dep)
    # A.X-K1's reader starts A.X-K1's child: this file must not ask for it
    assert "reference" not in dep and "hybrid_check" in dep
    # a pattern of 52 layers whole, 8 experts or more, an eighth of the
    # vocabulary
    pattern = cfg["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        23, 23, 6
    )
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert entry["reduced"] == dep["reduced"]
    assert entry["file"] == f"perfbench/configs/{NAME}/config.json"


def test_config_json_loads_to_the_published_widths():
    import dataclasses

    from gpustack_tpu.models.config import load_hf_config

    cfg = dataclasses.asdict(load_hf_config(DIRECTORY))
    want = {
        "hidden_size": 2688, "num_heads": 32, "num_kv_heads": 2,
        "head_dim": 128, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "mamba_n_groups": 8, "conv_kernel": 4,
        "ssm_chunk_size": 128, "moe_intermediate_size": 1856,
        "shared_expert_intermediate_size": 3712, "num_experts": 128,
        "num_experts_per_tok": 6, "experts_held": 16, "first_held_expert": 0,
        "routed_scaling_factor": 2.5, "moe_scoring": "sigmoid",
        "moe_act": "relu2", "rope": False, "num_layers": 52,
        "vocab_size": 16384, "rms_norm_eps": 1e-5,
    }
    assert {k: cfg[k] for k in want} == want


def test_the_traffic_is_the_issue_s_and_fills_the_slots():
    mix = loadgen.load_traffic("reason-closed", PB)
    dep = load(DIRECTORY + "/deployment.json")
    assert (mix["clients"], mix["pool"], mix["round"]) == (32, 64, 16)
    assert mix["clients"] == dep["model"]["max_slots"]
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128, "max": 1000,
    }
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.4, "min": 384, "max": 1536,
    }
    assert (mix["temperature"], mix["trace_steps"]) == (1.0, 16)
    planned = loadgen.plan_requests(mix, 64, seed=4600000001)
    prompts = [p.prompt_tokens for p in planned]
    outputs = [p.output_tokens for p in planned]
    assert min(prompts) >= 128 and max(prompts) <= 1000
    assert min(outputs) >= 384 and max(outputs) <= 1536
    assert max(prompts) + max(outputs) < dep["model"]["max_seq_len"]
    assert loadgen.buckets_of(planned, 4096) == [256, 512, 1024]
    # the comparison with the reference runs the two largest of them
    assert dep["hybrid_check"]["buckets"] == [512, 1024]
    other = loadgen.plan_requests(mix, 64, seed=7)
    for r in range(4):
        assert sorted(prompts[16 * r:16 * r + 16]) == sorted(
            p.prompt_tokens for p in other[16 * r:16 * r + 16]
        )


def test_the_cell_is_on_the_lists_of_what_moves_its_tokens_and_no_others():
    """A full batch in a closed loop is judged on its tokens per second.
    ISSUE 46 had the cell on ``ttft_ms_p50`` too: over two sets of six
    seeds its median first token (about 100 a window, of three buckets'
    prefills) spread by 9.4 % / 4.7 %, which the check's half of the
    bound, 5 %, admits four times in ten (PERF.md section 6, PR 46). So
    the cell is off that metric, and with it off the lists of the five
    per-layer metrics that move it: every cell on a metric's list reports
    the end-to-end metric it moves."""
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = lambda g: {  # noqa: E731
        m["name"] for m in bench[g] if CELL in m.get("workloads", [CELL])
    }
    assert mine("end_to_end") == {"output_tok_s", "setup_s"}
    start = {m["name"] for m in bench["per_layer"] if m["moves"] == "setup_s"}
    assert mine("per_layer") - start == {
        "client.itl_ms_p99", "sched.occupancy_p50.closed",
        "runner.decode_step_ms_p50.closed", "device.idle_pct.closed",
        "device.peak_mem_gb.closed", "check.hybrid_logit_err",
        "ssm.state_update_share_pct", "cache.state_share_pct",
    }
    assert {
        m["moves"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [])
    } == {"output_tok_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for new in ("check.hybrid_logit_err", "ssm.state_update_share_pct",
                "cache.state_share_pct"):
        assert by_name[new]["workloads"] == [CELL]
        assert by_name[new]["moves"] == "output_tok_s"
        assert os.path.exists(os.path.join(PB, "layer_metrics", new + ".py"))
    # the two rooflines have their readers and are not declared (the
    # stretch test holds every declared device_trace metric to Qwen3-8B's
    # trace: PERF.md section 7)
    assert not {n for n in by_name if n.startswith("kernel.ssm_")}
    for undeclared in ("kernel.ssm_decode_roofline", "kernel.ssm_prefill_roofline"):
        assert os.path.exists(os.path.join(PB, "layer_metrics", undeclared + ".py"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason-closed"
    assert "32 clients on 32 slots" in cell["why"] and len(cell["why"]) <= 200
    # one configuration, one cell
    assert [w["name"] for w in bench["workloads"] if w["config"] == NAME] == [CELL]


# ---- roofline_ssm.py on hand-worked shapes ----

def test_the_update_moves_each_live_slot_s_state_in_and_out():
    w = roofline_ssm.widths(load(DIRECTORY + "/config.json"))
    assert w == {
        "heads": 64, "head_dim": 64, "state": 128, "groups": 8, "chunk": 128,
        "layers": 23,
    }
    call = roofline_ssm.ssm_update_call(32, 64, 64, 128, 8)
    state = 64 * 64 * 128
    assert call["bytes"] == 32 * (2 * state * 4 + 4 * (3 * 64 * 64 + 2 * 8 * 128))
    assert call["flops"] == 5 * 32 * state
    assert round(call["bytes"] / 1e6, 1) == 136.1
    none = roofline_ssm.ssm_update_call(0, 64, 64, 128, 8)
    assert none == {"flops": 0.0, "bytes": 0.0}
    peaks = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]
    least = roofline.least_seconds(call["flops"], call["bytes"], peaks)
    assert least["bound"] == "memory" and 160e-6 < least["seconds"] < 170e-6


def test_the_scan_counts_whole_chunks():
    a = roofline_ssm.ssm_scan_call(512, 64, 64, 128, 8, 128)
    b = roofline_ssm.ssm_scan_call(500, 64, 64, 128, 8, 128)
    assert a == b
    per_position = 2 * 128 * 128 * 8 + (2 * 64 + 1) * 128 * 64 + 4 * 64 * 128 * 64
    assert a["flops"] == 512 * per_position
    assert a["bytes"] == 512 * (2 * 64 * 64 + 2 * 8 * 128) * 2 + 4 * 512 * 64
    peaks = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]
    # 1.75 GFLOP against 10.6 MB: 8.9 and 12.9 us, the activations bind
    least = roofline.least_seconds(a["flops"], a["bytes"], peaks)
    assert least["bound"] == "memory" and 12e-6 < least["seconds"] < 14e-6


# ---- the readers ----

UPDATE = (
    "%ssm_state_update.7 = (f32[23,32,64,64,128]{4,3,2,1,0:T(8,128)}, "
    "f32[32,64,64]{2,1,0:T(8,128)}) custom-call(%a, %b, %c, %d, %e, %f, %g, %h)"
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
    read = reader("ssm.state_update_share_pct").read
    # one call of 0.5 ms in each decode program of 10 ms
    assert read(ctx_of(stretch(10.0, 0.5))) == pytest.approx(5.0)
    # a model without state-space layers: 0.0, the truth of it
    qwen = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))
    assert read(ctx_of(stretch(10.0, 0.5, kernel=None), qwen)) == 0.0
    assert read(ctx_of(stretch(10.0, 0.5), qwen)) == 0.0
    # a hybrid whose stretch holds no such call (a renamed kernel, the
    # XLA form, no decode step): nothing, so the capture is retaken
    assert read(ctx_of(stretch(10.0, 0.5, kernel=None))) is None
    renamed = UPDATE.replace("ssm_state_update", "ssm_update")
    assert read(ctx_of(stretch(10.0, 0.5, kernel=renamed))) is None
    assert read({"model_config": load(DIRECTORY + "/config.json")}) is None


def test_the_state_s_share_of_the_slots_memory_comes_from_healthz():
    read = reader("cache.state_share_pct").read
    state = 32 * 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    rows = 32 * 6 * 2 * 2 * 128 * 2 * 4096
    health = {"cache": {
        "kv_bytes": rows, "state_bytes": state, "state_dtype": "float32",
    }}
    assert round(read({"healths": [health]}), 1) == 66.1
    plain = {"cache": {"kv_bytes": rows, "state_bytes": 0, "state_dtype": None}}
    assert read({"healths": [plain]}) == 0.0
    # the parent's /healthz has no such object: nothing to read, no raise
    for ctx in ({}, {"healths": None}, {"healths": [{}]},
                {"healths": [{"cache": None}]}):
        assert read(ctx) is None


def record(mode, slots):
    return {"mode": mode, "slots_used": slots, "prompt_tokens": 0, "admitted": []}


def test_the_undeclared_rooflines_read_this_model_s_programs():
    decode = reader("kernel.ssm_decode_roofline").read
    least = roofline_ssm.ssm_update_call(32, 64, 64, 128, 8)["bytes"] / 819e9
    ctx = ctx_of(stretch(15.0, 2 * least * 1e3), records=[record("decode", 32)] * 5)
    assert decode(ctx) == pytest.approx(50.0, rel=1e-3)
    # half the slots live: half the bytes to move
    half = ctx_of(stretch(15.0, 2 * least * 1e3), records=[record("decode", 16)] * 5)
    assert decode(half) == pytest.approx(25.0, rel=1e-3)
    assert decode(ctx_of(stretch(15.0, 1.0, kernel=None),
                         records=[record("decode", 32)])) is None
    assert decode(ctx_of(stretch(15.0, 1.0))) is None        # no decode record
    # the scan is plain einsums: no kernel of its name, nothing to read
    prefill = reader("kernel.ssm_prefill_roofline").read
    assert prefill(ctx) is None
    scan = UPDATE.replace("ssm_state_update", "ssm_chunk_scan")
    call = roofline_ssm.ssm_scan_call(1024, 64, 64, 128, 8, 128)
    took = 4 * roofline.least_seconds(call["flops"], call["bytes"], PEAKS)["seconds"]
    assert prefill(
        ctx_of(stretch(15.0, took * 1e3, kernel=scan))
    ) == pytest.approx(25.0, rel=1e-3)


def test_the_reference_check_starts_nothing_off_the_chip(monkeypatch):
    mod = reader("check.hybrid_logit_err")

    def no_child(*a, **k):
        raise AssertionError("started a child")

    monkeypatch.setattr(mod.subprocess, "run", no_child)
    cpu = {"device": {"platform": "cpu"}}
    tpu = {"device": {"platform": "tpu"}}
    assert mod.read({"spec": {"local_path": DIRECTORY}, "healths": [cpu]}) is None
    other = os.path.join(PB, "configs", "ax-k1-int8-ep16-l12")
    assert mod.read({"spec": {"local_path": other}, "healths": [tpu]}) is None
    # and A.X-K1's reader starts nothing for this configuration
    theirs = reader("check.reference_logit_err")
    monkeypatch.setattr(theirs.subprocess, "run", no_child)
    assert theirs.read({"spec": {"local_path": DIRECTORY}, "healths": [tpu]}) is None


SOUND = {"err": 0.1, "state_err": 0.005, "state_narrow": 0.0001,
         "score_err": 0.03,
         "rerun": {"prefill": 0.0, "decode": 0.0, "tokens_differ": 0}}


@pytest.mark.parametrize("change,says", [
    ({}, None),
    ({"err": 0.9}, "logits"),
    ({"err": float("nan")}, "logits"),
    ({"state_err": 0.5}, "recurrent state"),
    ({"state_narrow": 1.0}, "not kept in float32"),
    ({"score_err": 0.4}, "router"),
    ({"rerun": {"prefill": 0.0, "decode": 0.0, "tokens_differ": 2}}, "another token"),
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

    mod = reader("check.hybrid_logit_err")
    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    monkeypatch.setattr(mod.sys, "argv", ["run.py", "--seed", "4600000123"])
    tpu = {"device": {"platform": "tpu"}}
    ctx = {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}

    def child(got):
        def run(argv, **kw):
            assert argv[1].endswith("reference_check_hybrid.py")
            out = argv[argv.index("--out") + 1]
            assert "4600000123" in out and argv[argv.index("--seed") + 1] == "4600000123"
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


def test_every_fault_measured_on_the_chip_fails_through_the_judge():
    """``perfbench/check_noise/``'s table for this configuration: the
    sound readings pass the judge under the limits ``deployment.json``
    states, and each of the seven faults fails it."""
    dep = load(DIRECTORY + "/deployment.json")
    table = load(os.path.join(PB, "check_noise", NAME + ".reference.json"))
    assert table["config"] == NAME and table["platform"] == "tpu"
    for run in table["sound"]:
        assert check.judge(run, dep) == [], run
    faults = table["faults"]
    assert set(faults) == {
        "bf16_state", "state_after_bucket", "conv_from_padding", "no_d",
        "whole_gate_norm", "gated_silu", "no_scaling",
    }
    for name, readings in faults.items():
        for got in readings:
            assert check.judge(got, dep), name
            assert got["problems"] == check.judge(got, dep)


def test_the_check_compares_the_runner_with_the_reference_on_a_small_model(tmp_path):
    """``reference_check_hybrid.py`` whole, on the CPU: a small hybrid in
    float32 (so the limits can be tight), two padded prompts a bucket
    through the runner's prefill, insert with the state, eight decode
    steps; sound, and every fault over a limit."""
    hf = {
        "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h",
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "vocab_size": 264,
        "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
        "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
        "n_routed_experts": 4, "experts_held": {"of": 8, "first": 2},
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
        "mlp_hidden_act": "relu2", "torch_dtype": "float32",
    }
    dep = {
        "name": "tiny-nemotron-h",
        "model": {"quantization": "", "max_seq_len": 128, "max_slots": 4},
        # bf16 activations at 24-56 positions: looser than the chip's
        "hybrid_check": {
            "buckets": [32, 64], "prompts": 2, "steps": 8,
            "logit_tol": 0.15, "state_tol": 0.1, "narrow_tol": 0.05,
            "score_tol": 0.05,
        },
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with open(tmp_path / "deployment.json", "w") as f:
        json.dump(dep, f)
    out = tmp_path / "out.json"
    faults = ",".join(("",) + check_faults())
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "4600000007", "--out",
        str(out), "--any-platform", "--fault", faults,
    ]) == 0
    got = load(out)
    assert got["problems"] == [] and got["rerun"]["tokens_differ"] == 0
    assert len(got["cases"]) == 4 and got["steps"] == 8
    assert {c["bucket"] for c in got["cases"]} == {32, 64}
    assert all(c["n"] < c["bucket"] for c in got["cases"])     # padded
    for name in check_faults():
        assert got["by_fault"][name]["problems"], name
    # off a TPU, and not asked otherwise: no number under this name
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "1", "--out", str(out),
    ]) == 3


def check_faults():
    from perfbench.reference import nemotron_h

    return tuple(nemotron_h.FAULTS)


def test_the_calibration_file_moves_no_bound():
    cal = load(os.path.join(PB, "calibration", CELL + ".json"))
    assert cal["cell"] == CELL and cal["pr"] == 46
    assert not cal.get("sets_bounds")
    assert len(cal["sets"]) == 2
    seeds = [s for k in cal["sets"].values() for s in k["seeds"]]
    assert len(seeds) == 12
    assert set(cal["metrics"]) == {"output_tok_s", "setup_s"}
