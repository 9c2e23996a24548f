"""The Solar-Open2 configuration and its closed-loop cell (PR 60): the
file is the catalog's row but for the keys it lists, ``deployment.json``
says what ``ModelConfig`` counts, the cell is among the workloads and on
the lists the issue names, each of the three new metrics has a reader
that reads a number on a reduced stretch of this model's programs, and
the comparison with the reference judges through one function. Every
entry is found **by name**, never by its place in a list: the next PR
that appends must not flip this file (ROADMAP B0)."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)
NAME = "solar-open2-250b-int8-ep8-l12"
DIRECTORY = os.path.join(PB, "configs", NAME)
CELL = NAME + ".reason-closed"
SOURCE = "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
NEW = {
    "check.kda_logit_err": ("program_counter", "nats"),
    "kda.state_update_share_pct": ("device_trace", "%"),
    "kernel.kda_state_update_roofline": ("device_trace", "%"),
}
# what moves tokens/s in a cell held to it; none of what moves a latency
TOKENS_LISTS = (
    "client.itl_ms_p99", "sched.occupancy_p50.closed",
    "runner.decode_step_ms_p50.closed", "device.idle_pct.closed",
    "device.peak_mem_gb.closed",
)
UPDATE = (
    "%kda_state_update.3 = (f32[9,32,128,8192]{3,2,1,0}, f32[32,1,8192]"
    "{2,1,0}) custom-call(%a)"
)

from perfbench import loadgen, roofline, roofline_kda  # noqa: E402
from perfbench import reference_check_kda as check  # noqa: E402
from perfbench import run as bench_run  # noqa: E402


def load(path):
    with open(path) as f:
        return json.load(f)


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
QWEN = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))
OLMO = load(os.path.join(PB, "configs", "olmo-hybrid-7b-int8", "config.json"))


def named(group, name):
    found = [e for e in BENCH[group] if e["name"] == name]
    assert len(found) == 1, (group, name)
    return found[0]


def test_the_configuration_is_among_the_configs_under_its_source():
    entry = named("configs", NAME)
    assert entry["source"] == SOURCE
    assert entry["file"] == f"perfbench/configs/{NAME}/config.json"
    dep = load(DIRECTORY + "/deployment.json")
    assert set(entry["reduced"]) == set(dep["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size",
    }
    assert dep["name"] == NAME and dep["source"] == SOURCE
    assert dep["chips"] == 1
    assert len(entry["why"]) <= 200 and len(dep["source"]) <= 200


def test_the_cell_is_among_the_workloads_with_the_traffic_as_it_stands():
    cell = named("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "reason-closed", 1
    )
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    mix = loadgen.load_traffic("reason-closed", PB)
    dep = load(DIRECTORY + "/deployment.json")
    # a full batch: as many clients as the deployment has slots
    assert mix["loop"] == "closed"
    assert int(mix["clients"]) == int(dep["model"]["max_slots"]) == 32
    assert (mix["prompt_tokens"]["median"], mix["output_tokens"]["median"]) == (
        384, 768
    )
    assert int(mix["trace_steps"]) == 16
    # the longest request fits the deployment's context
    assert (
        mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
        <= int(dep["model"]["max_seq_len"]) == 2560
    )


def test_the_cell_is_on_the_lists_of_what_moves_its_tokens_and_no_latency():
    def cells_of(group, name):
        return named(group, name).get("workloads") or ()

    assert CELL in cells_of("end_to_end", "output_tok_s")
    for name in ("ttft_ms_p50", "itl_ms_p99"):
        assert CELL not in cells_of("end_to_end", name)
    for name in TOKENS_LISTS:
        assert CELL in cells_of("per_layer", name), name
        assert named("per_layer", name)["moves"] == "output_tok_s"
    for m in BENCH["per_layer"]:
        if CELL in (m.get("workloads") or ()):
            assert m["moves"] in ("output_tok_s", "setup_s"), m["name"]
    # every metric the cell reports has a reader
    for m in bench_run.metrics_of(BENCH, "per_layer", CELL):
        assert bench_run.reader_path(m["name"]), m["name"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_declared_for_this_cell_only_with_a_reader(name):
    entry = named("per_layer", name)
    source, unit = NEW[name]
    assert (entry["source"], entry["unit"]) == (source, unit)
    assert entry["moves"] == "output_tok_s"
    assert entry["workloads"] == [CELL]
    assert set(entry) == {
        "name", "unit", "better", "source", "layer", "moves", "workloads"
    }
    # the layer is one that the benchmark already names
    assert sum(m["layer"] == entry["layer"] for m in BENCH["per_layer"]) > 1
    assert os.path.exists(os.path.join(PB, "layer_metrics", name + ".py"))


def test_deployment_json_says_what_model_config_counts():
    from gpustack_tpu.models.config import config_from_hf, load_hf_config

    dep = load(DIRECTORY + "/deployment.json")
    ours = load(DIRECTORY + "/config.json")
    cfg = load_hf_config(DIRECTORY)
    published = config_from_hf({
        **{k: v for k, v in ours.items() if k != "experts_held"},
        **dep["published"],
    })
    assert dep["published"] == {
        "num_hidden_layers": 48,
        "gqa_layers": list(range(0, 48, 4)),
        "n_routed_experts": 320, "vocab_size": 196608,
    }
    assert set(dep["held_here"]) == set(dep["reduced"])
    assert (cfg.num_layers, cfg.num_held_experts, cfg.num_experts) == (
        12, 40, 320
    )
    assert (cfg.vocab_size, published.vocab_size) == (24576, 196608)
    assert cfg.vocab_size * 8 == published.vocab_size
    assert (published.num_layers, published.num_held_experts) == (48, 320)
    for said in ("8 chips", "12 of the 48"):
        assert said in dep["stands_for"], said
    assert "40 of 320" in dep["held_here"]["n_routed_experts"]
    assert "24,576 of 196,608" in dep["held_here"]["vocab_size"]
    # the sizes are param_count's
    sizes = dep["sizes"]
    assert f"{cfg.param_count() / 1e9:.2f} B" in sizes
    assert f"{published.param_count() / 1e9:.1f} B" in sizes
    assert round(cfg.param_count() / 1e9, 2) == 9.52
    assert round(published.param_count() / 1e9, 1) == 250.3
    state = cfg.state_bytes_per_slot(16)
    assert f"{state / 1e6:.1f} MB" in sizes and round(state / 1e6, 1) == 39.1
    assert "memory_analysis" in sizes
    assert dep["model"] == {
        "quantization": "int8", "max_seq_len": 2560, "max_slots": 32,
        "replicas": 1,
    }
    # every assumed reading names its other reading
    for key in (
        "low_rank", "shared_expert_width", "attention_gate", "router",
        "hidden_act", "qk_norm", "state_dtype", "architectures",
    ):
        assert key in dep["assumed"], key
    assert 0 < float(dep["prefill_vs_cache_tol"]) < 1
    kda = dep["kda_check"]
    assert kda["buckets"] == [512, 1024] and kda["prompts"] * 2 <= 32
    for tol in ("logit_tol", "state_tol", "score_tol", "narrow_tol"):
        assert 0 < float(kda[tol]) < 2, tol


def test_the_roofline_counts_a_call_from_its_shapes_alone():
    w = roofline_kda.widths(load(DIRECTORY + "/config.json"))
    assert w == {
        "heads": 64, "key": 128, "value": 128, "chunk": 64, "sub_block": 16,
        "layers": 9,
    }
    call = roofline_kda.kda_update_call(32, 64, 128, 128)
    state = 64 * 128 * 128
    assert call["flops"] == 7 * 32 * state
    # the state read and written, q, k and the decay a column a head,
    # beta, v and o a row
    assert call["bytes"] == 32 * (8 * state + 4 * (3 * 128 * 64 + 3 * 8192))
    assert round(call["bytes"] / 1e6, 1) == 274.7
    peaks = bench_run.peaks_for("TPU v5 lite")
    least = roofline.least_seconds(call["flops"], call["bytes"], peaks)
    assert least["bound"] == "memory"
    assert 0.30e-3 < least["seconds"] < 0.36e-3
    # a slot nobody holds moves nothing
    assert roofline_kda.kda_update_call(0, 64, 128, 128) == {
        "flops": 0.0, "bytes": 0.0
    }
    scan = roofline_kda.kda_scan_call(1000, 64, 128, 128, 64, 16)
    assert scan == roofline_kda.kda_scan_call(1024, 64, 128, 128, 64, 16)
    a_head = __import__("perfbench.roofline_delta", fromlist=["x"])
    # a decay a channel reads one more [t, H, Dk] array than a decay a head
    assert scan["bytes"] > a_head.delta_scan_call(1024, 64, 128, 128, 64)["bytes"]
    assert scan["flops"] > a_head.delta_scan_call(1024, 64, 128, 128, 64)["flops"]


def stretch(decode_ms, kernel_ms_a_call, steps=4, kernel=UPDATE):
    ops = {}
    if kernel:
        ops[kernel] = {
            "count": 9 * steps, "total_ns": 9 * steps * kernel_ms_a_call * 1e6,
            "median_ns": kernel_ms_a_call * 1e6,
        }
    return {"devices": [{
        "ops": ops,
        "module_events": [
            ["jit__decode_impl", i * decode_ms * 1e6, decode_ms * 1e6]
            for i in range(steps)
        ] + [["jit_prefill_512", 1e9, 50e6]],
    }]}


def ctx_of(trace, config=None, live=32):
    records = [
        {"mode": "decode", "slots_used": live} for _ in range(8)
    ] + [{"mode": "prefill", "slots_used": 1}]
    return {
        "model_config": config or load(DIRECTORY + "/config.json"),
        "traces": [trace], "flights": [records],
        "buckets": [256, 512, 1024],
        "peaks": bench_run.peaks_for("TPU v5 lite"),
    }


def test_the_update_s_share_is_its_calls_over_the_decode_programs():
    read = bench_run.load_reader("kda.state_update_share_pct").read
    # nine calls of 0.5 ms in each decode program of 18 ms
    assert read(ctx_of(stretch(18.0, 0.5))) == pytest.approx(25.0)
    # a model without KDA layers: 0.0, the truth of it
    for other in (QWEN, OLMO):
        assert read(ctx_of(stretch(18.0, 0.5, kernel=None), other)) == 0.0
    # this model with no such call in the stretch (a renamed kernel, the
    # XLA form, no decode step): nothing, so the capture is retaken
    assert read(ctx_of(stretch(18.0, 0.5, kernel=None))) is None
    a_head = UPDATE.replace("kda_state_update", "delta_state_update")
    assert read(ctx_of(stretch(18.0, 0.5, kernel=a_head))) is None
    assert read({"model_config": load(DIRECTORY + "/config.json")}) is None
    # Olmo-Hybrid's reader goes on reading Olmo's call and not this one
    theirs = bench_run.load_reader("delta.state_update_share_pct").read
    assert theirs(ctx_of(stretch(18.0, 0.5, kernel=a_head), OLMO)) == (
        pytest.approx(25.0)
    )
    assert theirs(ctx_of(stretch(18.0, 0.5), OLMO)) is None
    assert theirs(ctx_of(stretch(18.0, 0.5))) == 0.0


def test_the_update_s_roofline_is_the_least_time_over_the_median_call():
    read = bench_run.load_reader("kernel.kda_state_update_roofline").read
    call = roofline_kda.kda_update_call(32, 64, 128, 128)
    least = roofline.least_seconds(
        call["flops"], call["bytes"], bench_run.peaks_for("TPU v5 lite")
    )["seconds"]
    got = read(ctx_of(stretch(18.0, 0.8)))
    assert got == pytest.approx(100.0 * least / 0.8e-3)
    assert 35 < got < 50
    # half the slots live, the same call time: half the share
    assert read(ctx_of(stretch(18.0, 0.8), live=16)) == pytest.approx(
        got / 2, rel=0.01
    )
    # a call at its floor reads 100 and no more
    assert read(ctx_of(stretch(18.0, least * 1e3))) == pytest.approx(100.0)
    # nothing to time: the capture is retaken; another model: 0.0
    assert read(ctx_of(stretch(18.0, 0.8, kernel=None))) is None
    no_steps = ctx_of(stretch(18.0, 0.8))
    no_steps["flights"] = [[]]
    assert read(no_steps) is None
    assert read(ctx_of(stretch(18.0, 0.8, kernel=None), QWEN)) == 0.0


def test_the_reference_check_starts_nothing_off_the_chip(monkeypatch):
    mod = bench_run.load_reader("check.kda_logit_err")

    def no_child(*a, **k):
        raise AssertionError("started a child")

    monkeypatch.setattr(mod.subprocess, "run", no_child)
    cpu = {"device": {"platform": "cpu"}}
    tpu = {"device": {"platform": "tpu"}}
    assert mod.read({"spec": {"local_path": DIRECTORY}, "healths": [cpu]}) is None
    for other in ("ax-k1-int8-ep16-l12", "nemotron-3-nano-30b-a3b-int8-ep8",
                  "olmo-hybrid-7b-int8", "granite-4.0-h-micro-int8"):
        theirs = os.path.join(PB, "configs", other)
        assert mod.read({"spec": {"local_path": theirs}, "healths": [tpu]}) is None
    rehearsal = os.path.join(PB, "rehearsal", "tiny-qwen3")
    assert mod.read({"spec": {"local_path": rehearsal}, "healths": [tpu]}) is None
    # and the other models' readers start nothing for this configuration
    for name in ("check.reference_logit_err", "check.hybrid_logit_err",
                 "check.delta_logit_err", "check.granite_logit_err"):
        theirs = bench_run.load_reader(name)
        monkeypatch.setattr(theirs.subprocess, "run", no_child)
        assert theirs.read(
            {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}
        ) is None


SOUND = {
    "err": 0.02, "state_err": 0.01, "state_narrow": 0.0001, "score_err": 0.001,
    "rerun": {"tokens_differ": 0},
}


@pytest.mark.parametrize("change,says", [
    ({}, None),
    ({"err": 5.0}, "logits"),
    ({"err": float("nan")}, "logits"),
    ({"err": None}, "logits"),
    ({"state_err": 0.9}, "recurrent state"),
    ({"state_narrow": 1.0}, "not kept in float32"),
    ({"score_err": 0.5}, "router's scores"),
    ({"rerun": {"tokens_differ": 2}}, "chose another token"),
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

    mod = bench_run.load_reader("check.kda_logit_err")
    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    monkeypatch.setattr(mod.sys, "argv", ["run.py", "--seed", "6000000123"])
    tpu = {"device": {"platform": "tpu"}}
    ctx = {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}

    def child(got):
        def run(argv, **kw):
            assert argv[1].endswith("reference_check_kda.py")
            out = argv[argv.index("--out") + 1]
            assert "6000000123" in out and argv[argv.index("--seed") + 1] == "6000000123"
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


def test_the_reference_imports_no_model_code_and_no_other_reference():
    from perfbench.reference import solar_open2

    with open(os.path.join(PB, "reference", "solar_open2.py")) as f:
        text = f.read()
    lines = [ln for ln in text.splitlines() if ln.startswith(("import", "from"))]
    assert lines and all(
        "gpustack_tpu" not in ln and "perfbench" not in ln for ln in lines
    ), lines
    assert 'default_matmul_precision("highest")' in text
    assert {
        "bf16_state", "decay_a_head", "beta_not_doubled", "gate_before_norm",
        "silu_gate", "attn_gate_left_out", "rotary", "softmax_scores",
    } <= set(solar_open2.FAULTS)


def test_every_fault_measured_on_the_chip_fails_through_the_judge():
    """``perfbench/check_noise/``'s table for this configuration: the
    sound readings pass the judge under the limits ``deployment.json``
    states, each of the faults fails it, and a computation in lower
    precision than the file states (the state kept in bf16) fails."""
    from perfbench.reference import solar_open2

    dep = load(DIRECTORY + "/deployment.json")
    table = load(os.path.join(PB, "check_noise", NAME + ".reference.json"))
    assert table["config"] == NAME and table["platform"] == "tpu"
    assert len(table["sound"]) >= 3
    assert len({run["seed"] for run in table["sound"]}) == len(table["sound"])
    for run in table["sound"]:
        assert check.judge(run, dep) == [], run
    faults = table["faults"]
    assert set(faults) == set(solar_open2.FAULTS)
    for name, readings in faults.items():
        assert readings, name
        for got in readings:
            assert check.judge(got, dep), name
            assert got["problems"] == check.judge(got, dep)
    assert any(
        "not kept in float32" in p for got in faults["bf16_state"]
        for p in got["problems"]
    )


def test_the_cell_s_noise_table_backs_the_stated_tolerance():
    """No sound prompt of the table reads over the tolerance, let alone a
    median of five; a wrong answer's median lies over it."""
    dep = load(DIRECTORY + "/deployment.json")
    table = load(os.path.join(PB, "check_noise", CELL + ".seed1.json"))
    assert table["workload"] == CELL
    tol = float(dep["prefill_vs_cache_tol"])
    assert set(table["buckets"]) == {"256", "1024"}
    for bucket in table["buckets"].values():
        assert bucket["ranked"]["n"] >= 40
        assert bucket["ranked"]["max"] < tol
        assert bucket["ranked_other_prompt"]["median"] > 2 * tol


def test_the_calibration_file_if_brought_moves_no_bound():
    path = os.path.join(PB, "calibration", CELL + ".json")
    if not os.path.exists(path):
        pytest.skip("no calibration file brought for this cell")
    cal = load(path)
    assert cal["cell"] == CELL and cal["pr"] == 60
    assert not cal.get("sets_bounds")
    assert set(cal["metrics"]) <= {"output_tok_s", "setup_s"}


def test_the_readme_says_how_to_run_the_check_alone():
    with open(DIRECTORY + "/README.md") as f:
        text = f.read()
    assert "reference_check_kda.py" in text and "--config-dir" in text
    dep = load(DIRECTORY + "/deployment.json")
    for key in dep["assumed"]:
        assert re.search(rf"`{re.escape(key)}`", text), key
