"""The Command A+ configuration and its cell (PR 48): the file is the
published model with only the listed cuts, the cell is on the lists the
issue names and no others, its readers read what its traffic can give and
nothing from what it cannot, ``roofline_window.py`` counts on hand-worked
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
NAME = "command-a-plus-int8-ep8-l8"
DIRECTORY = os.path.join(PB, "configs", NAME)
CELL = NAME + ".longdoc-closed"
SIBLING = "ax-k1-int8-ep16-l12.longdoc-closed"

from perfbench import loadgen, roofline, roofline_window  # noqa: E402
from perfbench import reference_check_window as check  # noqa: E402
from perfbench import run as bench  # noqa: E402


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


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog's entry (source_url below), every key of its config
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144,
}
CUT = {"num_hidden_layers": 8, "num_experts": 16, "vocab_size": 32768}
SOURCE = (
    "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/"
    "config.json"
)


def test_config_json_is_the_published_file_but_for_the_listed_cuts():
    cfg, dep = load(DIRECTORY + "/config.json"), load(DIRECTORY + "/deployment.json")
    assert dep["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    # nothing else but the restored name and the share; layer_types whole
    assert set(cfg) - set(PUBLISHED) == {"architectures", "experts_held"}
    assert cfg["architectures"] == ["Cohere2MoeForCausalLM"]
    assert cfg["experts_held"] == {"of": 128, "first": 0}
    assert dep["published"] == {k: PUBLISHED[k] for k in CUT}
    assert dep["source"] == SOURCE and len(dep["source"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["source"] == SOURCE and entry["reduced"] == list(CUT)
    assert entry["file"] == f"perfbench/configs/{NAME}/config.json"
    # no width among the cuts
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("stands_for", "held_here", "assumed", "sizes", "window_check"):
        assert dep[key], key
    assert "average" in dep["assumed"]["shared_experts"]
    assert dep["model"] == {
        "quantization": "int8", "max_seq_len": 8192, "max_slots": 16,
        "replicas": 1,
    }
    assert 0 < dep["prefill_vs_cache_tol"] <= 0.12


def test_config_json_loads_to_the_published_widths_and_two_stores():
    from gpustack_tpu.models.config import load_hf_config

    cfg = load_hf_config(DIRECTORY)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        4096, 128, 8, 128
    )
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok) == (
        128, 16, 8
    )
    assert cfg.moe_intermediate_size == 4096
    assert cfg.shared_expert_intermediate_size == 4 * 4096
    assert cfg.shared_expert_average and cfg.n_shared_experts == 4
    assert (cfg.sliding_window, cfg.window_rows) == (4096, True)
    assert cfg.layer_sliding == (True, True, True, False) * 2
    assert cfg.parallel_block and cfg.layer_norm and cfg.tie_word_embeddings
    assert cfg.moe_scoring == "sigmoid" and not cfg.router_correction_bias
    assert cfg.rope_theta == 50000 and cfg.rope_interleaved


def test_the_cell_is_the_issue_s_and_is_on_the_lists_of_its_sibling():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {
        "name": CELL, "config": NAME, "traffic": "longdoc-closed",
        "chips": 1, "why": cell["why"],
    }
    assert len(cell["why"]) <= 200 and "1/8" in cell["why"]
    assert BENCH["workloads"][-1] == cell and len(BENCH["workloads"]) == 6
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    mix = loadgen.load_traffic("longdoc-closed", PB)
    assert (mix["loop"], mix["clients"], mix["round"]) == ("closed", 6, 16)
    planned = loadgen.plan_requests(mix, int(mix["pool"]), 4800000001)
    assert loadgen.buckets_of(planned, 8192) == [4096, 8192]
    # every list the A.X-K1 cell under the same mix is on, but its own
    # reference check and the rag cells' five
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            listed = m.get("workloads")
            if listed is None or m["name"] == "check.reference_logit_err":
                continue
            if SIBLING in listed:
                assert CELL in listed, m["name"]
    mine = {
        m["name"] for m in bench.metrics_of(BENCH, "per_layer", CELL)
        if "workloads" in m
    }
    assert mine == {
        "client.itl_ms_p99", "loadgen.late_ms_max.closed",
        "proxy.pre_dial_ms_p50.closed", "sched.queue_wait_ms_p50.closed",
        "sched.occupancy_p50.closed", "runner.decode_step_ms_p50.closed",
        "runner.padding_waste_pct.closed", "device.idle_pct.closed",
        "device.peak_mem_gb.closed", "moe.held_pairs_pct",
        "check.window_logit_err", "cache.window_share_pct",
        "attn.window_decode_share_pct", "kernel.window_prefill_roofline",
    }
    # the rooflines whose counts would overstate this model's work
    for name in ("kernel.flash_prefill_roofline",
                 "kernel.decode_hbm_roofline.closed"):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in m["workloads"]
    ends = {m["name"] for m in bench.metrics_of(BENCH, "end_to_end", CELL)}
    assert {"setup_s", "output_tok_s"} <= ends


def test_the_new_metrics_are_declared_last_with_a_reader_each():
    new = [
        ("check.window_logit_err", "nats", "program_counter", "output_tok_s"),
        ("cache.window_share_pct", "%", "program_counter", "output_tok_s"),
        ("attn.window_decode_share_pct", "%", "device_trace", "output_tok_s"),
        ("kernel.window_prefill_roofline", "%", "device_trace", "ttft_ms_p50"),
    ]
    tail = BENCH["per_layer"][-len(new):]
    for m, (name, unit, source, moves) in zip(tail, new):
        assert (m["name"], m["unit"], m["source"], m["moves"]) == (
            name, unit, source, moves
        )
        assert m["workloads"] == [CELL]
        assert callable(reader(name).read)
        layers = {x["layer"] for x in BENCH["per_layer"][:-len(new)]}
        assert m["layer"] in layers
    ends = {m["name"] for m in bench.metrics_of(BENCH, "end_to_end", CELL)}
    assert all(moves in ends for *_, moves in new)
    # shipped beside the MLA and SSM pairs, undeclared (PERF.md section 7)
    assert callable(reader("kernel.window_decode_roofline").read)
    assert "kernel.window_decode_roofline" not in {
        m["name"] for m in BENCH["per_layer"]
    }


# ---- roofline_window.py on hand-worked shapes --------------------------------


def test_the_band_counts_min_i_plus_one_window_keys_a_query():
    assert roofline_window.band_pairs(4, 0) == 10
    assert roofline_window.band_pairs(4, 9) == 10
    # window 2 over 5 positions: 1 + 2 + 2 + 2 + 2
    assert roofline_window.band_pairs(5, 2) == 9
    # an 8,192 prefill under 4,096: three quarters of the triangle
    assert roofline_window.band_pairs(8192, 4096) / (
        roofline_window.band_pairs(8192, 0)
    ) == pytest.approx(0.75, abs=1e-3)
    call = roofline_window.window_prefill_call(8192, 128, 8, 128, 4096)
    assert call["flops"] == 4.0 * (4096 * 4097 / 2 + 4096 * 4096) * 128 * 128
    assert call["bytes"] == 2.0 * (2 * 8192 * 128 * 128 + 2 * 8192 * 8 * 128)
    whole = roofline_window.window_prefill_call(8192, 128, 8, 128, 0)
    assert whole == roofline.flash_prefill_call(8192, 128, 8, 128)


def test_a_decode_call_reads_the_live_rows_once():
    # six live slots past the window: 6 x 4,096 rows of 8 heads of 128,
    # keys and values, bf16
    call = roofline_window.window_decode_call(6 * 4096, 6, 128, 8, 128)
    assert call["bytes"] == 2.0 * (
        2 * 6 * 4096 * 8 * 128 + 2 * 6 * 128 * 128
    )
    assert call["flops"] == 4.0 * 6 * 4096 * 128 * 128
    cfg = load(DIRECTORY + "/config.json")
    assert roofline_window.window_of(cfg) == (4096, 6, 2)
    qwen = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))
    assert roofline_window.window_of(qwen) == (0, 0, 36)


# ---- the readers on hand-made reduced traces ---------------------------------

RING = (
    "%gqa_window_decode_attention.5 = bf16[16,128,128]{2,1,0:T(8,128)(2,1)} "
    "custom-call(%a, %b, %c, %d, %e, %f, %g, %h)"
)
BAND = "%flash_attention_window.3 = bf16[1,128,8192,128]{3,2,1,0} custom-call(%a)"
FULL = "%flash_attention_prefill.9 = bf16[1,128,{t},128]{{3,2,1,0}} custom-call(%a)"
PEAKS = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]


def stretch(decode_ms, kernel_ms_a_call, steps=4, kernel=RING, more=None):
    ops = dict(more or {})
    if kernel:
        ops[kernel] = {
            "count": 6 * steps, "total_ns": 6 * steps * kernel_ms_a_call * 1e6,
            "median_ns": kernel_ms_a_call * 1e6,
        }
    return {"devices": [{
        "ops": ops,
        "module_events": [
            ["jit__decode_impl", i * decode_ms * 1e6, decode_ms * 1e6]
            for i in range(steps)
        ] + [["jit_prefill_8192", 1e9, 700e6]],
    }]}


def ctx_of(trace, config=None, records=()):
    return {
        "model_config": config or load(DIRECTORY + "/config.json"),
        "peaks": PEAKS, "traces": [trace], "flights": [list(records)],
        "buckets": [4096, 8192],
    }


def test_the_ring_s_share_is_its_calls_over_the_decode_programs():
    read = reader("attn.window_decode_share_pct").read
    # six calls of 0.1 ms in each decode program of 10 ms
    assert read(ctx_of(stretch(10.0, 0.1))) == pytest.approx(6.0)
    # a model without sliding layers kept at window size: 0.0
    qwen = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))
    assert read(ctx_of(stretch(10.0, 0.1, kernel=None), qwen)) == 0.0
    assert read(ctx_of(stretch(10.0, 0.1), qwen)) == 0.0
    # this one with no such call in the stretch (a renamed kernel, the
    # XLA form, no decode step): nothing, so the capture is retaken
    assert read(ctx_of(stretch(10.0, 0.1, kernel=None))) is None
    renamed = RING.replace("gqa_window_decode", "gqa_decode")
    assert read(ctx_of(stretch(10.0, 0.1, kernel=renamed))) is None
    assert read({"model_config": load(DIRECTORY + "/config.json")}) is None


def test_the_window_store_s_share_comes_from_healthz():
    read = reader("cache.window_share_pct").read
    row = 2 * 8 * 128 * 2
    health = {"cache": {
        "kv_bytes": 16 * 2 * 8192 * row, "state_bytes": 0,
        "state_dtype": None, "window_bytes": 16 * 6 * 4096 * row,
    }}
    assert read({"healths": [health]}) == pytest.approx(60.0)
    plain = {"cache": {"kv_bytes": 5, "state_bytes": 0, "window_bytes": 0}}
    assert read({"healths": [plain]}) == 0.0
    # the parent's /healthz has no such field: nothing to read, no raise
    for ctx in ({}, {"healths": None}, {"healths": [{}]},
                {"healths": [{"cache": None}]},
                {"healths": [{"cache": {"kv_bytes": 5, "state_bytes": 0}}]}):
        assert read(ctx) is None


def test_the_prefill_roofline_counts_a_band_where_the_call_has_one():
    read = reader("kernel.window_prefill_roofline").read
    least_band = roofline.least_seconds(
        roofline_window.window_prefill_call(8192, 128, 8, 128, 4096)["flops"],
        0.0, PEAKS,
    )["seconds"]
    least_full = roofline.least_seconds(
        roofline.flash_prefill_call(8192, 128, 8, 128)["flops"], 0.0, PEAKS
    )["seconds"]
    ops = {
        BAND: {"count": 6, "total_ns": 6 * 2 * least_band * 1e9,
               "median_ns": 0.0},
        FULL.format(t=8192): {"count": 2, "total_ns": 2 * 2 * least_full * 1e9,
                              "median_ns": 0.0},
    }
    got = read(ctx_of(stretch(10.0, 0.1, kernel=None, more=ops)))
    assert got == pytest.approx(50.0)
    # counted as the whole triangle, the band's calls would read a third more
    flash = reader("kernel.flash_prefill_roofline").read
    only_band = {BAND.replace("_window", "_prefill"): ops[BAND]}
    assert flash(ctx_of(stretch(10.0, 0.1, kernel=None, more=only_band))) == (
        pytest.approx(50.0 * least_full / least_band)
    )
    # a file without a window: the accepted metric's number
    qwen = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))
    old = {
        "%flash_attention_prefill.7 = bf16[1,32,2048,128]{3,2,1,0} "
        "custom-call(%a)": {"count": 36, "total_ns": 36 * 0.43e6,
                            "median_ns": 0.43e6},
    }
    ctx = ctx_of(stretch(10.0, 0.1, kernel=None, more=old), qwen)
    assert read(ctx) == pytest.approx(flash(ctx))
    assert read(ctx_of(stretch(10.0, 0.1, kernel=None))) is None


def record(mode, slots, window_rows=None):
    r = {"mode": mode, "slots_used": slots, "prompt_tokens": 0, "admitted": []}
    if window_rows is not None:
        r["window_rows"], r["full_rows"] = window_rows, 2 * slots * 6000
    return r


def test_the_undeclared_decode_roofline_reads_this_model_s_programs():
    read = reader("kernel.window_decode_roofline").read
    records = [record("decode", 6, 6 * 6 * 4096)] * 5 + [
        record("prefill", 6, 10 ** 9)
    ]
    call = roofline_window.window_decode_call(6 * 4096, 6, 128, 8, 128)
    least = call["bytes"] / PEAKS["hbm_bytes_per_s"]
    got = read(ctx_of(stretch(10.0, 2 * least * 1e3), records=records))
    assert got == pytest.approx(50.0)
    assert read(ctx_of(stretch(10.0, 0.1, kernel=None), records=records)) is None
    assert read(ctx_of(stretch(10.0, 0.1))) is None      # no flight records
    # the parent's records carry no window_rows: nothing, no raise
    old = [record("decode", 6)] * 5
    assert read(ctx_of(stretch(10.0, 0.1), records=old)) is None


# ---- the comparison with the reference ---------------------------------------


def test_the_reference_check_starts_nothing_off_the_chip(monkeypatch):
    mod = reader("check.window_logit_err")

    def no_child(*a, **k):
        raise AssertionError("started a child")

    monkeypatch.setattr(mod.subprocess, "run", no_child)
    cpu = {"device": {"platform": "cpu"}}
    tpu = {"device": {"platform": "tpu"}}
    assert mod.read({"spec": {"local_path": DIRECTORY}, "healths": [cpu]}) is None
    for other in ("ax-k1-int8-ep16-l12", "nemotron-3-nano-30b-a3b-int8-ep8"):
        there = os.path.join(PB, "configs", other)
        assert mod.read({"spec": {"local_path": there}, "healths": [tpu]}) is None
    # and the other two checks' readers start nothing for this configuration
    for name in ("check.reference_logit_err", "check.hybrid_logit_err"):
        theirs = reader(name)
        monkeypatch.setattr(theirs.subprocess, "run", no_child)
        assert theirs.read(
            {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}
        ) is None


SOUND = {"err": 0.03, "ring_err": 0.009, "score_err": 0.006, "edge_err": 0.004,
         "rerun": {"prefill": 0.0, "decode": 0.0, "tokens_differ": 0}}


@pytest.mark.parametrize("change,says", [
    ({}, None),
    ({"err": 0.9}, "logits"),
    ({"err": float("nan")}, "logits"),
    ({"ring_err": 0.9}, "ring"),
    ({"score_err": 0.4}, "router"),
    ({"edge_err": 1.5}, "window's edges"),
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

    mod = reader("check.window_logit_err")
    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    monkeypatch.setattr(mod.sys, "argv", ["run.py", "--seed", "4800000123"])
    tpu = {"device": {"platform": "tpu"}}
    ctx = {"spec": {"local_path": DIRECTORY}, "healths": [tpu]}

    def child(got):
        def run(argv, **kw):
            assert argv[1].endswith("reference_check_window.py")
            out = argv[argv.index("--out") + 1]
            assert "4800000123" in out and argv[argv.index("--seed") + 1] == "4800000123"
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                json.dump({**got, "seconds": {"all": 1.0}}, f)
            return type("P", (), {"returncode": 0, "stderr": ""})()
        return run

    monkeypatch.setattr(mod.subprocess, "run", child(SOUND))
    assert mod.read(ctx) == 0.03
    monkeypatch.setattr(mod.subprocess, "run", child({**SOUND, "ring_err": 0.9}))
    with pytest.raises(BenchFailure, match="ring"):
        mod.read(ctx)
    failed = lambda argv, **kw: type(  # noqa: E731
        "P", (), {"returncode": 3, "stderr": "on cpu"}
    )()
    monkeypatch.setattr(mod.subprocess, "run", failed)
    with pytest.raises(BenchFailure, match="exited with 3"):
        mod.read(ctx)


def check_faults():
    from perfbench.reference import cohere2_moe

    return tuple(cohere2_moe.FAULTS)


def test_every_fault_measured_on_the_chip_fails_through_the_judge():
    """``perfbench/check_noise/``'s table for this configuration: the
    sound readings pass the judge under the limits ``deployment.json``
    states, and each fault that a program could have fails it."""
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
            assert got["problems"] == check.judge(got, dep)
            if name not in table["reads_as_sound"]:
                assert check.judge(got, dep), name
    assert set(table["reads_as_sound"]) <= {"bf16_stated"}


def test_the_check_compares_the_runner_with_the_reference_on_a_small_model(tmp_path):
    """``reference_check_window.py`` whole, on the CPU: a small stack of
    two periods with a window of 8, two padded prompts a bucket (the
    longer ones wrap their ring) through the runner's prefill, insert
    with the ring's rows, eight decode steps; sound, and every fault but
    the one a bf16 program cannot show over a limit."""
    hf = {
        "architectures": ["Cohere2MoeForCausalLM"],
        "model_type": "cohere2_moe", "hidden_size": 64,
        "intermediate_size": 32, "num_hidden_layers": 8,
        "layer_types": PERIOD * 2, "num_attention_heads": 4,
        # heads of 128, which the decode kernel takes: the ring's probe
        "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 264,
        "sliding_window": 8, "num_experts": 4,
        "experts_held": {"of": 8, "first": 2}, "num_experts_per_tok": 2,
        "num_shared_experts": 4, "layer_norm_eps": 1e-5, "rope_theta": 50000,
        "logit_scale": 1, "tie_word_embeddings": True, "norm_topk_prob": True,
    }
    dep = {
        "name": "tiny-command-a-plus",
        "model": {"quantization": "", "max_seq_len": 128, "max_slots": 4},
        "window_check": {
            "buckets": [32, 64], "prompts": 2, "steps": 8,
            "logit_tol": 0.15, "ring_tol": 0.05, "score_tol": 0.05,
            "edge_tol": 0.05,
        },
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with open(tmp_path / "deployment.json", "w") as f:
        json.dump(dep, f)
    out = tmp_path / "out.json"
    faults = ",".join(("",) + check_faults())
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "4800000007", "--out",
        str(out), "--any-platform", "--fault", faults,
    ]) == 0
    got = load(out)
    assert got["problems"] == [] and got["rerun"]["tokens_differ"] == 0
    assert len(got["cases"]) == 4 and got["steps"] == 8
    assert {c["bucket"] for c in got["cases"]} == {32, 64}
    assert all(c["n"] < c["bucket"] for c in got["cases"])     # padded
    assert max(c["n"] for c in got["cases"]) > 8 + 8           # wrapped
    for name in check_faults():
        if name != "bf16_stated":
            assert got["by_fault"][name]["problems"], name
    # a ring written at position shows in the ring alone
    only = got["by_fault"]["ring_at_position"]
    assert len(only["problems"]) == 1 and "ring" in only["problems"][0]
    # a window off by one at its edges, in the kernels' probes: one key
    # wider shows in the prefill's band, one narrower in both
    assert got["edges"]["band"] < 0.01 and got["edges"]["ring"] < 0.01
    wider = got["by_fault"]["window_plus_one"]["edges"]
    narrower = got["by_fault"]["window_minus_one"]["edges"]
    assert wider["band"] > 0.5 and narrower["band"] > 0.5
    assert narrower["ring"] > 0.5
    # off a TPU, and not asked otherwise: no number under this name
    assert check.main([
        "--config-dir", str(tmp_path), "--seed", "1", "--out", str(out),
    ]) == 3
