"""The A.X-K1 configuration and its cell (PR 35): the file is the
published model with only the listed cuts, the cell's readers are served
by what its traffic can give and read nothing from what it cannot, the
MLA roofline counts on hand-worked shapes, and the benchmark's copy of
the reference is the repository's."""

import dataclasses
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PB = os.path.join(ROOT, "perfbench")
sys.path.insert(0, ROOT)
DIRECTORY = os.path.join(PB, "configs", "ax-k1-int8-ep16-l12")
CELL = "ax-k1-int8-ep16-l12.longdoc-closed"

from perfbench import loadgen, roofline, roofline_mla  # noqa: E402


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


# the catalog's entry for A.X-K1 (source_url below), every number of it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn",
    },
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840,
}
CUT = {"num_hidden_layers": 12, "n_routed_experts": 12, "vocab_size": 20480}


def test_config_json_is_the_published_file_but_for_the_listed_cuts():
    cfg, dep = load(DIRECTORY + "/config.json"), load(DIRECTORY + "/deployment.json")
    assert sorted(dep["reduced"]) == sorted(CUT)
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    assert dep["published"] == {k: PUBLISHED[k] for k in CUT}
    assert cfg["published"] == dep["published"]
    assert cfg["experts_held"] == {"published": 192, "first": 0}
    assert dep["source"] == "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    assert len(dep["source"]) <= 200 and dep["name"] == "ax-k1-int8-ep16-l12"
    assert dep["model"] == {
        "quantization": "int8", "max_seq_len": 8192, "max_slots": 16,
        "replicas": 1,
    }
    assert dep["chips"] == 1 and set(dep["assumed"]) >= {
        "topk_method", "architectures", "weights", "tokenizer", "quantization",
    }
    # the floors of a cut: a period and four layers more, eight experts,
    # an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 1 + 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_config_json_loads_to_the_published_widths():
    from gpustack_tpu.models.config import load_hf_config

    cfg = dataclasses.asdict(load_hf_config(DIRECTORY))
    want = {
        "hidden_size": 7168, "intermediate_size": 18432, "num_heads": 64,
        "head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "moe_intermediate_size": 2048, "shared_expert_intermediate_size": 2048,
        "num_experts": 192, "num_experts_per_tok": 8, "n_group": 8,
        "topk_group": 4, "routed_scaling_factor": 2.5, "moe_scoring": "sigmoid",
        "first_k_dense": 1, "experts_held": 12, "first_held_expert": 0,
        "num_layers": 12, "vocab_size": 20480, "rms_norm_eps": 1e-6,
    }
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_scaling"]["factor"] == 32
    # 1,152 bytes a position a layer
    assert load_hf_config(DIRECTORY).kv_cache_bytes_per_token() == 12 * 1152


CLIENTS = 6


def test_the_traffic_is_the_issue_s_and_stays_inside_the_context():
    """Rounds of 16 over the whole of the two distributions; fewer
    clients than the issue's 16, which is what it allows a builder to
    change when the spreads ask for it (PERF.md section 6, PR 35)."""
    mix = loadgen.load_traffic("longdoc-closed", PB)
    assert (mix["clients"], mix["pool"], mix["round"]) == (CLIENTS, 64, 16)
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 5600, "sigma": 0.3, "min": 3072, "max": 7600,
    }
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 128, "max": 512,
    }
    planned = loadgen.plan_requests(mix, 64, seed=3500000001)
    prompts = [p.prompt_tokens for p in planned]
    outputs = [p.output_tokens for p in planned]
    assert len(set(prompts)) == 15 and len(set(outputs)) == 16
    assert (min(prompts), max(prompts)) == (3203, 7600)
    assert (min(outputs), max(outputs)) == (128, 512)
    assert max(prompts) + max(outputs) < 8192
    assert loadgen.buckets_of(planned, 8192) == [4096, 8192]
    # every round offers the same lengths, whatever the seed
    other = loadgen.plan_requests(mix, 64, seed=7)
    for r in range(4):
        assert sorted(prompts[16 * r:16 * r + 16]) == sorted(
            p.prompt_tokens for p in other[16 * r:16 * r + 16]
        )


def test_the_cell_declares_what_the_issue_names():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = lambda g: {  # noqa: E731
        m["name"] for m in bench[g] if CELL in m.get("workloads", [CELL])
    }
    assert mine("end_to_end") == {"ttft_ms_p50", "output_tok_s", "setup_s"}
    layer = mine("per_layer")
    assert {
        "check.reference_logit_err", "moe.held_pairs_pct",
        "client.itl_ms_p99", "device.idle_pct.closed",
        "runner.decode_step_ms_p50.closed", "runner.padding_waste_pct.closed",
    } <= layer
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["moe.held_pairs_pct"]["moves"] == "ttft_ms_p50"
    # their counts read other models' keys: a share over 105 % is refused
    assert not {
        "kernel.flash_prefill_roofline", "kernel.decode_hbm_roofline.closed",
    } & layer
    # the two MLA rooflines have their readers and are not declared:
    # test_perfbench_stretch.py holds every declared device_trace metric
    # to Qwen3-8B's stretch and to 16 traced steps, and that file is the
    # benchmark's (PERF.md section 7: which file would need which edit)
    assert not {n for n in by_name if n.startswith("kernel.mla_")}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc-closed"
    assert f"{CLIENTS} clients" in cell["why"] and len(cell["why"]) <= 200


# ---- roofline_mla.py on hand-worked shapes ----

def test_prefill_counts_the_lower_triangle_at_the_two_widths():
    call = roofline_mla.mla_prefill_call(4, heads=2, qk=3, v=2)
    # 10 pairs, 2 heads, 3 + 2 wide, two operations a multiply-add
    assert call["flops"] == 2 * 10 * 2 * 5
    # q and k 3 wide, v and o 2 wide, 4 rows, 2 heads, 2 bytes
    assert call["bytes"] == 4 * 2 * (3 + 3 + 2 + 2) * 2
    big = roofline_mla.mla_prefill_call(8192, 64, 192, 128)
    assert big["flops"] == pytest.approx(2 * 8192 * 8193 / 2 * 64 * 320)
    peaks = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]
    least = roofline.least_seconds(big["flops"], big["bytes"], peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(1.3745e12 / 197e12, rel=1e-3)


def test_decode_counts_the_latent_rows_once_for_all_heads():
    call = roofline_mla.mla_decode_call(10, heads=3, rank=4, rope=2)
    assert call["bytes"] == 10 * 6 * 2
    assert call["flops"] == 2 * 3 * 10 * (6 + 4)
    # 16 slots of 6,000 rows at the published widths: 110.6 MB, 13.4 GFLOP
    w = roofline_mla.widths(load(DIRECTORY + "/config.json"))
    assert w == {"heads": 64, "qk": 192, "v": 128, "rank": 512, "rope": 64}
    big = roofline_mla.mla_decode_call(96000, w["heads"], w["rank"], w["rope"])
    assert big["bytes"] == 96000 * 1152
    assert big["flops"] == 2 * 64 * 96000 * 1088
    peaks = load(os.path.join(PB, "peaks.json"))["TPU v5 lite"]
    assert roofline.least_seconds(big["flops"], big["bytes"], peaks)[
        "bound"
    ] == "memory"


# ---- the cell's readers over stretches its traffic can and cannot give ----

FLASH = (
    "%flash_attention_prefill.3 = bf16[1,64,8192,128]{3,2,1,0:T(8,128)(2,1)}"
    " custom-call(%a, %b, %c, %d)"
)
DECODE = (
    "%mla_decode_attention.5 = bf16[16,64,512]{2,1,0:T(8,128)(2,1)}"
    " custom-call(%p, %l, %q, %r, %c, %k)"
)


def ctx_of(ops, records):
    return {
        "model_config": load(DIRECTORY + "/config.json"),
        "peaks": load(os.path.join(PB, "peaks.json"))["TPU v5 lite"],
        "traces": [{"devices": [{"ops": ops}]}],
        "flights": [records],
    }


def record(mode, slots, prompt=0, admitted=0):
    return {
        "mode": mode, "slots_used": slots, "prompt_tokens": prompt,
        "admitted": [["", 1.0]] * admitted,
    }


def test_the_prefill_reader_is_served_by_a_stretch_with_a_flash_call():
    read = reader("kernel.mla_prefill_roofline").read
    least = 1.3745e12 / 197e12
    ops = {FLASH: {"count": 11, "total_ns": 11 * 2 * least * 1e9, "median_ns": 0}}
    assert read(ctx_of(ops, [])) == pytest.approx(50.0, rel=1e-3)
    # a stretch of decode steps only: nothing to read, the harness retakes
    assert read(ctx_of({DECODE: {"count": 1, "total_ns": 1, "median_ns": 1}}, [])) is None
    # another model's flash call is not this metric's
    qwen = ctx_of(ops, [])
    qwen["model_config"] = load(os.path.join(PB, "configs", "qwen3-8b-int8", "config.json"))
    assert read(qwen) is None


def test_the_decode_reader_holds_the_window_s_prompts_against_the_trace():
    read = reader("kernel.mla_decode_roofline").read
    records = (
        [record("prefill", 12, prompt=6000, admitted=1)] * 4
        + [record("decode", 16)] * 30
    )
    # 16 live slots of 6,000 prompt rows: 110.6 MB at 819 GB/s
    least = 96000 * 1152 / 819e9
    ops = {DECODE: {"count": 12, "total_ns": 0, "median_ns": 4 * least * 1e9}}
    assert read(ctx_of(ops, records)) == pytest.approx(25.0, rel=1e-3)
    # no kernel in the stretch (a program from before it, or the XLA form)
    assert read(ctx_of({FLASH: {"count": 1, "total_ns": 1, "median_ns": 1}}, records)) is None
    # a window without an admitted request gives no prompt length
    assert read(ctx_of(ops, [record("decode", 16)])) is None


def stretch_of(*programs):
    """A reduced trace of one chip that ran these programs back to back,
    ``(name, ms)`` each: a prefill's 12 layers each call the flash kernel
    once, a decode step's 12 layers the decode kernel."""
    events, ops, at = [], {}, 0.0
    for name, ms in programs:
        events.append([name, at, ms * 1e6])
        at += ms * 1e6 + 3000.0
        if name.startswith("jit_prefill_"):
            op = FLASH.replace("8192", name.rsplit("_", 1)[1])
            took = 12 * 7.0e6 * (int(name.rsplit("_", 1)[1]) / 8192) ** 2
        else:
            op, took = DECODE, 12 * 0.2e6
        entry = ops.setdefault(op, {"count": 0, "total_ns": 0.0, "median_ns": 0.0})
        entry["count"] += 12
        entry["total_ns"] += took
        entry["median_ns"] = took / 12
    return {"devices": [{
        "window_s": at / 1e9, "busy_s": at / 1e9 - 1e-4, "idle_pct": 0.03,
        "module_events": events, "ops": ops,
    }]}


BENCH = load(os.path.join(ROOT, "BENCHMARK.json"))
TRACED = [
    m["name"] for m in BENCH["per_layer"]
    if CELL in m.get("workloads", []) and m["source"] == "device_trace"
] + ["kernel.mla_prefill_roofline", "kernel.mla_decode_roofline"]


@pytest.mark.parametrize("metric", TRACED)
def test_a_stretch_of_the_cell_s_own_programs_serves_every_device_trace_reader(metric):
    """The ``device_trace`` metrics the cell declares, and the two MLA
    rooflines that wait for a ``benchmark`` PR to be declared, against a
    stretch of this model's programs: a whole prefill at each bucket its
    requests reach, decode steps between, serves every reader, and one of
    decode steps only all but the prefill's kernel."""
    from perfbench import run as bench

    mix = loadgen.load_traffic("longdoc-closed", PB)
    planned = loadgen.plan_requests(mix, 64, 3000000001)
    buckets = loadgen.buckets_of(planned, 8192)
    D = ("jit__decode_impl", 20.0)
    programs = [D]
    for b in buckets:
        programs += [(f"jit_prefill_{b}", 450.0 * b / 8192), D, D]
    records = [record("prefill", 16, prompt=5600, admitted=1), record("decode", 16)]
    ctx = {
        **ctx_of({}, records), "buckets": buckets,
        "traces": [stretch_of(*programs)],
    }
    value = bench.load_reader(metric).read(ctx)
    assert value is not None and (not metric.endswith("_roofline") or value <= 100)
    # as every cell's mix: test_perfbench_stretch.py holds it to that
    assert int(mix["trace_steps"]) == 16
    decode_only = {**ctx, "traces": [stretch_of(D, D, D)]}
    nothing = bench.load_reader(metric).read(decode_only) is None
    assert nothing == (metric == "kernel.mla_prefill_roofline")


def test_held_pairs_come_from_the_engine_s_counter_or_not_at_all():
    read = reader("moe.held_pairs_pct").read
    assert read({"healths": [{"moe_pairs": {"held": 1, "absent": 15}}]}) == 6.25
    assert read({"healths": [{"moe_pairs": None}]}) is None
    assert read({"healths": [{}]}) is None          # the parent's /healthz


def test_the_reference_check_starts_nothing_off_the_chip(monkeypatch):
    """A rehearsal, the test suite, a configuration without a reference:
    no child, nothing to read."""
    mod = reader("check.reference_logit_err")

    def no_child(*a, **k):
        raise AssertionError("started a child")

    monkeypatch.setattr(mod.subprocess, "run", no_child)
    cpu = {"device": {"platform": "cpu"}}
    assert mod.read({"spec": {"local_path": DIRECTORY}, "healths": [cpu]}) is None
    tpu = {"device": {"platform": "tpu"}}
    qwen = os.path.join(PB, "configs", "qwen3-8b-int8")
    assert mod.read({"spec": {"local_path": qwen}, "healths": [tpu]}) is None


SOUND = {
    "err": 0.05, "differs": 0, "score_err": 0.001,
    "rerun": {"prefill": 0.0, "decode": 0.0, "tokens_differ": 0},
}


@pytest.mark.parametrize("wrong,says", [
    ({}, None),
    ({"err": 10.0}, "float32 reference's"),
    ({"differs": 3}, "other experts than the program in 3"),
    ({"score_err": 0.9}, "router's scores"),
    ({"rerun": {"prefill": 0.0, "decode": 0.0, "tokens_differ": 1}}, "another token"),
], ids=["sound", "logits", "selection", "scores", "rerun"])
def test_the_reference_check_fails_the_run_outside_a_limit(
    monkeypatch, tmp_path, wrong, says
):
    """Each of the comparison's readings ends the run on its own, through
    the reader, and the prompts are the run's ``--seed``'s."""
    mod = reader("check.reference_logit_err")
    seen = []

    def run(argv, **kw):
        seen.append(argv[argv.index("--seed") + 1])
        with open(argv[argv.index("--out") + 1], "w") as f:
            json.dump({**SOUND, **wrong, "readings": {},
                       "seconds": {"all": 1.0}}, f)
        return type("P", (), {"returncode": 0, "stderr": ""})()

    monkeypatch.setattr(mod, "ROOT", str(tmp_path))
    monkeypatch.setattr(mod.subprocess, "run", run)
    monkeypatch.setattr(
        mod.sys, "argv", ["run.py", "--workload", CELL, "--seed", "3500000001"]
    )
    ctx = {
        "spec": {"local_path": DIRECTORY},
        "healths": [{"device": {"platform": "tpu"}}],
        "loadgen": {"attempted": 3, "tokens": 5, "ttft_ms": [1.0]},
    }
    if says is None:
        assert mod.read(ctx) == SOUND["err"]
        assert mod.read(ctx) == SOUND["err"]
        assert seen == [str(3500000001 % 2**31)] * 2
    else:
        with pytest.raises(mod.BenchFailure, match=says):
            mod.read(ctx)


def test_every_fault_measured_on_the_chip_fails_through_the_judge():
    """``perfbench/check_noise/``'s table: the sound readings pass
    ``reference_check.judge`` under the configuration's limits and every
    fault's readings fail it, the lower precision (float8 activations)
    among them."""
    from perfbench.reference_check import judge

    deployment = load(DIRECTORY + "/deployment.json")
    table = load(os.path.join(PB, "check_noise", "ax-k1-int8-ep16-l12.reference.json"))
    assert table["sound"] and all(
        judge(got, deployment) == [] for got in table["sound"]
    )
    assert set(table["faults"]) >= {
        "no_mscale", "plain_topk", "shared_twice", "kv_b_unscaled",
        "fp8_activations",
    }
    for name, got in table["faults"].items():
        assert (judge(got, deployment) != []) == (name not in table["not_caught"]), name
    assert "fp8_activations" not in table["not_caught"]


def test_the_benchmark_s_reference_is_the_repository_s():
    """Two files, one text: the benchmark keeps its own so that a later
    PR that edits ``gpustack_tpu/testing/reference_axk1.py`` does not move
    the yardstick without saying so here."""
    with open(os.path.join(PB, "reference", "axk1.py")) as f:
        theirs = f.read()
    with open(os.path.join(ROOT, "gpustack_tpu", "testing", "reference_axk1.py")) as f:
        assert f.read() == theirs
    assert "models.transformer" not in theirs and "import gpustack_tpu" not in theirs
    assert "from gpustack_tpu" not in theirs


def test_reference_check_compares_the_runner_with_the_reference_on_a_small_model(tmp_path):
    """``reference_check.py`` end to end on the CPU (``--any-platform``, a
    small configuration of the same family): the runner's prefill and
    decode programs against the reference, and a fault found."""
    import subprocess

    from tests.models.test_axk1 import HF

    hf = {**HF, "n_routed_experts": 4,
          "experts_held": {"published": 16, "first": 4}}
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with open(tmp_path / "deployment.json", "w") as f:
        json.dump({
            "name": "tiny-axk1", "reference_logit_tol": 0.2,
            "reference_score_tol": 0.05,
            "model": {
                "quantization": "int8", "max_seq_len": 128, "max_slots": 6,
            },
        }, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))

    def run(*extra):
        out = tmp_path / "out.json"
        proc = subprocess.run(
            [sys.executable, os.path.join(PB, "reference_check.py"),
             "--config-dir", str(tmp_path), "--seed", "35", "--out", str(out),
             "--prompts", "3", "--any-platform", *extra],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        return load(out)

    sound = run()
    assert set(sound["buckets"]) == {"64", "128"}
    assert all(len(p["decode"]) == 4 for ps in sound["buckets"].values() for p in ps)
    assert set(sound["readings"]["128"]) == {"prefill", "decode"}
    # the largest of three prompts, of twelve steps, of all
    for ps, r in ((sound["buckets"][b], sound["readings"][b]) for b in ("64", "128")):
        assert r["prefill"] == max(p["prefill"] for p in ps)
        assert r["decode"] == max(e for p in ps for e in p["decode"])
    assert sound["err"] == max(v for r in sound["readings"].values() for v in r.values())
    assert sound["err"] < 0.2, sound    # bf16 at a hidden size of 64
    # the program's own routing followed: its selection is the reference's
    # over its scores, and the program run again is the same computation
    assert sound["differs"] == 0 and sound["score_err"] < 0.05
    assert sound["rerun"] == {"prefill": 0.0, "decode": 0.0, "tokens_differ": 0}
    assert sound["problems"] == []
    wrong = run("--fault", "no_mscale,plain_topk,fp8_activations")["by_fault"]
    assert wrong["no_mscale"]["err"] > 4 * sound["err"]
    assert wrong["fp8_activations"]["err"] > 2 * sound["err"]
    # another selection rule moves no logit (the program's choice is
    # followed) and is seen where it is: in the choice
    assert wrong["plain_topk"]["differs"] > 0
    assert wrong["plain_topk"]["err"] == pytest.approx(sound["err"], rel=0.5)
    assert all(w["problems"] for w in wrong.values())
    # and without the flag a CPU gives no number
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "reference_check.py"),
         "--config-dir", str(tmp_path), "--seed", "1", "--out",
         str(tmp_path / "none.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3 and not (tmp_path / "none.json").exists()
