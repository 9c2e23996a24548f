"""A stack that keeps its sliding layers' rows at window size through the
engine: a second store a slot beside ``k`` and ``v`` (``KVCache.wk`` /
``.wv``, a ring of window rows). Prefill hands its rows back, insert
places them, a slot that changes hands is the new prompt's, ``/healthz``,
the exporters and the flight records report both stores and the rows
attended, and whatever would need rows a ring has overwritten is refused
at the start, by name."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.engine.runner import ModelRunner
from gpustack_tpu.models.config import config_from_hf
from gpustack_tpu.models.transformer import init_params
from gpustack_tpu.parallel.mesh import MeshPlan
from perfbench.reference import cohere2_moe as ref

WINDOW = 8
HF = {
    "architectures": ["Cohere2MoeForCausalLM"], "model_type": "cohere2_moe",
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 264, "sliding_window": WINDOW, "num_experts": 4,
    "experts_held": {"of": 8, "first": 2}, "num_experts_per_tok": 2,
    "num_shared_experts": 4, "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "logit_scale": 1, "tie_word_embeddings": True, "norm_topk_prob": True,
}


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        config_from_hf(HF, "tiny-command-a-plus"), dtype="float32"
    )
    return cfg, init_params(cfg, jax.random.key(0), jnp.float32)


def prompt(n, start=5):
    return [(start + 7 * i) % 250 + 5 for i in range(n)]


def test_the_engine_serves_the_reference_s_tokens_and_reports_both_stores(model):
    """Four requests over three slots (one slot changes hands), greedy,
    prompts under, at and over the window, outputs that wrap the ring:
    every token is the argmax of the reference's full forward over the
    prompt and what was generated so far."""
    cfg, params = model
    eng = LLMEngine(cfg, params, max_slots=3, max_seq_len=64)
    health = eng.health()
    row = 2 * 2 * 16 * 4            # k and v, 2 heads of 16, float32
    assert health["cache"] == {
        "kv_bytes": 2 * 3 * 64 * row, "state_bytes": 0, "state_dtype": None,
        "window_bytes": 6 * 3 * WINDOW * row,
    }
    assert health["kv_cache_bytes"] == health["cache"]["kv_bytes"]
    assert health["kv_cache_bytes_per_token"] == row
    lengths = (5, 13, 20, WINDOW)
    reqs = [
        GenRequest(prompt_ids=prompt(n, n), max_tokens=11, temperature=0.0)
        for n in lengths
    ]
    eng.start()
    try:
        done = [eng.generate(r) for r in reqs]
    finally:
        eng.stop()
    for r in done:
        seq = list(r.prompt_ids) + list(r.output_ids)
        n = len(r.prompt_ids)
        want, _ = ref.forward(
            params, HF, seq, list(range(n - 1, len(seq) - 1))
        )
        assert list(np.argmax(np.asarray(want), -1)) == list(r.output_ids)
    records = eng.flight.snapshot()
    # the prefills' part: a sliding layer's band and a full layer's
    # triangle over every prompt, times the layers of the kind
    def band(n):
        w = min(n, WINDOW)
        return w * (w + 1) // 2 + (n - w) * w

    prefills = [e for e in records if e["mode"] == "prefill"]
    assert sum(e["window_rows"] for e in prefills) >= 6 * sum(
        band(n) for n in lengths
    )
    assert sum(e["full_rows"] for e in prefills) >= 2 * sum(
        n * (n + 1) // 2 for n in lengths
    )
    # a decode step of one slot of 20+ positions: 8 rows a sliding layer
    decodes = [
        e for e in records
        if e["mode"] == "decode" and e["slots_used"] == 1
        and e.get("full_rows", 0) >= 2 * 20
    ]
    assert decodes and all(
        e["window_rows"] == 6 * WINDOW for e in decodes
    )
    text = "\n".join(eng.flight.metrics_lines())
    got = {
        kind: int(n) for kind, n in re.findall(
            r'gpustack_engine_attn_rows_total\{layer="(\w+)"\} (\d+)', text
        )
    }
    assert got == {
        "sliding": sum(e["window_rows"] for e in records),
        "full": sum(e["full_rows"] for e in records),
    }
    assert got["full"] > got["sliding"] / 3 > 0


def test_a_model_without_a_window_store_reports_none_of_it():
    from gpustack_tpu.models.config import get_config

    cfg = dataclasses.replace(get_config("tiny"), dtype="float32")
    eng = LLMEngine(
        cfg, init_params(cfg, jax.random.key(0), jnp.float32),
        max_slots=2, max_seq_len=32,
    )
    assert eng.health()["cache"]["window_bytes"] == 0
    eng.start()
    try:
        eng.generate(GenRequest(prompt_ids=prompt(5), max_tokens=3))
    finally:
        eng.stop()
    records = eng.flight.snapshot()
    assert records and all("window_rows" not in e for e in records)
    assert "gpustack_engine_attn_rows_total" not in "\n".join(
        eng.flight.metrics_lines()
    )


def test_the_exporter_names_the_three_kinds_of_cache_bytes(model):
    import asyncio

    from gpustack_tpu.engine.api_server import OpenAIServer

    cfg, params = model
    eng = LLMEngine(cfg, params, max_slots=2, max_seq_len=32)
    text = asyncio.run(
        OpenAIServer(eng, model_name="m").metrics(None)
    ).text
    row = 2 * 2 * 16 * 4
    assert f'gpustack_engine_cache_bytes{{kind="kv"}} {2 * 2 * 32 * row}' in text
    assert (
        f'gpustack_engine_cache_bytes{{kind="window"}} '
        f"{6 * 2 * WINDOW * row}" in text
    )
    assert 'gpustack_engine_cache_bytes{kind="state"} 0' in text


def test_a_slot_that_changes_hands_is_the_new_prompt_s(model):
    """The ring of a slot's last tenant, longer than the new prompt, is
    never attended: the new tenant's tokens are the reference's."""
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=64)
    state = runner.new_state()
    old = prompt(21, 3)
    _, k, v, rows = runner.prefill(old + [0] * (32 - 21), 21)
    assert rows[0].shape == (6, WINDOW, 2, 16)
    state = runner.insert(state, k, v, 1, 21, 9, 0.0, 0, 1.0, mixer=rows)
    for _ in range(5):
        state, _ = runner.decode_step(state, jax.random.key(0))
    state = runner.deactivate(state, 1)
    new = prompt(3, 77)
    last, k, v, rows = runner.prefill(new + [0] * (32 - 3), 3)
    first = int(np.argmax(np.asarray(last)))
    state = runner.insert(state, k, v, 1, 3, first, 0.0, 0, 1.0, mixer=rows)
    out = []
    for _ in range(7):
        state, (sampled, *_rest) = runner.decode_step(
            state, jax.random.key(0)
        )
        out.append(int(sampled[1]))
    seq = new + [first] + out
    want, _ = ref.forward(params, HF, seq, list(range(2, len(seq) - 1)))
    assert list(np.argmax(np.asarray(want), -1)) == [first] + out


REFUSED = {
    "speculative": (dict(speculative="ngram"), "verify step cannot roll back"),
    "prefix_cache": (dict(host_kv_cache_mb=8), "prefix cache"),
    "spill": (dict(host_kv_cache_mb=0, kv_spill_mb=8), "spill tier"),
    "handoff": (dict(kv_role="prefill"), "KV handoff"),
    "chunked_prefill": (dict(prefill_chunk=16), "a chunk goes on from"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_cannot_carry_a_ring_is_refused_at_engine_start_by_name(
    model, what
):
    cfg, params = model
    options, why = REFUSED[what]
    with pytest.raises(ValueError) as err:
        LLMEngine(cfg, params, max_slots=2, max_seq_len=32, **options)
    assert "keeps its sliding layers' rows at window size" in str(err.value)
    assert why in str(err.value) and cfg.name in str(err.value)


def test_a_mesh_of_several_devices_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(ValueError, match="served on one device"):
        ModelRunner(
            cfg, params, plan=MeshPlan(tp=2), max_slots=2, max_seq_len=32
        )


def test_the_runner_refuses_what_the_engine_cannot_reach(model):
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=32)
    with pytest.raises(ValueError, match="window has passed"):
        runner.prefill_with_prefix(None, None, 0, [0] * 32, 1, 32)
    with pytest.raises(ValueError, match="ring has overwritten"):
        runner.verify_step(runner.new_state(), np.zeros((2, 4), np.int32))


def test_the_scheduler_s_fit_counts_both_stores():
    import os

    from gpustack_tpu.models.config import load_hf_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    cfg = load_hf_config(os.path.join(
        root, "perfbench", "configs", "command-a-plus-int8-ep8-l8"
    ))
    # 16 slots of 8,192: 2 full layers x 8,192 rows + 6 sliding x 4,096,
    # 4,096 B a position of a layer
    per_slot = (
        8192 * cfg.kv_cache_bytes_per_token()
        + cfg.window_bytes_per_slot(8192)
    )
    assert 16 * per_slot == 16 * (2 * 8192 + 6 * 4096) * 4096
    # a context under the window keeps no more rows than it has positions
    assert cfg.window_bytes_per_slot(1024) == 6 * 1024 * 4096
