"""A stack of Mamba-2 mixers and attention by kind with an MLP in every
layer (Granite 4.0-H) through the engine: a state a slot beside rows
that hold two heads of 64 each (``KVCache.ssm`` / ``.conv``, the shapes
``ModelConfig.state_shapes``'; ``kv_row_shapes``). Prefill hands the
state back, insert places it, slots are filled and freed out of order
and a slot that changes hands starts from the new prompt's, ``/healthz``
and the exporters count it under the mixer's kind, and whatever would
move or reuse a slot without its state is refused at the start, by
name."""

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
from perfbench.reference import granite_hybrid as ref

HF = {
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


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        config_from_hf(HF, "tiny-granite-hybrid"), dtype="float32"
    )
    return cfg, init_params(cfg, jax.random.key(0), jnp.float32)


def prompt(n, start=5):
    return [(start + 7 * i) % 250 + 5 for i in range(n)]


def test_the_engine_serves_the_reference_s_tokens_and_counts_the_state(model):
    """Four requests over two slots, of lengths that free the slots out
    of the order they were filled in (both slots change hands), greedy:
    every token is the argmax of the reference's full forward over the
    prompt and what was generated so far."""
    cfg, params = model
    eng = LLMEngine(cfg, params, max_slots=2, max_seq_len=64)
    health = eng.health()
    # 6 Mamba-2 layers: [H, P, N] float32 and 3 rows of xBC (float32
    # conv rows here); 2 attention layers' rows, two heads of 64 a row
    state = 2 * 6 * (32 * 16 * 16 * 4 + 3 * 544 * 4)
    assert health["cache"] == {
        "kv_bytes": 2 * 2 * 2 * 64 * 1 * 128 * 4, "state_bytes": state,
        "state_dtype": "float32", "window_bytes": 0,
    }
    assert health["state_mixer"] == "ssm"
    assert (health["ssm_scan"], health["ssm_update"]) == (
        "chunked_einsum", "xla"
    )
    assert health["kv_cache_bytes_per_token"] == 2 * 2 * 64 * 4   # a layer
    reqs = [
        GenRequest(prompt_ids=prompt(n, n), max_tokens=m, temperature=0.0)
        for n, m in ((7, 9), (13, 3), (20, 6), (9, 4))
    ]
    eng.start()
    try:
        done = [eng.generate(r) for r in reqs]
    finally:
        eng.stop()
    assert [len(r.output_ids) for r in done] == [9, 3, 6, 4]
    for r in done:
        seq = list(r.prompt_ids) + list(r.output_ids)
        n = len(r.prompt_ids)
        want, _ = ref.forward(
            params, HF, seq, list(range(n - 1, len(seq) - 1))
        )
        assert list(np.argmax(np.asarray(want), -1)) == list(r.output_ids)
    records = eng.flight.snapshot()
    assert sum(e["ssm_tokens"] for e in records) == 7 + 13 + 20 + 9
    assert max(e["state_slots"] for e in records) >= 1
    assert {e["state_mixer"] for e in records} == {"ssm"}
    text = "\n".join(eng.flight.metrics_lines())
    assert (
        'gpustack_engine_ssm_tokens_total{kind="prefill",mixer="ssm"} 49'
        in text
    )
    decoded = re.search(
        r'gpustack_engine_ssm_tokens_total\{kind="decode",mixer="ssm"\} '
        r"(\d+)", text,
    )
    assert decoded and int(decoded.group(1)) >= 8 + 2 + 5 + 3


def test_a_slot_that_changes_hands_starts_clean_and_leaves_its_neighbours(model):
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=3, max_seq_len=64)
    state = runner.new_state()
    for slot, n in ((0, 9), (1, 17), (2, 5)):
        ids = prompt(n, slot)
        _, k, v, mixer = runner.prefill(ids + [0] * (32 - n), n)
        assert mixer[0].shape == (6, 32, 16, 16) and mixer[1].shape == (6, 1632)
        assert k.shape[-2:] == (1, 128)
        state = runner.insert(
            state, k, v, slot, n, 7, 0.0, 0, 1.0, mixer=mixer
        )
    for _ in range(3):
        state, _ = runner.decode_step(state, jax.random.key(0))
    before = jnp.array(state.cache.ssm), jnp.array(state.cache.conv)
    state = runner.deactivate(state, 1)
    ids = prompt(11, 40)
    _, k, v, mixer = runner.prefill(ids + [0] * (32 - 11), 11)
    state = runner.insert(state, k, v, 1, 11, 7, 0.0, 0, 1.0, mixer=mixer)
    np.testing.assert_array_equal(state.cache.ssm[:, 1], mixer[0])
    np.testing.assert_array_equal(state.cache.conv[:, 1], mixer[1])
    for other in (0, 2):
        np.testing.assert_array_equal(
            state.cache.ssm[:, other], before[0][:, other]
        )
        np.testing.assert_array_equal(
            state.cache.conv[:, other], before[1][:, other]
        )
    fresh = runner.insert(
        runner.new_state(), k, v, 1, 11, 7, 0.0, 0, 1.0, mixer=mixer
    )
    state, out_a = runner.decode_step(state, jax.random.key(1))
    fresh, out_b = runner.decode_step(fresh, jax.random.key(1))
    np.testing.assert_allclose(
        np.asarray(out_a[3])[1], np.asarray(out_b[3])[1], rtol=1e-5, atol=1e-5
    )
    # an insert without a state (rows alone) clears the slot's
    blank = runner.insert(state, k, v, 2, 11, 7, 0.0, 0, 1.0)
    assert not np.asarray(blank.cache.ssm[:, 2]).any()
    assert not np.asarray(blank.cache.conv[:, 2]).any()


def test_an_ingest_takes_the_tokens_that_count_into_the_state(model):
    """A padded block over a cache: the chunked form from a carried
    state, over each row's ``counts`` tokens and no further."""
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=64)
    ids = prompt(10)
    _, k, v, mixer = runner.prefill(ids + [0] * 22, 10)

    def seeded():
        return runner.insert(
            runner.new_state(), k, v, 0, 10, 31, 0.0, 0, 1.0, mixer=mixer
        )

    block = [[40, 41, 42, 0], [0, 0, 0, 0]]
    ingested = runner.ingest_step(seeded(), block, [3, 0])
    stepped = seeded()
    for tok in (31, 40, 41):
        stepped = dataclasses.replace(
            stepped, last_tokens=stepped.last_tokens.at[0].set(tok)
        )
        stepped, _ = runner.decode_step(stepped, jax.random.key(0))
    np.testing.assert_allclose(
        ingested.cache.ssm[:, 0], stepped.cache.ssm[:, 0], rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(
        ingested.cache.conv[:, 0], stepped.cache.conv[:, 0],
        rtol=2e-4, atol=2e-5,
    )


@pytest.mark.parametrize(
    "asked,names",
    [
        ({"speculative": "ngram"}, "verify step"),
        ({"host_kv_cache_mb": 8}, "prefix cache"),
        ({"kv_spill_mb": 8}, "spill tier"),
        ({"kv_role": "prefill"}, "KV handoff"),
        ({"kv_role": "decode"}, "KV handoff"),
        ({"prefill_chunk": 16}, "chunk"),
    ],
    ids=["speculative", "prefix_cache", "spill", "transfer_prefill",
         "transfer_decode", "chunked_prefill"],
)
def test_what_would_move_a_slot_without_its_state_is_refused_at_the_start(
    model, asked, names
):
    cfg, params = model
    with pytest.raises(ValueError, match=names) as e:
        LLMEngine(cfg, params, max_slots=2, max_seq_len=32, **asked)
    assert "state-space layers" in str(e.value)
    assert cfg.name in str(e.value)


@pytest.mark.parametrize(
    "plan", [MeshPlan(sp=2), MeshPlan(tp=2), MeshPlan(dp=2)],
    ids=["ring", "tp", "dp"],
)
def test_a_mesh_of_several_devices_is_refused_by_name(model, plan):
    cfg, params = model
    with pytest.raises(ValueError, match="ring") as e:
        ModelRunner(cfg, params, plan=plan, max_slots=2, max_seq_len=32)
    assert "recurrent state" in str(e.value)


def test_the_runner_refuses_the_steps_that_cannot_carry_a_state(model):
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=32)
    ids = prompt(8)
    _, k, v, _ = runner.prefill(ids + [0] * 24, 8)
    with pytest.raises(ValueError, match="recurrent state"):
        runner.prefill_with_prefix(k, v, 8, [1] * 32, 4, 32)
    with pytest.raises(ValueError, match="roll a recurrent state back"):
        runner.verify_step(runner.new_state(), jnp.zeros((2, 4), jnp.int32))
