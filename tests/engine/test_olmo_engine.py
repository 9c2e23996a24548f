"""A model with gated-delta-rule layers through the engine: a matrix
state a head a slot beside its rows, in the cache's fields a Mamba-2
state lies in (``KVCache.ssm`` / ``.conv``, the shapes
``ModelConfig.state_shapes``'). Prefill hands it back, insert places it,
a slot that changes hands starts from the new prompt's, ``/healthz`` and
the exporters count it under the mixer's kind, and whatever would move
or reuse a slot without its state is refused at the start, by name."""

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
from perfbench.reference import olmo_hybrid as ref

HF = {
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


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        config_from_hf(HF, "tiny-olmo-hybrid"), dtype="float32"
    )
    return cfg, init_params(cfg, jax.random.key(0), jnp.float32)


def prompt(n, start=5):
    return [(start + 7 * i) % 250 + 5 for i in range(n)]


def test_the_engine_serves_the_reference_s_tokens_and_counts_the_state(model):
    """Four requests over three slots (one slot changes hands), greedy:
    every token is the argmax of the reference's full forward over the
    prompt and what was generated so far."""
    cfg, params = model
    eng = LLMEngine(cfg, params, max_slots=3, max_seq_len=64)
    health = eng.health()
    # 6 linear layers: [Dk, H * Dv] float32 and 3 rows of q | k | v
    # (float32 conv rows here), nothing padded
    state = 3 * 6 * (6 * 48 * 4 + 3 * 96 * 4)
    assert health["cache"] == {
        "kv_bytes": 2 * 2 * 3 * 64 * 4 * 16 * 4, "state_bytes": state,
        "state_dtype": "float32", "window_bytes": 0,
    }
    assert health["state_mixer"] == "delta"
    assert (health["ssm_scan"], health["ssm_update"]) == (
        "chunked_einsum", "xla"
    )
    assert health["kv_cache_bytes_per_token"] == 2 * 4 * 16 * 4   # a layer
    reqs = [
        GenRequest(prompt_ids=prompt(n, n), max_tokens=6, temperature=0.0)
        for n in (7, 13, 20, 9)
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
    assert sum(e["ssm_tokens"] for e in records) == 7 + 13 + 20 + 9
    assert max(e["state_slots"] for e in records) >= 1
    assert {e["state_mixer"] for e in records} == {"delta"}
    text = "\n".join(eng.flight.metrics_lines())
    assert (
        'gpustack_engine_ssm_tokens_total{kind="prefill",mixer="delta"} 49'
        in text
    )
    decoded = re.search(
        r'gpustack_engine_ssm_tokens_total\{kind="decode",mixer="delta"\} '
        r"(\d+)", text,
    )
    assert decoded and int(decoded.group(1)) >= 4 * 5


def test_a_slot_that_changes_hands_starts_clean_and_leaves_its_neighbours(model):
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=3, max_seq_len=64)
    state = runner.new_state()
    for slot, n in ((0, 9), (1, 17), (2, 5)):
        ids = prompt(n, slot)
        _, k, v, mixer = runner.prefill(ids + [0] * (32 - n), n)
        assert mixer[0].shape == (6, 6, 48) and mixer[1].shape == (6, 288)
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
    assert "linear-attention layers" in str(e.value)
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
