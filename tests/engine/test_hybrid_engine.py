"""A model with state-space layers through the engine: a recurrent state
a slot beside its rows (``KVCache.ssm`` / ``.conv``). Prefill hands it
back, insert places it, a slot that changes hands starts from the new
prompt's and leaves its neighbours alone, snapshot and restore carry it,
``/healthz`` and the exporters report both kinds, and whatever would
move or reuse a slot without its state is refused at the start."""

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
from perfbench.reference import nemotron_h as ref

HF = {
    "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 264,
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
}


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        config_from_hf(HF, "tiny-nemotron-h"), dtype="float32"
    )
    return cfg, init_params(cfg, jax.random.key(0), jnp.float32)


def prompt(n, start=5):
    return [(start + 7 * i) % 250 + 5 for i in range(n)]


def test_the_engine_serves_the_reference_s_tokens_and_reports_both_kinds(model):
    """Four requests over three slots (one slot changes hands), greedy:
    every token is the argmax of the reference's full forward over the
    prompt and what was generated so far."""
    cfg, params = model
    eng = LLMEngine(cfg, params, max_slots=3, max_seq_len=64)
    health = eng.health()
    state = 3 * 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)    # float32 conv rows here
    assert health["cache"] == {
        "kv_bytes": 2 * 1 * 3 * 64 * 2 * 16 * 4, "state_bytes": state,
        "state_dtype": "float32", "window_bytes": 0,
    }
    assert (health["ssm_scan"], health["ssm_update"]) == (
        "chunked_einsum", "xla"
    )
    assert health["kv_cache_bytes_per_token"] == 2 * 2 * 16 * 4
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
    text = "\n".join(eng.flight.metrics_lines())
    assert 'gpustack_engine_ssm_tokens_total{kind="prefill"} 49' in text
    decoded = re.search(
        r'gpustack_engine_ssm_tokens_total\{kind="decode"\} (\d+)', text
    )
    assert decoded and int(decoded.group(1)) >= 4 * 5


def test_a_model_without_a_state_reports_none_of_it():
    from gpustack_tpu.models.config import get_config

    cfg = dataclasses.replace(get_config("tiny"), dtype="float32")
    eng = LLMEngine(
        cfg, init_params(cfg, jax.random.key(0), jnp.float32),
        max_slots=2, max_seq_len=32,
    )
    health = eng.health()
    assert health["cache"]["state_bytes"] == 0
    assert health["cache"]["state_dtype"] is None
    assert health["cache"]["kv_bytes"] == health["kv_cache_bytes"]
    assert health["ssm_scan"] is None and health["ssm_update"] is None
    eng.step()
    assert "gpustack_engine_ssm_tokens_total" not in "\n".join(
        eng.flight.metrics_lines()
    )


def test_a_slot_that_changes_hands_starts_clean_and_leaves_its_neighbours(model):
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=3, max_seq_len=64)
    state = runner.new_state()
    first = {}
    for slot, n in ((0, 9), (1, 17), (2, 5)):
        ids = prompt(n, slot)
        _, k, v, mixer = runner.prefill(ids + [0] * (32 - n), n)
        first[slot] = mixer
        state = runner.insert(
            state, k, v, slot, n, 7, 0.0, 0, 1.0, mixer=mixer
        )
    for _ in range(3):
        state, _ = runner.decode_step(state, jax.random.key(0))
    before = jnp.array(state.cache.ssm), jnp.array(state.cache.conv)
    # slot 1 ends and is given to another prompt
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
    # nothing of the last tenant's is left: the new tenant decodes as if
    # it had the slot from the start
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


def test_snapshot_and_restore_carry_the_state(model):
    """What a draft runner does around a proposal run: the steps in
    between move every live slot's state, and restoring puts back the
    snapshot's, bit for bit, with the positions and last tokens."""
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=64)
    ids = prompt(12)
    _, k, v, mixer = runner.prefill(ids + [0] * 20, 12)
    state = runner.insert(
        runner.new_state(), k, v, 0, 12, 9, 0.0, 0, 1.0, mixer=mixer
    )
    snap = runner.snapshot_sequence(state)
    assert len(snap) == 4
    state, first = runner.decode_step(state, jax.random.key(0))
    for _ in range(2):
        state, _ = runner.decode_step(state, jax.random.key(0))
    assert float(jnp.abs(state.cache.ssm[:, 0] - snap[2][:, 0]).max()) > 1e-4
    state = runner.restore_sequence(state, snap)
    np.testing.assert_array_equal(state.cache.ssm, snap[2])
    np.testing.assert_array_equal(state.cache.conv, snap[3])
    assert int(state.positions[0]) == 12
    _, again = runner.decode_step(state, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(first[0]), np.asarray(again[0]))
    np.testing.assert_allclose(
        np.asarray(first[3])[0], np.asarray(again[3])[0], rtol=1e-5, atol=1e-5
    )


def test_an_ingest_takes_the_tokens_that_count_into_the_state(model):
    """A draft's catch-up block is padded: the state moves over each
    row's ``counts`` tokens and no further."""
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
    for tok in (31, 40, 41):       # the verify feeding pattern: last first
        stepped = dataclasses.replace(
            stepped, last_tokens=stepped.last_tokens.at[0].set(tok)
        )
        # greedy decode would sample its own token; feed ours
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
        ({"host_kv_cache_mb": 8, "kv_spill_mb": 8}, "prefix cache"),
        ({"kv_spill_mb": 8}, "spill tier"),
        ({"kv_role": "prefill"}, "KV handoff"),
        ({"kv_role": "decode"}, "KV handoff"),
        ({"prefill_chunk": 16}, "chunk"),
    ],
    ids=["speculative", "prefix_cache", "prefix_cache_and_spill", "spill",
         "transfer_prefill", "transfer_decode", "chunked_prefill"],
)
def test_what_would_move_a_slot_without_its_state_is_refused_at_the_start(
    model, asked, names
):
    cfg, params = model
    with pytest.raises(ValueError, match=names) as e:
        LLMEngine(cfg, params, max_slots=2, max_seq_len=32, **asked)
    assert "state-space layers" in str(e.value) and cfg.name in str(e.value)


def test_a_draft_model_for_a_hybrid_target_is_refused_too(model):
    cfg, params = model
    with pytest.raises(ValueError, match="speculative='draft'"):
        LLMEngine(
            cfg, params, max_slots=2, max_seq_len=32, speculative="draft",
            draft_cfg=cfg, draft_params=params,
        )


@pytest.mark.parametrize(
    "plan", [MeshPlan(sp=2), MeshPlan(tp=2), MeshPlan(ep=2), MeshPlan(dp=2)],
    ids=["ring", "tp", "ep", "dp"],
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
