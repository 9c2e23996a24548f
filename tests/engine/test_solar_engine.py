"""Solar-Open2 through the engine: KDA layers' matrix state a head a
slot beside three attention layers' rows, routed experts under a share
in every layer of the scan over periods. ``/healthz`` says the state's
share of a slot's memory and the experts held, the exporters count the
state's tokens under ``mixer="kda"`` and the experts a decode step read,
and the tokens served are the float32 reference's."""

import dataclasses
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.models.config import config_from_hf
from gpustack_tpu.models.transformer import init_params
from perfbench.reference import solar_open2 as ref

HF = {
    "architectures": ["SolarOpen2ForCausalLM"], "model_type": "solar_open2",
    "partial_rotary_factor": 1,
    "linear_attn_config": {
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
        "num_kv_heads": None,
    },
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 264,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
    "experts_held": {"of": 16, "first": 4},
}


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        config_from_hf(HF, "tiny-solar-open2"), dtype="float32"
    )
    return cfg, init_params(cfg, jax.random.key(0), jnp.float32)


def prompt(n, start=5):
    return [(start + 7 * i) % 250 + 5 for i in range(n)]


@pytest.mark.parametrize("touched", [False, True], ids=["dense", "touched"])
def test_the_engine_serves_the_reference_s_tokens_and_counts(model, touched):
    """Four requests over three slots (one slot changes hands), greedy:
    every token is the argmax of the reference's full forward over the
    prompt and what was generated so far; a decode step's experts go
    through the touched kernel (interpret mode) as on the chip, or every
    held one."""
    from gpustack_tpu.engine import runner as runner_module
    from gpustack_tpu.models import transformer

    cfg, params = model
    dispatch = transformer.moe_dispatch

    def on_a_chip(rows, cfg, platform, mesh, decode=False):
        name = dispatch(rows, cfg, "tpu", None, decode=decode)
        return name + "_interpret" if name == "touched" else "dense"

    chooser = on_a_chip if touched else dispatch
    with mock.patch.object(transformer, "moe_dispatch", chooser), \
            mock.patch.object(runner_module, "moe_dispatch", chooser):
        eng = LLMEngine(cfg, params, max_slots=3, max_seq_len=64)
        health = eng.health()
        reqs = [
            GenRequest(prompt_ids=prompt(n, n), max_tokens=6, temperature=0.0)
            for n in (7, 13, 20, 9)
        ]
        eng.start()
        try:
            done = [eng.generate(r) for r in reqs]
            after = eng.health()
        finally:
            eng.stop()
    # 6 KDA layers: [Dk, H * Dv] float32 and 3 rows of q | k | v
    # (float32 conv rows here); 2 attention layers' rows
    state = 3 * 6 * (16 * 64 * 4 + 3 * 192 * 4)
    rows = 2 * 2 * 3 * 64 * 2 * 16 * 4
    assert health["cache"] == {
        "kv_bytes": rows, "state_bytes": state, "state_dtype": "float32",
        "window_bytes": 0,
    }
    assert health["state_share_pct"] == round(
        100.0 * state / (state + rows), 2
    )
    assert health["state_mixer"] == "kda"
    assert health["ssm_scan"] == "chunked_einsum"
    assert health["experts_held"] == {"held": 8, "of": 16, "first": 4}
    assert health["decode_moe_dispatch"] == (
        "touched_interpret" if touched else "dense"
    )
    for r in done:
        seq = list(r.prompt_ids) + list(r.output_ids)
        n = len(r.prompt_ids)
        want, _ = ref.forward(
            params, HF, seq, list(range(n - 1, len(seq) - 1))
        )
        assert list(np.argmax(np.asarray(want), -1)) == list(r.output_ids)
    records = eng.flight.snapshot()
    assert sum(e["ssm_tokens"] for e in records) == 7 + 13 + 20 + 9
    assert {e["state_mixer"] for e in records} == {"kda"}
    text = "\n".join(eng.flight.metrics_lines())
    assert (
        'gpustack_engine_ssm_tokens_total{kind="prefill",mixer="kda"} 49'
        in text
    )
    assert re.search(
        r'gpustack_engine_ssm_tokens_total\{kind="decode",mixer="kda"\} \d+',
        text,
    )
    # the experts-read counter counts in the scan over periods: 8 held
    # experts a layer, 8 layers
    held = re.search(
        r'gpustack_engine_moe_decode_experts_total\{kind="held"\} (\d+)', text
    )
    read = re.search(
        r'gpustack_engine_moe_decode_experts_total\{kind="read"\} (\d+)', text
    )
    assert held and read and int(held.group(1)) % (8 * 8) == 0
    shares = [e["moe_read_pct"] for e in records if "moe_read_pct" in e]
    assert shares
    if touched:
        assert 0 < int(read.group(1)) < int(held.group(1))
        assert max(shares) <= 100.0 and min(shares) < 100.0
    else:
        assert read.group(1) == held.group(1) and set(shares) == {100.0}
    # under a share the prefill programs' pairs are counted by whether
    # their expert is held here
    pairs = after["moe_pairs"]
    assert pairs["held"] > 0 and pairs["absent"] > 0
    assert (pairs["held"] + pairs["absent"]) % (4 * 8) == 0


@pytest.mark.parametrize(
    "asked,names",
    [
        ({"speculative": "ngram"}, "verify step"),
        ({"host_kv_cache_mb": 8}, "prefix cache"),
        ({"kv_spill_mb": 8}, "spill tier"),
        ({"kv_role": "prefill"}, "KV handoff"),
        ({"prefill_chunk": 16}, "chunk"),
    ],
    ids=["speculative", "prefix_cache", "spill", "transfer", "chunked_prefill"],
)
def test_what_would_move_a_slot_without_its_state_is_refused_at_the_start(
    model, asked, names
):
    cfg, params = model
    with pytest.raises(ValueError, match=names) as e:
        LLMEngine(cfg, params, max_slots=2, max_seq_len=32, **asked)
    assert "linear-attention layers" in str(e.value)
    assert cfg.name in str(e.value)
