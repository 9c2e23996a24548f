"""HBM estimation + chips-per-replica ladder (replaces the reference's
gguf-parser-driven estimate tests, tests/fixtures/estimates/**)."""

import pytest

from gpustack_tpu.scheduler.calculator import (
    EvaluationError,
    chips_for_claim,
    evaluate_model,
    resolve_model_config,
)
from gpustack_tpu.schemas import Model

_GIB = 2**30


def test_llama3_8b_bf16_needs_two_v5e_chips():
    model = Model(
        name="m", preset="llama3-8b", max_seq_len=2048, max_slots=8
    )
    ev = evaluate_model(model)
    # 8.03B params * 2 bytes ≈ 16.06 GB = 14.96 GiB
    assert 14.5 * _GIB < ev.weight_bytes < 15.5 * _GIB
    claim = chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8)
    assert claim is not None
    assert claim.chips == 2
    assert "tp2" in claim.mesh_plan


def test_llama3_8b_int8_fits_one_chip():
    model = Model(
        name="m", preset="llama3-8b", quantization="int8",
        max_seq_len=2048, max_slots=8,
    )
    ev = evaluate_model(model)
    claim = chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8)
    assert claim is not None and claim.chips == 1


def test_llama3_70b_needs_multihost_on_v5e():
    model = Model(
        name="m", preset="llama3-70b", max_seq_len=2048, max_slots=8
    )
    ev = evaluate_model(model)
    # no fit within one 8-chip host
    assert chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8) is None
    claim = chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=32)
    assert claim is not None
    assert claim.chips == 16
    assert "tp8" in claim.mesh_plan  # kv_heads=8 caps TP at 8


def test_explicit_mesh_plan_respected():
    model = Model(name="m", preset="llama3-8b", quantization="int8")
    ev = evaluate_model(model)
    claim = chips_for_claim(
        ev, hbm_per_chip=16 * _GIB, max_chips=8,
        explicit_plan="dp2xtp4",
    )
    assert claim is not None
    assert claim.chips == 8
    assert claim.mesh_plan == "dp2xsp1xep1xtp4"


def test_explicit_chip_count_that_cannot_fit():
    model = Model(name="m", preset="llama3-70b", max_seq_len=2048)
    ev = evaluate_model(model)
    assert (
        chips_for_claim(
            ev, hbm_per_chip=16 * _GIB, max_chips=32, explicit_chips=2
        )
        is None
    )


def test_moe_plan_uses_ep():
    model = Model(
        name="m", preset="mixtral-8x7b", quantization="int8",
        max_seq_len=2048, max_slots=4,
    )
    ev = evaluate_model(model)
    claim = chips_for_claim(ev, hbm_per_chip=95 * _GIB, max_chips=4)
    assert claim is not None
    assert claim.chips == 1  # ~47 GB int8 fits one v5p chip

    claim = chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8)
    assert claim is not None and claim.chips == 4
    assert "ep2" in claim.mesh_plan and "tp2" in claim.mesh_plan


def test_long_context_plan_uses_sp():
    model = Model(
        name="m", preset="llama3-8b", quantization="int8",
        max_seq_len=32768, max_slots=4,
    )
    ev = evaluate_model(model)
    claim = chips_for_claim(
        ev, hbm_per_chip=16 * _GIB, max_chips=8, long_context=True
    )
    assert claim is not None
    # kv cache alone: 32k * 4 slots * 128 KiB/token = 16 GiB -> multi-chip
    assert claim.chips >= 2
    assert "sp" in claim.mesh_plan and "sp1" not in claim.mesh_plan


def test_resolve_errors():
    with pytest.raises(EvaluationError, match="unknown preset"):
        resolve_model_config(Model(name="x", preset="nope"))
    with pytest.raises(EvaluationError, match="no source"):
        resolve_model_config(Model(name="x"))
    with pytest.raises(EvaluationError, match="cannot fetch config"):
        resolve_model_config(
            Model(name="x", huggingface_repo_id="meta/llama")
        )


def _benchmark_s(directory, **spec):
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    return Model(
        name="m", quantization="int8", local_path=os.path.join(
            root, "perfbench", "configs", directory
        ), **spec,
    )


def _nemotron(**spec):
    return _benchmark_s("nemotron-3-nano-30b-a3b-int8-ep8", **spec)


def test_a_hybrid_claims_its_slots_states_beside_the_rows_of_six_layers():
    """Nemotron-3-Nano's share as the benchmark deploys it: the weights,
    and a slot a recurrent state of 23 layers (49.1 MB, whatever the
    context) and rows of the 6 attention layers only (6 KB a position,
    not 52 layers' 52 KB)."""
    ev = evaluate_model(_nemotron(max_seq_len=4096, max_slots=32))
    assert ev.config.layer_kinds is not None
    # 5.26 B parameters at a byte, and the experts' stored width
    assert 5.25e9 < ev.weight_bytes < 5.45e9
    state = 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    rows = 6 * 2 * 2 * 128 * 2 * 4096
    assert ev.kv_cache_bytes == 32 * (state + rows)
    assert round(state / 1e6, 1) == 49.1 and round(rows / 1e6, 1) == 25.2
    claim = chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8)
    assert claim is not None and claim.chips == 1
    # the state does not grow with the context, the rows do
    longer = evaluate_model(_nemotron(max_seq_len=8192, max_slots=32))
    assert longer.kv_cache_bytes - ev.kv_cache_bytes == 32 * rows


def test_a_hybrid_that_does_not_fit_one_chip_is_not_spread_over_more():
    """The runner serves such a model on one device: a claim of two
    chips would start an instance that refuses its mesh."""
    ev = evaluate_model(_nemotron(max_seq_len=4096, max_slots=160))
    assert ev.total_bytes > 16 * _GIB
    assert chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8) is None


def test_a_delta_rule_stack_claims_its_matrix_states_beside_eight_layers_rows():
    """Olmo-Hybrid-7B whole, as the benchmark deploys it: 7.43 B
    parameters at a byte, and a slot a matrix state of 24 layers (54.7
    MB whatever the context, nothing padded) and rows of the 8 attention
    layers only, 32 stored heads for the 30 (128 KB a position)."""
    ev = evaluate_model(
        _benchmark_s("olmo-hybrid-7b-int8", max_seq_len=2560, max_slots=12)
    )
    assert ev.config.layer_types is not None
    assert 7.4e9 < ev.weight_bytes < 7.6e9
    state = 24 * (96 * 5760 * 4 + 3 * 11520 * 2)
    rows = 8 * 2 * 32 * 128 * 2 * 2560
    assert ev.kv_cache_bytes == 12 * (state + rows)
    assert round(state / 1e6, 1) == 54.7 and round(rows / 1e6, 1) == 335.5
    claim = chips_for_claim(ev, hbm_per_chip=16 * _GIB, max_chips=8)
    assert claim is not None and claim.chips == 1
    # served on one device: what does not fit one chip is not spread
    more = evaluate_model(
        _benchmark_s("olmo-hybrid-7b-int8", max_seq_len=2560, max_slots=24)
    )
    assert more.total_bytes > 16 * _GIB
    assert chips_for_claim(more, hbm_per_chip=16 * _GIB, max_chips=8) is None
