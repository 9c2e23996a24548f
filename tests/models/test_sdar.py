"""SDAR-MoE (Qwen3-MoE's layers, generated by diffusion over blocks) at
a small size: the family's reader and what it refuses, the block mask in
``forward`` against the plain reference with and without a cache, a
prefill and then block passes through the cache against the reference's
full forward (logits, not tokens) for every ``P mod L``, in the XLA form
and with each kernel in interpret mode, and each of the three rules on
hand-made confidences."""

import contextlib
import dataclasses
import json
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.engine import runner as runner_module
from gpustack_tpu.engine import sampling
from gpustack_tpu.engine.runner import ModelRunner
from gpustack_tpu.models import transformer
from gpustack_tpu.models.config import (
    DIFFUSION_RULES,
    config_from_hf,
    load_hf_config,
)
from gpustack_tpu.models.transformer import KVCache, forward
from gpustack_tpu.ops import cache_write
from gpustack_tpu.testing import reference_sdar as ref
from gpustack_tpu.testing.sdar_small import GENERATION, HF, MASK_ID, small

L = 4


@pytest.fixture(scope="module")
def model():
    return small(quantized=True)


# ---- the reader -----------------------------------------------------------


def test_the_reader_takes_the_file_as_qwen3_moe_s_and_the_family_s_defaults():
    published = {**HF, "vocab_size": 151936}
    cfg = config_from_hf(published, "x")
    twin = config_from_hf(
        {**published, "architectures": ["Qwen3MoeForCausalLM"],
         "model_type": "qwen3_moe"}, "x",
    )
    mine = {
        "diffusion_block": 4, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_dynamic",
        "confidence_threshold": 0.9, "mask_token_id": 151669,
    }
    # what the released Chat checkpoints generate with; the id lies in
    # the published vocabulary, not in the tests' small one
    with pytest.raises(AssertionError):
        config_from_hf(HF, "x")
    assert {k: getattr(cfg, k) for k in mine} == mine
    assert dataclasses.replace(
        cfg, **{k: getattr(twin, k) for k in mine}
    ) == twin
    assert (twin.diffusion_block, twin.mask_token_id) == (0, -1)
    assert cfg.qk_norm and cfg.is_moe and not cfg.sliding_window


def test_a_generation_config_beside_the_file_says_what_a_deployment_runs(
    tmp_path,
):
    (tmp_path / "config.json").write_text(json.dumps(HF))
    (tmp_path / "generation_config.json").write_text(json.dumps({
        **GENERATION, "remasking_strategy": "low_confidence_static",
        "denoising_steps": 2,
    }))
    cfg = load_hf_config(str(tmp_path))
    assert (
        cfg.diffusion_block, cfg.denoising_steps, cfg.remasking_strategy,
        cfg.mask_token_id,
    ) == (4, 2, "low_confidence_static", MASK_ID)
    # no other family's reader opens the file
    (tmp_path / "config.json").write_text(json.dumps({
        **HF, "architectures": ["Qwen3MoeForCausalLM"],
        "model_type": "qwen3_moe",
    }))
    assert load_hf_config(str(tmp_path)).diffusion_block == 0


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [1]),
    ("decoder_sparse_step", 2),
])
def test_the_reader_refuses_by_key_what_no_layer_here_does(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**HF, key: value}, "x", GENERATION)


@pytest.mark.parametrize("generation", [
    {"block_length": 3}, {"block_length": 1}, {"denoising_steps": 5},
    {"denoising_steps": 0}, {"remasking_strategy": "random"},
    {"mask_token_id": 512},
])
def test_generation_numbers_that_nothing_serves_are_refused(generation):
    with pytest.raises(AssertionError):
        config_from_hf(HF, "x", {**GENERATION, **generation})


# ---- the mask in forward ----------------------------------------------------


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 400, size=n)


@pytest.mark.parametrize("cache", [False, True], ids=["no-cache", "cache"])
def test_forward_attends_under_the_block_mask(model, cache):
    cfg, params = model
    # whole blocks: over a cache a last block cut short would see the
    # rows nobody wrote (the runner prefills a prompt's whole blocks)
    ids = ids_of(24)
    positions = jnp.arange(24, dtype=jnp.int32)[None]
    logits, *_ = forward(
        params, cfg, jnp.asarray(ids)[None], positions,
        KVCache.create(cfg, 1, 32) if cache else None,
    )
    want, _ = ref.forward(params, HF, ids.tolist(), L)
    assert float(jnp.max(jnp.abs(logits[0] - want))) < 1e-4
    # and not under the causal one, which the reference can be given
    wrong, _ = ref.forward(params, HF, ids.tolist(), L, fault="causal_in_block")
    assert float(jnp.max(jnp.abs(logits[0] - wrong))) > 0.1


def test_the_mask_is_the_written_equation():
    want = [[k // L <= q // L for k in range(11)] for q in range(11)]
    assert np.array_equal(np.asarray(ref.block_mask(11, L)), np.array(want))


# ---- prefill, then passes through the cache ---------------------------------


def interpreted():
    """The choosers' answers for one TPU chip, the kernels in interpret
    mode: the decode kernel with the block folded into its group, the
    touched experts at ``B x L`` rows, the flash prefill with ``block``."""
    dispatch = transformer.moe_dispatch

    def attn(cfg, rows, max_len, platform, mesh):
        step = transformer.steps_over_cache(cfg, rows)
        return "kernel_interpret" if step else "xla"

    def moe(rows, cfg, platform, mesh, decode=False):
        name = dispatch(rows, cfg, "tpu", None, decode=decode)
        return name if name == "dense" else name + "_interpret"

    def prefill(platform, bucket, sp, cfg):
        return "flash_interpret" if bucket >= 128 else "xla"

    stack = contextlib.ExitStack()
    for where, name, fn in (
        (transformer, "decode_attention_impl", attn),
        (runner_module, "decode_attention_impl", attn),
        (transformer, "moe_dispatch", moe),
        (runner_module, "moe_dispatch", moe),
        (runner_module, "prefill_attention", prefill),
    ):
        stack.enter_context(mock.patch.object(where, name, fn))
    return stack


_PASSES = {}


def passes_in(model, kernels: bool):
    """Four prompts (``P mod L`` 0, 1, 2, 3; two of them in the bucket
    the flash kernel serves) prefilled, inserted and run for nine passes
    with the program's logits as an output: ``{P mod L: the largest
    difference of any pass's logits from the reference's full forward
    over [decided tokens ..., the block with its mask ids]}``, the forms
    chosen, the blocks each slot completed, how often the block's row
    write was traced as the tile call, and each pass's logits, blocks and
    commits and the cache they left, for a comparison of the two forms.
    Once a process a form."""
    if kernels in _PASSES:
        return _PASSES[kernels]
    cfg, params = model
    with contextlib.ExitStack() as stack:
        if kernels:
            stack.enter_context(interpreted())
        tile_write = stack.enter_context(mock.patch.object(
            cache_write, "gqa_write_block_rows",
            wraps=cache_write.gqa_write_block_rows,
        ))
        r = ModelRunner(
            cfg, params, max_slots=4, max_seq_len=256,
            prefill_buckets=(32, 128, 256),
        )
        prompts = [ids_of(n, n).tolist() for n in (100, 41, 102, 43)]
        state = r.new_state()
        for slot, p in enumerate(prompts):
            whole = len(p) // L * L
            b = r.bucket_for(whole)
            _, k, v = r.prefill(p[:whole] + [0] * (b - whole), whole)
            state = r.insert_block(state, k, v, slot, p, 0.0, 0, 1.0)
        seqs = [list(p[: len(p) // L * L]) for p in prompts]
        worst = {len(p) % L: 0.0 for p in prompts}
        blocks = [0] * 4
        key = np.zeros((2,), np.uint32)
        trail = []
        for _ in range(9):
            tokens = np.asarray(state.block_tokens)
            decided = np.asarray(state.block_decided)
            state, out = r.denoise_step(state, key, probe=True)
            committed, logits = np.asarray(out[2]), np.asarray(out[7])
            trail.append((tokens, decided, committed, logits))
            for slot, p in enumerate(prompts):
                ids = ref.pass_ids(
                    seqs[slot], tokens[slot], decided[slot], MASK_ID, len(p)
                )
                want, _ = ref.forward(
                    params, HF, ids + [0] * (128 - len(ids)), L
                )
                at = len(seqs[slot])
                worst[len(p) % L] = max(worst[len(p) % L], float(np.max(
                    np.abs(np.asarray(want)[at: at + L] - logits[slot])
                )))
                if committed[slot]:
                    assert decided[slot].all()
                    seqs[slot] += [int(t) for t in tokens[slot]]
                    blocks[slot] += 1
        positions = np.asarray(state.positions).tolist()
        cache = (np.asarray(state.cache.k), np.asarray(state.cache.v))
    _PASSES[kernels] = {
        "worst": worst, "blocks": blocks, "positions": positions,
        "forms": (r.decode_attention, r.decode_moe_dispatch),
        "kernels": kernels, "tile_writes": tile_write.call_count,
        "trail": trail, "cache": cache,
    }
    return _PASSES[kernels]


@pytest.fixture(scope="module", params=["xla", "kernels"])
def passes(request, model):
    return passes_in(model, request.param == "kernels")


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_passes_through_the_cache_are_the_reference_s_full_forward(
    passes, tail
):
    """Denoise passes at 0 to 3 decided positions, the commit pass
    (checked by the next block's passes, which attend the rows it kept)
    and the block after it."""
    assert passes["worst"][tail] < 2e-4, passes


def test_the_passes_ran_in_the_forms_the_choosers_named(passes):
    assert passes["forms"] == (
        ("kernel_interpret", "touched_interpret") if passes["kernels"]
        else ("xla", "dense")
    )
    # nine passes: a tail of t leaves 4 - t + 1 passes to the first
    # commit, then 5 a block
    assert passes["blocks"] == [1, 2, 2, 2]
    assert passes["positions"] == [104, 48, 108, 48]
    # a block of 4 rows of 2 heads is a float32 tile: the pass's row
    # write is the tile call, traced once (the layers' scan), and a
    # prefill's, or anything's in the XLA form, never
    assert passes["tile_writes"] == (1 if passes["kernels"] else 0)


def test_the_tile_call_leaves_what_the_scatter_leaves_over_two_blocks(model):
    """The same nine passes with the block's rows written by the tile
    call (the kernels' form) and by the scatter (the XLA form): three
    slots finish a block, commit it and denoise the next over the rows
    the commit kept. The same blocks and commits pass for pass, logits
    and the cache left behind to the rounding of the two attention
    forms."""
    scatter, call = passes_in(model, False), passes_in(model, True)
    assert (scatter["tile_writes"], call["tile_writes"]) == (0, 1)
    assert scatter["blocks"] == call["blocks"] == [1, 2, 2, 2]
    for a, b in zip(scatter["trail"], call["trail"]):
        for x, y in zip(a[:3], b[:3]):          # tokens, decided, committed
            assert np.array_equal(x, y)
        np.testing.assert_allclose(a[3], b[3], atol=2e-4)
    for x, y in zip(scatter["cache"], call["cache"]):
        np.testing.assert_allclose(x, y, atol=2e-5)
    # layer 0's rows come of the embedding alone: bit for bit
    assert np.array_equal(scatter["cache"][0][0], call["cache"][0][0])


# ---- the rules ---------------------------------------------------------------

CONF = jnp.asarray([
    [0.30, 0.95, 0.10, 0.92],
    [0.50, 0.20, 0.91, 0.40],
    [0.10, 0.20, 0.30, 0.40],
], jnp.float32)
OPEN = jnp.asarray([
    [True, True, True, True],
    [False, True, True, True],
    [False, False, True, True],
])


def decided(rule, quota, threshold=0.9):
    return np.asarray(sampling.decide(
        rule, OPEN, CONF, jnp.full((3,), quota, jnp.int32), threshold
    )).astype(int).tolist()


@pytest.mark.parametrize("rule,quota,want", [
    # the first undecided ones
    ("sequential", 1, [[1, 0, 0, 0], [0, 1, 0, 0], [0, 0, 1, 0]]),
    ("sequential", 2, [[1, 1, 0, 0], [0, 1, 1, 0], [0, 0, 1, 1]]),
    ("sequential", 4, [[1, 1, 1, 1], [0, 1, 1, 1], [0, 0, 1, 1]]),
    # the ones of largest confidence among the undecided
    ("low_confidence_static", 1, [[0, 1, 0, 0], [0, 0, 1, 0], [0, 0, 0, 1]]),
    ("low_confidence_static", 2, [[0, 1, 0, 1], [0, 0, 1, 1], [0, 0, 1, 1]]),
    # every one over the threshold where those are at least the quota,
    # else the ones of largest confidence
    ("low_confidence_dynamic", 1, [[0, 1, 0, 1], [0, 0, 1, 0], [0, 0, 0, 1]]),
    ("low_confidence_dynamic", 2, [[0, 1, 0, 1], [0, 0, 1, 1], [0, 0, 1, 1]]),
    ("low_confidence_dynamic", 3, [[1, 1, 0, 1], [0, 1, 1, 1], [0, 0, 1, 1]]),
])
def test_each_rule_decides_the_positions_the_published_rule_decides(
    rule, quota, want
):
    assert decided(rule, quota) == want


def test_a_decided_position_never_goes_back_and_ties_go_left():
    for rule in DIFFUSION_RULES:
        for quota in (1, 2, 3, 4):
            got = np.asarray(decided(rule, quota), bool)
            assert not (got & ~np.asarray(OPEN)).any(), (rule, quota)
            # a pass decides its quota, or all that is left
            assert (got.sum(1) >= np.minimum(quota, np.asarray(OPEN).sum(1))).all()
    flat = jnp.full((1, 4), 0.5, jnp.float32)
    got = sampling.decide(
        "low_confidence_static", jnp.ones((1, 4), bool), flat,
        jnp.asarray([2], jnp.int32),
    )
    assert np.asarray(got).tolist() == [[True, True, False, False]]
    with pytest.raises(ValueError):
        sampling.decide("random", OPEN, CONF, jnp.ones((3,), jnp.int32))


@pytest.mark.parametrize("block,steps,want", [
    (4, 4, [1, 1, 1, 1]), (4, 2, [2, 2]), (4, 3, [2, 1, 1]), (8, 3, [3, 3, 2]),
])
def test_the_quota_spreads_the_remainder_over_the_first_passes(
    block, steps, want
):
    got = sampling.pass_quota(jnp.arange(steps), block, steps)
    assert np.asarray(got).tolist() == want and sum(want) == block


def test_block_sampling_never_takes_the_mask_token_and_says_its_confidence():
    """Greedy rows: the candidate is the row's argmax but for the mask
    id, whatever a bias says of it; the confidence its share of the
    candidates at temperature 1; a seeded row's draw follows its
    position, not its place in the batch."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(2, 4, 512)), jnp.float32)
    logits = logits.at[:, :, MASK_ID].set(50.0)
    state = sampling.SamplingState.create(2)
    state = state.set_slot(
        0, 0.0, 0, 1.0,
        bias_ids=jnp.full((sampling.MAX_BIAS,), -1).at[0].set(MASK_ID),
        bias_vals=jnp.zeros((sampling.MAX_BIAS,)).at[0].set(100.0),
    )
    state = state.set_slot(1, 1.0, 0, 1.0, seed=5, seeded=True)
    positions = jnp.asarray([[8, 9, 10, 11], [20, 21, 22, 23]], jnp.int32)
    key = jax.random.key(0)
    cand, lp, top_ids, top_lps, conf = sampling.sample_block(
        logits, state, key, positions, MASK_ID
    )
    plain = np.array(logits)
    plain[:, :, MASK_ID] = -np.inf
    assert np.asarray(cand)[0].tolist() == plain[0].argmax(-1).tolist()
    assert (np.asarray(cand) != MASK_ID).all()
    assert (np.asarray(top_ids) != MASK_ID).all()
    top = np.sort(plain[0], axis=-1)[:, -sampling.CAND:]
    want = np.exp(top[:, -1]) / np.exp(top).sum(-1)
    assert np.allclose(np.asarray(conf)[0], want, rtol=1e-4)
    assert ((np.asarray(conf) > 0) & (np.asarray(conf) <= 1)).all()
    assert np.isfinite(np.asarray(lp)).all()
    # the seeded slot alone, in another row of another batch
    again = sampling.sample_block(
        logits[1:], jax.tree.map(lambda a: a[1:], state),
        jax.random.key(9), positions[1:], MASK_ID,
    )
    assert np.asarray(again[0])[0].tolist() == np.asarray(cand)[1].tolist()
