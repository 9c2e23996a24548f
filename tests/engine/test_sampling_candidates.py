"""``engine/sampling.py top_candidates``: the top ``CAND`` of a row found
from ``CAND`` chunks of 128 columns, against ``jax.lax.top_k`` over the
whole row — values, ids and order, ties included — and ``sample`` end to
end against the body it had when it ranked the whole row (kept below).

The cases of one shape share their compiled programs.
"""

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.engine import sampling
from gpustack_tpu.engine.sampling import (
    CAND,
    LANES,
    MAX_BIAS,
    TOPLP,
    SamplingState,
    candidate_chunks,
    candidates_form,
    sample,
    top_candidates,
)

# (slots, vocabulary): the three Qwen3 decode programs' and their
# first-token program's, A.X-K1's slice, a vocabulary that is no multiple
# of 128 (157 chunks, the last one padded), and one ranked whole
WIDE = [(1, 151936), (12, 151936), (32, 151936), (16, 20480)]
SHAPES = WIDE + [(4, 20000), (4, 5000)]
# the benchmark's logit_bias (perfbench/loadgen.py LOGIT_BIAS): the byte
# tokenizer's ids of 64 characters, +100 each
BENCH_BIAS_IDS = np.asarray([
    ord(c) + 1 for c in
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789 ."
], np.int32)


def test_which_rows_are_chunked():
    assert candidate_chunks(151936) == 1187
    assert candidate_chunks(20480) == 160
    assert candidate_chunks(20000) == 157
    assert candidate_chunks(5000) == 0
    assert candidate_chunks(sampling.CHUNKED_MIN_CHUNKS * LANES - LANES) == 0
    assert candidates_form(151936) == "chunked: 64 of 1187 chunks of 128"
    assert candidates_form(5000) == "whole row of 5000"
    assert candidates_form(32) == "whole row of 32"


# jitted once; a shape compiles once, whatever the case's inputs
CHUNKED = jax.jit(lambda x: top_candidates(x, CAND))
WHOLE = jax.jit(lambda x: jax.lax.top_k(x, CAND))


def _logits(kind: str, B: int, V: int) -> np.ndarray:
    rng = np.random.default_rng(B * V + len(kind))
    x = (rng.standard_normal((B, V)) * 4).astype(np.float32)
    if kind == "bf16":
        # 8 bits of mantissa: a few thousand distinct values a row, so
        # equal logits are everywhere, the 64th place included
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    elif kind == "neg_inf":
        # banned columns: scattered, whole chunks, and in the last row
        # all but 40 columns, so fewer than CAND are finite
        x[rng.random((B, V)) < 0.3] = -np.inf
        x[:, 3 * LANES:40 * LANES] = -np.inf
        keep = rng.choice(V, 40, replace=False)
        last = np.full((V,), -np.inf, np.float32)
        last[keep] = x[-1, keep]
        x[-1] = last
    elif kind == "straddle":
        # 60 columns above, then 200 equal ones in chunks all over the
        # row: the 61st to 64th are four of them, the lowest ids
        x = np.clip(x, -8, 8)
        for row in x:
            cols = rng.choice(V, 260, replace=False)
            row[cols[:60]] = 20 + rng.random(60).astype(np.float32)
            row[cols[60:]] = 15.0
    return x


@pytest.mark.parametrize("kind", ["float32", "bf16", "neg_inf", "straddle"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_the_candidates_are_lax_top_k_s(shape, kind):
    x = _logits(kind, *shape)
    vals, ids = (np.asarray(a) for a in CHUNKED(x))
    want_vals, want_ids = (np.asarray(a) for a in WHOLE(x))
    np.testing.assert_array_equal(vals, want_vals)
    # both put the lower id first among equals, so the ids agree too,
    # where equal logits straddle the 64th place as anywhere else
    np.testing.assert_array_equal(ids, want_ids)
    assert ids.max() < shape[1]
    if kind == "straddle":
        assert (vals[:, 60:] == 15.0).all() and (vals[:, 59] > 15.0).all()
        tied = np.sort(np.where(x == 15.0)[1].reshape(shape[0], -1), axis=1)
        np.testing.assert_array_equal(ids[:, 60:], tied[:, :4])


def _sample_over_the_whole_row(logits, state, key, positions):
    """``sample`` as it was before ``top_candidates`` (PR 35's tree),
    line for line but for the module's names."""
    B, V = logits.shape
    valid = state.bias_ids >= 0
    bias_cols = jnp.clip(state.bias_ids, 0, V - 1)
    bias_vals = jnp.where(valid, state.bias_vals, 0.0)
    logits = logits.at[
        jnp.arange(B)[:, None], bias_cols
    ].add(bias_vals)
    n = min(CAND, V)
    top_logits, top_idx = jax.lax.top_k(logits, n)   # [B, n] descending

    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = top_logits / temp

    k = jnp.where(state.top_k > 0, jnp.minimum(state.top_k, n), n)
    rank = jnp.broadcast_to(jnp.arange(n)[None, :], (B, n))
    masked = jnp.where(rank >= k[:, None], -jnp.inf, scaled)

    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < state.top_p[:, None]
    masked = jnp.where(keep, masked, -jnp.inf)

    kd = sampling._row_keys(state, positions, key)
    noise = jax.vmap(
        lambda kdata: jax.random.gumbel(
            jax.random.wrap_key_data(kdata), (n,)
        )
    )(kd)
    choice = jnp.argmax(masked + noise, axis=-1)
    choice = jnp.where(state.temperature > 0, choice, 0)
    tokens = jnp.take_along_axis(
        top_idx, choice[:, None], axis=1
    )[:, 0].astype(jnp.int32)

    lse = jax.nn.logsumexp(logits, axis=-1)
    token_logprob = (
        jnp.take_along_axis(top_logits, choice[:, None], axis=1)[:, 0] - lse
    )
    m = min(TOPLP, n)
    return tokens, token_logprob, top_idx[:, :m], top_logits[:, :m] - lse[:, None]


OURS = jax.jit(sample)
THEIRS = jax.jit(_sample_over_the_whole_row)


def _promoted(x: np.ndarray) -> np.ndarray:
    """A column a row: not the largest of the chunk whose maximum is the
    row's smallest, so without a bias no selection comes near it."""
    B, V = x.shape
    C = V // LANES
    chunks = x[:, :C * LANES].reshape(B, C, LANES)
    chunk = chunks.max(axis=2).argmin(axis=1)
    lane = np.take_along_axis(
        chunks, chunk[:, None, None], axis=1
    )[:, 0].argmin(axis=1)
    return (chunk * LANES + lane).astype(np.int32)


@pytest.mark.parametrize(
    "rows", ["greedy", "seeded_with_the_benchmark_s_bias", "a_promoted_token"]
)
@pytest.mark.parametrize("shape", WIDE, ids=lambda s: f"{s[0]}x{s[1]}")
def test_sample_is_what_it_was_over_the_whole_row(shape, rows):
    B, V = shape
    x = _logits("bf16", B, V)
    state = SamplingState.create(B)
    if rows == "seeded_with_the_benchmark_s_bias":
        # temperature 1, a seed a row, every second row top_k / top_p
        state = dataclasses.replace(
            state,
            temperature=jnp.ones((B,), jnp.float32),
            top_k=jnp.asarray(np.arange(B) % 2 * 40, jnp.int32),
            top_p=jnp.asarray(1.0 - np.arange(B) % 2 * 0.1, jnp.float32),
            seed=jnp.arange(B, dtype=jnp.uint32) + 7,
            seeded=jnp.ones((B,), jnp.bool_),
            bias_ids=jnp.tile(BENCH_BIAS_IDS, (B, 1)),
            bias_vals=jnp.full((B, MAX_BIAS), 100.0, jnp.float32),
        )
    elif rows == "a_promoted_token":
        # even rows greedy, odd rows at temperature 0.7 from the step key
        bias_ids = np.full((B, MAX_BIAS), -1, np.int32)
        bias_ids[:, 5] = _promoted(x)
        bias_vals = np.zeros((B, MAX_BIAS), np.float32)
        bias_vals[:, 5] = 60.0
        state = dataclasses.replace(
            state,
            temperature=jnp.asarray(np.arange(B) % 2 * 0.7, jnp.float32),
            bias_ids=jnp.asarray(bias_ids),
            bias_vals=jnp.asarray(bias_vals),
        )
    call = (x, state, jax.random.key(36), jnp.arange(B, dtype=jnp.int32) + 11)
    got = [np.asarray(a) for a in OURS(*call)]
    want = [np.asarray(a) for a in THEIRS(*call)]
    for g, w in zip(got, want):      # tokens, log-prob, top ids, top log-probs
        np.testing.assert_array_equal(g, w)
    tokens, _, top_ids, _ = got
    if rows == "seeded_with_the_benchmark_s_bias":
        assert np.isin(tokens, BENCH_BIAS_IDS).all()
    elif rows == "a_promoted_token":
        np.testing.assert_array_equal(top_ids[:, 0], _promoted(x))
        np.testing.assert_array_equal(tokens[::2], _promoted(x)[::2])


def test_the_runner_says_which_form_it_was_built_with(caplog):
    """The choice is static, so the engine says it once: the runner's
    start-up line and ``sample_candidates`` in ``health()``."""
    from gpustack_tpu.engine.engine import LLMEngine
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.config import get_config

    cfg = get_config("tiny")
    with caplog.at_level(logging.INFO, logger="gpustack_tpu.engine.runner"):
        engine = LLMEngine(
            cfg, init_params(cfg, jax.random.key(0)),
            max_slots=2, max_seq_len=64,
        )
    want = f"whole row of {cfg.vocab_size}"
    assert engine.health()["sample_candidates"] == want
    assert f"sampling candidates: {want}" in caplog.text
