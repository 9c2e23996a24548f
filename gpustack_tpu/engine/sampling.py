"""Vectorized token samplers.

All sampling state is per-slot arrays of shape ``[B]`` so one jitted
``sample`` call serves a heterogeneous continuous batch (each request may
carry its own temperature/top-k/top-p/seed, as OpenAI API params allow)
without re-specialization — static shapes, no host branching.

Besides the sampled token, :func:`sample` returns the sampled token's
logprob and the top-``TOPLP`` (id, logprob) candidates — the data the
OpenAI ``logprobs``/``top_logprobs`` response fields need (reference
proxies vLLM's logprobs surface, gpustack/routes/openai.py). They come
almost free: the sampler already ranks the top-``CAND`` logits, so the
only extra work is one logsumexp for normalization.

Nothing here sorts a whole vocabulary. A ``jax.lax.top_k`` over the row
did: XLA writes it as a sort of the row and a slice, and makes a ``TopK``
call of the pair only where nothing else reads the sort, which the
top-``TOPLP`` sliced from the top-``CAND`` below prevents (on the v5e
3.0-5.9 ms a decode step at 151,936 columns and 1.6 ms a first token:
PERF.md section 6, PR 36). So :func:`top_candidates` first narrows a wide
row to the ``CAND`` lane-wide chunks that can hold its ``CAND`` largest
logits and ranks only those — exactly, ties included.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplingState:
    """Per-slot sampling parameters, shape ``[B]`` each.

    ``temperature == 0`` selects greedy decoding for that slot.
    ``top_k == 0`` / ``top_p == 1`` disable the respective filters.
    ``seeded`` rows draw noise from ``fold_in(seed, position)`` instead of
    the engine's step key, so a request that sets OpenAI's ``seed`` param
    replays identically (given the same context) — the engine-global key
    never enters a seeded row's path.
    """

    temperature: jax.Array  # f32 [B]
    top_k: jax.Array        # i32 [B]
    top_p: jax.Array        # f32 [B]
    seed: jax.Array         # u32 [B]
    seeded: jax.Array       # bool [B]
    bias_ids: jax.Array     # i32 [B, MAX_BIAS] (-1 = unused slot)
    bias_vals: jax.Array    # f32 [B, MAX_BIAS]

    @staticmethod
    def create(batch: int) -> "SamplingState":
        return SamplingState(
            temperature=jnp.zeros((batch,), jnp.float32),
            top_k=jnp.zeros((batch,), jnp.int32),
            top_p=jnp.ones((batch,), jnp.float32),
            seed=jnp.zeros((batch,), jnp.uint32),
            seeded=jnp.zeros((batch,), jnp.bool_),
            bias_ids=jnp.full((batch, MAX_BIAS), -1, jnp.int32),
            bias_vals=jnp.zeros((batch, MAX_BIAS), jnp.float32),
        )

    def set_slot(
        self, slot, temperature, top_k, top_p, seed=0, seeded=False,
        bias_ids=None, bias_vals=None,
    ) -> "SamplingState":
        if bias_ids is None:
            bias_ids = jnp.full((MAX_BIAS,), -1, jnp.int32)
        if bias_vals is None:
            bias_vals = jnp.zeros((MAX_BIAS,), jnp.float32)
        return SamplingState(
            temperature=self.temperature.at[slot].set(temperature),
            top_k=self.top_k.at[slot].set(top_k),
            top_p=self.top_p.at[slot].set(top_p),
            seed=self.seed.at[slot].set(seed),
            seeded=self.seeded.at[slot].set(seeded),
            bias_ids=self.bias_ids.at[slot].set(bias_ids),
            bias_vals=self.bias_vals.at[slot].set(bias_vals),
        )


# Sampling never looks past the top CAND candidates: the probability mass
# beyond the top-64 logits is negligible, and ranking more of the row
# costs a sort of it. Exact for greedy and for top_k <= CAND; pure
# temperature sampling is truncated to the top-64 tail (the standard
# serving-engine tradeoff). How the CAND are found: top_candidates.
CAND = 64
# Top-logprob candidates returned per step (OpenAI caps top_logprobs at 20).
TOPLP = 20
# logit_bias entries per request. Applied to the FULL logits before the
# top-k rank (exact semantics — a +bias can promote a token from outside
# the candidate window, a -100 ban always lands).
MAX_BIAS = 64


# One chunk of a row in top_candidates: the TPU's lane width, so a row of
# chunks is the row's own tiling and a chunk's maximum is one lane reduce.
LANES = 128
# Rows of fewer chunks than this are ranked whole: the selection ranks
# CAND * LANES = 8,192 columns whatever the row's width, so it cannot pay
# at CAND chunks and does from about one and a half times that. ``sample``
# on the v5e, whole row against chunks, microseconds (hack/sample_bench.py;
# PERF.md section 6, PR 36): at 16 slots 64 chunks 88 / 104, 96 chunks
# 145 / 106, 128 chunks 178 / 107, 160 chunks (A.X-K1's slice of 20,480
# columns) 272 / 108; at 32 slots 1,187 chunks (Qwen3's 151,936) 6,200 /
# 444. Twice CAND, so that the tests' vocabularies of a few thousand
# columns compile what they always did.
CHUNKED_MIN_CHUNKS = 2 * CAND


def candidate_chunks(vocab: int, n: int = CAND) -> int:
    """How many chunks :func:`top_candidates` cuts a row of ``vocab``
    columns into for its top ``n``; 0 where it ranks the row whole. A
    static choice from the shape alone."""
    chunks = -(-vocab // LANES)
    return chunks if chunks >= max(CHUNKED_MIN_CHUNKS, n) else 0


def candidates_form(vocab: int) -> str:
    """Which form of :func:`top_candidates` every ``sample`` over a
    vocabulary of ``vocab`` columns is built with, in words: the
    engine's start-up log and ``sample_candidates`` in ``/healthz``."""
    chunks = candidate_chunks(vocab)
    if chunks:
        return f"chunked: {CAND} of {chunks} chunks of {LANES}"
    return f"whole row of {vocab}"


def top_candidates(logits: jax.Array, n: int):
    """``jax.lax.top_k(logits, n)`` — same values, same ids, same order
    — without a sort of the row's whole width.

    A wide row is viewed as chunks of ``LANES`` columns. The ``n`` chunks
    with the largest maxima are gathered in the vocabulary's own order
    and only those ``n * LANES`` columns are ranked. Exact: with ``t`` the
    ``n``-th largest chunk maximum, the chosen chunks hold at least ``n``
    values >= ``t`` and every value of a chunk left out is <= its
    maximum <= ``t``, so the ``n`` largest candidates are the row's.
    Ties: both ``top_k`` calls put the lower index first among equals and
    the candidates keep the vocabulary's order, so a chunk left out only
    ever ties with chosen chunks of lower index, each of which holds an
    equal value of lower index: the ids are ``lax.top_k``'s as well.
    """
    B, V = logits.shape
    C = candidate_chunks(V, n)
    if not C:
        return jax.lax.top_k(logits, n)
    with jax.named_scope("sample_candidates"):
        if C * LANES != V:   # padding ranks after every real column
            logits = jnp.pad(
                logits, ((0, 0), (0, C * LANES - V)),
                constant_values=-jnp.inf,
            )
        chunks = logits.reshape(B, C, LANES)
        _, chunk_ids = jax.lax.top_k(jnp.max(chunks, axis=-1), n)
        chunk_ids = jnp.sort(chunk_ids, axis=-1)
        cands = jnp.take_along_axis(chunks, chunk_ids[:, :, None], axis=1)
        vals, local = jax.lax.top_k(cands.reshape(B, n * LANES), n)
        ids = (
            jnp.take_along_axis(chunk_ids, local // LANES, axis=1) * LANES
            + local % LANES
        )
        return vals, ids


def _row_keys(state: SamplingState, positions: jax.Array, key: jax.Array):
    """Per-row PRNG keys: seeded rows derive from (seed, position) only —
    deterministic replay; unseeded rows derive from the step key + row
    index so concurrent identical prompts (OpenAI ``n>1``) diverge."""
    B = positions.shape[0]
    root = jax.random.key(0)

    def seeded_key(seed, pos):
        return jax.random.key_data(
            jax.random.fold_in(jax.random.fold_in(root, seed), pos)
        )

    def step_key(row):
        return jax.random.key_data(jax.random.fold_in(key, row))

    seeded_kd = jax.vmap(seeded_key)(state.seed, positions)
    step_kd = jax.vmap(step_key)(jnp.arange(B, dtype=jnp.uint32))
    kd = jnp.where(state.seeded[:, None], seeded_kd, step_kd)
    return kd


def sample(
    logits: jax.Array,       # [B, V] f32
    state: SamplingState,
    key: jax.Array,
    positions: jax.Array | None = None,  # i32 [B]; required for seeded rows
    confidence: bool = False,
):
    """Sample one token per row honoring per-row temperature/top-k/top-p
    and per-row seeds.

    Returns ``(tokens i32[B], token_logprob f32[B], top_ids i32[B, TOPLP],
    top_logprobs f32[B, TOPLP])``, and with ``confidence`` (static) a
    fifth: the probability of the row's token under the distribution it
    was drawn from (``f32[B]``: the candidates after temperature, top-k
    and top-p; a greedy row's under temperature 1), what a diffusion
    pass decides by (:func:`decide`).
    """
    B, V = logits.shape
    # logit_bias before ranking: scatter-add the sparse per-row biases
    # (unused slots carry id -1 / value 0 → clipped no-op add at col 0)
    valid = state.bias_ids >= 0
    bias_cols = jnp.clip(state.bias_ids, 0, V - 1)
    bias_vals = jnp.where(valid, state.bias_vals, 0.0)
    logits = logits.at[
        jnp.arange(B)[:, None], bias_cols
    ].add(bias_vals)
    n = min(CAND, V)
    top_logits, top_idx = top_candidates(logits, n)   # [B, n] descending

    temp = jnp.maximum(state.temperature, 1e-6)[:, None]
    scaled = top_logits / temp

    # top-k: mask candidates at rank >= k.
    k = jnp.where(state.top_k > 0, jnp.minimum(state.top_k, n), n)
    rank = jnp.broadcast_to(jnp.arange(n)[None, :], (B, n))
    masked = jnp.where(rank >= k[:, None], -jnp.inf, scaled)

    # top-p over the (already sorted) candidates: keep the smallest prefix
    # reaching p (the first candidate always survives).
    probs = jax.nn.softmax(scaled, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < state.top_p[:, None]
    masked = jnp.where(keep, masked, -jnp.inf)

    if positions is None:
        positions = jnp.zeros((B,), jnp.int32)
    kd = _row_keys(state, positions, key)
    noise = jax.vmap(
        lambda kdata: jax.random.gumbel(
            jax.random.wrap_key_data(kdata), (n,)
        )
    )(kd)
    # categorical(key, logits) == argmax(logits + gumbel(key)); the
    # per-row formulation lets seeded rows keep private noise streams.
    choice = jnp.argmax(masked + noise, axis=-1)        # [B] in [0, n)
    choice = jnp.where(state.temperature > 0, choice, 0)
    tokens = jnp.take_along_axis(
        top_idx, choice[:, None], axis=1
    )[:, 0].astype(jnp.int32)

    # Exact logprobs: top-n logits are the true top-n of the full vocab,
    # so normalizing them against the full logsumexp gives exact values.
    lse = jax.nn.logsumexp(logits, axis=-1)             # [B]
    token_logprob = (
        jnp.take_along_axis(top_logits, choice[:, None], axis=1)[:, 0] - lse
    )
    m = min(TOPLP, n)
    top_ids = top_idx[:, :m]
    top_logprobs = top_logits[:, :m] - lse[:, None]
    if not confidence:
        return tokens, token_logprob, top_ids, top_logprobs
    # (a greedy row's ``masked`` keeps its first candidate alone: the
    # near-zero temperature puts all of top-p's mass on it)
    drawn_from = jnp.where(
        state.temperature[:, None] > 0, masked, top_logits
    )
    conf = jnp.take_along_axis(
        jax.nn.softmax(drawn_from, axis=-1), choice[:, None], axis=1
    )[:, 0]
    return tokens, token_logprob, top_ids, top_logprobs, conf


def sample_block(
    logits: jax.Array,       # [B, L, V] f32: a diffusion block's rows
    state: SamplingState,    # [B]: a slot's parameters serve its L rows
    key: jax.Array,
    positions: jax.Array,    # i32 [B, L]: the rows' absolute positions
    mask_id: int,
):
    """:func:`sample` for every row of every slot's block: ``(candidates
    i32[B, L], logprob f32[B, L], top_ids i32[B, L, TOPLP], top_logprobs
    f32[B, L, TOPLP], confidence f32[B, L])``. The logits at position
    ``i`` are for the token at ``i``. A seeded slot's draw is a function
    of its seed and the row's absolute position, so an answer depends on
    no neighbour and a position undecided in one pass draws the same
    noise over the next pass's logits. The mask token is never a
    candidate, whatever a ``logit_bias`` says of it."""
    B, L, V = logits.shape
    rows = jax.tree.map(lambda a: jnp.repeat(a, L, axis=0), state)
    flat = logits.reshape(B * L, V).at[:, mask_id].set(-jnp.inf)
    outs = sample(flat, rows, key, positions.reshape(-1), confidence=True)
    return tuple(o.reshape(B, L, *o.shape[1:]) for o in outs)


def pass_quota(pass_index: jax.Array, block: int, steps: int) -> jax.Array:
    """How many positions the ``pass_index``-th denoise pass of a block
    decides at least (the family's ``get_num_transfer_tokens``): ``block
    // steps``, the remainder spread over the first passes."""
    return block // steps + (pass_index < block % steps).astype(jnp.int32)


def decide(
    rule: str,               # one of models.config.DIFFUSION_RULES (static)
    undecided: jax.Array,    # bool [B, L]
    conf: jax.Array,         # f32 [B, L]: sample_block's confidence
    quota: jax.Array,        # i32 [B]: pass_quota
    threshold: float = 0.0,
) -> jax.Array:
    """Which of a block's undecided positions a denoise pass decides
    (bool ``[B, L]``, never a decided one), by the published rules:
    ``sequential`` the first ``quota`` undecided ones;
    ``low_confidence_static`` the ``quota`` of largest confidence (the
    lower index first among equals); ``low_confidence_dynamic`` every one
    whose confidence is over ``threshold`` where those are at least
    ``quota``, else as ``low_confidence_static``. A pass over a block
    with fewer undecided positions than ``quota`` decides them all."""
    quota = quota[:, None]
    if rule == "sequential":
        return undecided & (jnp.cumsum(undecided, axis=1) <= quota)
    c = jnp.where(undecided, conf, -jnp.inf)
    at = jnp.arange(c.shape[1])
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (at[None, :] < at[:, None])
    )                                       # [B, i, j]: j ranks before i
    largest = undecided & (jnp.sum(ahead, axis=2) < quota)
    if rule == "low_confidence_static":
        return largest
    if rule != "low_confidence_dynamic":
        raise ValueError(f"remasking_strategy {rule!r}")
    high = undecided & (conf > threshold)
    enough = jnp.sum(high, axis=1, keepdims=True) >= quota
    return jnp.where(enough, high, largest)
