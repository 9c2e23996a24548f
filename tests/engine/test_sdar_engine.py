"""A model generated by diffusion over blocks through the engine
(SDAR-MoE at a small size, seeded random weights): a step is a pass over
each slot's block of four rows and yields 0 to 4 tokens a slot. The
answers are those of the family's published order of work (prefill of
the whole blocks, denoise passes, one commit pass a block) computed with
the float32 reference's full forward, under each of the three rules;
every token is a chunk of its own; a stream ends at exactly
``max_tokens`` or at the first EOS in order; and what is not served is
refused at engine start by name."""

import asyncio
import dataclasses
import math
import queue

import jax
import numpy as np
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.engine.sampling import CAND
from gpustack_tpu.testing import reference_sdar as ref
from gpustack_tpu.testing.sdar_small import HF, MASK_ID, small

L = 4
ALPHABET = {i + 1: 100.0 for i in range(65, 65 + 26)}   # 'A'..'Z'


def prompt(n, start=5):
    return [(start + 7 * i) % 90 + 33 for i in range(n)]


@pytest.fixture(scope="module")
def model():
    return small()


def engine_of(model, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(cfg, params, **kw)


def served(eng, requests, timeout=240):
    eng.start()
    try:
        reqs = [eng.submit(r) for r in requests]
        for r in reqs:
            assert r.done.wait(timeout), r.request_id
    finally:
        eng.stop()
    return reqs


def published(params, ids, max_tokens, rule, steps=4, threshold=0.9,
              bias=None):
    """The family's ``block_diffusion_generate``, greedy, on the
    reference's full forward: the tokens in the order of their
    positions, as many as ``max_tokens``, and how many passes it took."""
    whole = len(ids) // L * L
    seq, block = list(ids[:whole]), list(ids[whole:])
    decided = [True] * len(block) + [False] * (L - len(block))
    block += [MASK_ID] * (L - len(block))
    out, passes = [], 0
    quota = [L // steps + (i < L % steps) for i in range(steps)]
    while len(out) < max_tokens:
        for step in range(steps + 1):
            passes += 1
            if all(decided):      # the commit pass
                out += block[len(out) + len(ids) - len(seq):]
                seq += block
                block, decided = [MASK_ID] * L, [False] * L
                break
            feed = ref.pass_ids(seq, block, decided, MASK_ID, len(ids))
            logits, _ = ref.forward(params, HF, feed + [0] * (64 - len(feed)), L)
            rows = np.array(logits[len(seq): len(seq) + L], np.float64)
            rows[:, MASK_ID] = -np.inf
            for t, b in (bias or {}).items():
                rows[:, t] += b
            x0 = rows.argmax(axis=1)
            # the confidence: the candidate's share of the sampler's
            # CAND candidates, at temperature 1 for a greedy row
            top = np.sort(rows, axis=1)[:, -CAND:]
            conf = np.exp(rows.max(axis=1) - top.max(axis=1)) / np.exp(
                top - top.max(axis=1, keepdims=True)
            ).sum(axis=1)
            open_ = [i for i in range(L) if not decided[i]]
            n = quota[min(step, steps - 1)]
            by_conf = sorted(open_, key=lambda i: (-conf[i], i))[:n]
            if rule == "sequential":
                take = open_[:n]
            elif rule == "low_confidence_static":
                take = by_conf
            else:
                high = [i for i in open_ if conf[i] > threshold]
                take = high if len(high) >= n else by_conf
            for i in take:
                block[i], decided[i] = int(x0[i]), True
    return out[:max_tokens], passes


@pytest.mark.parametrize("rule,threshold", [
    ("sequential", 0.9), ("low_confidence_static", 0.9),
    ("low_confidence_dynamic", 0.9), ("low_confidence_dynamic", 0.02),
])
def test_the_engine_serves_the_published_order_of_work(rule, threshold):
    """Four greedy requests over three slots (one slot changes hands),
    prompts that end 0, 1, 2 and 3 into a block: every answer is the
    published generation's on the reference's forward, under the rule
    the configuration names; the flight records count passes, blocks and
    decided positions."""
    model = small(remasking_strategy=rule, confidence_threshold=threshold)
    cfg, params = model
    prompts = [prompt(8), prompt(13, 9), prompt(6, 2), prompt(11, 4)]
    lengths = [9, 6, 11, 5]
    eng = engine_of(model)
    reqs = served(eng, [
        GenRequest(prompt_ids=p, max_tokens=n, temperature=0.0,
                   logit_bias=ALPHABET)
        for p, n in zip(prompts, lengths)
    ])
    for r, p, n in zip(reqs, prompts, lengths):
        want, _ = published(params, p, n, rule, threshold=threshold,
                            bias=ALPHABET)
        assert r.output_ids == want, (rule, p)
        assert r.finish_reason == "length"
    h = eng.health()["diffusion"]
    assert (h["block"], h["steps"], h["rule"]) == (L, 4, rule)
    records = [e for e in eng.flight.snapshot() if "passes_denoise" in e]
    assert {e["mode"] for e in records} <= {"denoise", "prefill"}
    assert any(e["mode"] == "denoise" for e in records)
    for key in ("passes_denoise", "passes_commit", "tokens_decided"):
        assert h[key] == sum(e[key] for e in records) > 0
    assert h["blocks_done"] == h["passes_commit"]
    # every position decided was decided once; what was handed on is at
    # most that (the rest of a last block is dropped)
    assert sum(lengths) <= h["tokens_decided"]
    if rule == "sequential":
        # a pass decides one position: 4 + 1 passes a whole block
        assert h["tokens_decided"] == h["passes_denoise"]
    text = "\n".join(eng.flight.metrics_lines())
    assert (
        'gpustack_engine_diffusion_passes_total{kind="commit"} '
        f"{h['passes_commit']}"
    ) in text
    assert (
        f"gpustack_engine_diffusion_tokens_decided_total {h['tokens_decided']}"
    ) in text
    assert f"gpustack_engine_diffusion_blocks_total {h['blocks_done']}" in text


def test_the_dynamic_rule_decides_several_positions_a_pass():
    """Under a threshold every confidence passes, a denoise pass decides
    the whole block: two passes a block, and the answer the published
    one."""
    model = small(
        remasking_strategy="low_confidence_dynamic", confidence_threshold=0.0
    )
    _, params = model
    eng = engine_of(model)
    (r,) = served(eng, [GenRequest(
        prompt_ids=prompt(8), max_tokens=8, temperature=0.0,
        logit_bias=ALPHABET,
    )])
    want, passes = published(
        params, prompt(8), 8, "low_confidence_dynamic", threshold=0.0,
        bias=ALPHABET,
    )
    assert r.output_ids == want and passes == 4
    h = eng.health()["diffusion"]
    assert h["tokens_decided"] == 8 and h["passes_denoise"] == 2


@pytest.mark.parametrize("max_tokens", [1, 2, 3, 5, 6, 7])
@pytest.mark.parametrize("tail", [0, 3])
def test_a_chunk_a_token_and_exactly_max_tokens(model, max_tokens, tail):
    """A length that ends inside a block: the stream has a content chunk
    for every token, in order, and the request ends at ``max_tokens``
    whatever the rest of the block holds."""
    stream = queue.Queue()
    eng = engine_of(model)
    (r,) = served(eng, [GenRequest(
        prompt_ids=prompt(8 + tail), max_tokens=max_tokens, temperature=0.0,
        logit_bias=ALPHABET, stream=stream,
    )])
    pieces = []
    while (item := stream.get(timeout=5)) is not None:
        pieces.append(item)
    assert len(r.output_ids) == max_tokens and r.finish_reason == "length"
    assert [tok for tok, _ in pieces] == r.output_ids
    assert "".join(p for _, p in pieces) == r.output_text
    assert all(len(p) == 1 for _, p in pieces)


def test_eos_inside_a_block_ends_the_stream_there(model):
    """The first EOS in order ends the answer, though the block's later
    positions were decided by the same passes."""
    eng = engine_of(model)
    (plain,) = served(eng, [GenRequest(
        prompt_ids=prompt(9), max_tokens=10, temperature=0.0,
        logit_bias=ALPHABET,
    )])
    # the token at the sixth place: second of the second block generated
    stop = plain.output_ids[5]
    cut = plain.output_ids.index(stop)
    (r,) = served(engine_of(model), [GenRequest(
        prompt_ids=prompt(9), max_tokens=10, temperature=0.0,
        logit_bias=ALPHABET, stop_ids=(stop,),
    )])
    assert r.output_ids == plain.output_ids[:cut]
    assert r.finish_reason == "stop"


def test_a_seeded_request_twice_alike_with_finite_logprobs(model):
    def one():
        return GenRequest(
            prompt_ids=prompt(10), max_tokens=7, temperature=1.0, seed=1234,
            logit_bias=ALPHABET, logprobs=True, top_logprobs=5,
        )

    a, b = served(engine_of(model), [one(), one()])
    assert a.output_ids == b.output_ids and len(a.output_ids) == 7
    assert a.output_logprobs == b.output_logprobs
    assert len(a.output_logprobs) == len(a.output_top_logprobs) == 7
    assert all(math.isfinite(x) and x <= 1e-3 for x in a.output_logprobs)
    (other,) = served(engine_of(model), [dataclasses.replace(one(), seed=99)])
    assert other.output_ids != a.output_ids


def test_a_token_s_logprobs_are_those_of_the_pass_that_decided_it(model):
    """Greedy: a token is the first of its pass's top candidates, and
    its log-probability the reference's at the pass that decided it."""
    cfg, params = model
    ids = prompt(10)
    (r,) = served(engine_of(model), [GenRequest(
        prompt_ids=ids, max_tokens=3, temperature=0.0, logprobs=True,
        top_logprobs=5,
    )])
    seq, block, decided = ids[:8], ids[8:] + [MASK_ID] * 2, [1, 1, 0, 0]
    for i, (tok, lp, top) in enumerate(zip(
        r.output_ids[:2], r.output_logprobs, r.output_top_logprobs
    )):
        feed = ref.pass_ids(seq, block, decided, MASK_ID, len(ids))
        logits, _ = ref.forward(params, HF, feed + [0] * (64 - len(feed)), L)
        row = np.array(logits[10 + i], np.float64)
        row[MASK_ID] = -np.inf
        want = row - (np.log(np.exp(row - row.max()).sum()) + row.max())
        assert tok == int(row.argmax()) == top[0][0]
        assert abs(lp - want[tok]) < 1e-4
        block[2 + i], decided[2 + i] = tok, 1


def test_greedy_answers_are_bit_identical_at_depth_0_and_2(model):
    def ask():
        return [
            GenRequest(prompt_ids=prompt(n, s), max_tokens=m, temperature=0.0,
                       logit_bias=ALPHABET, logprobs=True)
            for n, s, m in ((9, 1, 10), (14, 3, 7), (4, 8, 9), (7, 2, 6))
        ]

    serial = served(engine_of(model, pipeline_depth=0), ask())
    ahead = served(engine_of(model, pipeline_depth=2), ask())
    for a, b in zip(serial, ahead):
        assert a.output_ids == b.output_ids
        assert a.output_logprobs == b.output_logprobs
        assert a.output_text == b.output_text


def test_an_answer_does_not_depend_on_its_neighbours(model):
    """The same seeded request alone and among three others, in another
    slot: the same tokens (a draw is a function of the request's seed and
    the absolute position)."""
    def mine():
        return GenRequest(
            prompt_ids=prompt(11), max_tokens=9, temperature=0.8, seed=7,
            top_k=8, logit_bias=ALPHABET,
        )

    (alone,) = served(engine_of(model), [mine()])
    among = served(engine_of(model), [
        GenRequest(prompt_ids=prompt(6, 3), max_tokens=12, temperature=1.0,
                   logit_bias=ALPHABET),
        GenRequest(prompt_ids=prompt(9, 5), max_tokens=3, temperature=0.0),
        mine(),
    ])
    assert among[2].output_ids == alone.output_ids


def test_a_slot_re_tenanted_mid_block_starts_clean(model):
    """One slot: the first request ends two positions into a block; the
    next takes the slot, its own block and the same answer it gets
    alone."""
    first = GenRequest(prompt_ids=prompt(8), max_tokens=6, temperature=0.0,
                       logit_bias=ALPHABET)
    second = GenRequest(prompt_ids=prompt(13, 4), max_tokens=7,
                        temperature=0.0, logit_bias=ALPHABET)
    eng = engine_of(model, max_slots=1)
    a, b = served(eng, [first, second])
    (alone,) = served(engine_of(model, max_slots=1), [GenRequest(
        prompt_ids=prompt(13, 4), max_tokens=7, temperature=0.0,
        logit_bias=ALPHABET,
    )])
    assert len(a.output_ids) == 6 and b.output_ids == alone.output_ids
    assert eng.health()["pipeline_rollback_tokens"] >= 0


def test_the_capacity_edge(model):
    """``positions + L <= max_seq_len``: a request runs to the last block
    that fits and ends with ``length``; a prompt whose first token's
    block does not fit is refused."""
    eng = engine_of(model, max_seq_len=32)
    (r,) = served(eng, [GenRequest(
        prompt_ids=prompt(22), max_tokens=50, temperature=0.0,
        logit_bias=ALPHABET,
    )])
    assert len(r.output_ids) == 10 and r.finish_reason == "length"
    _, params = model
    want, _ = published(params, prompt(22), 10, "sequential", bias=ALPHABET)
    assert r.output_ids == want
    eng = engine_of(model, max_seq_len=30)     # the last block: 24..27
    with pytest.raises(ValueError, match="block of 4"):
        eng.submit(GenRequest(prompt_ids=prompt(28), max_tokens=1))
    (r,) = served(eng, [GenRequest(
        prompt_ids=prompt(25), max_tokens=9, temperature=0.0,
    )])
    assert len(r.output_ids) == 3 and r.finish_reason == "length"


def test_completions_with_logprobs_5(model):
    from aiohttp.test_utils import TestClient, TestServer

    from gpustack_tpu.engine.api_server import OpenAIServer

    eng = engine_of(model)
    eng.start()
    server = OpenAIServer(eng, model_name="tiny-sdar")
    bias = {str(t): b for t, b in ALPHABET.items()}

    async def run():
        client = TestClient(TestServer(server.app))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "prompt": "block by block", "max_tokens": 8,
                "temperature": 0, "logprobs": 5, "logit_bias": bias,
            })
            assert r.status == 200, await r.text()
            first = await r.json()
            r = await client.get("/healthz")
            health = await r.json()
            r = await client.get("/metrics")
            return first, health, await r.text()
        finally:
            await client.close()

    try:
        data, health, metrics = asyncio.run(run())
    finally:
        eng.stop()
    choice = data["choices"][0]
    assert len(choice["text"]) == 8
    assert data["usage"]["completion_tokens"] == 8
    tops = choice["logprobs"]["top_logprobs"]
    assert len(tops) == 8 and all(len(t) == 5 for t in tops)
    assert all(
        math.isfinite(x) and x <= 1e-3 for x in choice["logprobs"]["token_logprobs"]
    )
    assert health["diffusion"]["rule"] == "sequential"
    assert health["diffusion"]["tokens_decided"] >= 8
    assert "gpustack_engine_diffusion_passes_total" in metrics


@pytest.mark.parametrize("kw,names", [
    ({"speculative": "ngram"}, "speculative='ngram'"),
    ({"host_kv_cache_mb": 8}, "host_kv_cache_mb"),
    ({"host_kv_cache_mb": 0, "kv_spill_mb": 8}, "kv_spill_mb"),
    ({"kv_role": "prefill"}, "kv_role='prefill'"),
    ({"prefill_chunk": 32}, "prefill_chunk"),
])
def test_what_is_not_served_is_refused_at_engine_start_by_name(
    model, kw, names
):
    with pytest.raises(ValueError) as e:
        engine_of(model, **kw)
    assert names in str(e.value)
    assert "generated by diffusion over blocks of 4" in str(e.value)
    assert "tiny-sdar" in str(e.value)


def test_a_mesh_of_several_devices_is_refused_by_name(model):
    from gpustack_tpu.parallel.mesh import MeshPlan

    with pytest.raises(ValueError) as e:
        engine_of(model, plan=MeshPlan(tp=2))
    assert "served on one device" in str(e.value)
    assert "diffusion over blocks" in str(e.value)


def test_an_image_input_is_refused(model):
    eng = engine_of(model)
    with pytest.raises(ValueError, match="image inputs"):
        eng.submit(GenRequest(
            prompt_ids=prompt(8), max_tokens=2,
            embeds_override=(np.zeros((8, 128)), np.zeros((8,), bool)),
        ))
