"""Engine-level tests: continuous batching, streaming, quantization.

Hermetic (tiny model, byte tokenizer, CPU) — the reference's doctrine of
fixture-driven tests with no real accelerators (SURVEY.md §4).
"""

import queue

import jax
import numpy as np
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.engine.sampling import SamplingState, sample
from gpustack_tpu.models import forward, init_params
from gpustack_tpu.models.config import get_config
from gpustack_tpu.models.quant import dequantize, quantize_params
import jax.numpy as jnp
from gpustack_tpu.testing.oracle import greedy_reference as _greedy_reference


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = LLMEngine(cfg, params, max_slots=4, max_seq_len=64)
    eng.start()
    yield eng
    eng.stop()


def test_engine_greedy_matches_oracle(engine):
    prompt = [5, 17, 42, 99, 7]
    req = engine.generate(
        GenRequest(prompt_ids=prompt, max_tokens=8, temperature=0.0),
        timeout=120,
    )
    oracle = _greedy_reference(engine.cfg, engine.runner.params, prompt, 8)
    # Stop tokens would truncate; compare up to the engine's output length.
    assert len(req.output_ids) >= 1
    assert req.output_ids == oracle[: len(req.output_ids)]
    assert req.finish_reason in ("stop", "length")


def test_engine_concurrent_requests_isolated(engine):
    """More requests than slots; every request completes and matches its own
    single-request output (continuous batching must not cross-pollute)."""
    prompts = [[3, 1, 4], [15, 9, 2, 6], [5, 3], [5, 8, 9, 7, 9], [31, 41], [2, 7]]
    solo = [
        _greedy_reference(engine.cfg, engine.runner.params, p, 5)
        for p in prompts
    ]
    reqs = [
        engine.submit(GenRequest(prompt_ids=p, max_tokens=5, temperature=0.0))
        for p in prompts
    ]
    for r in reqs:
        assert r.done.wait(180), r.request_id
    for r, s in zip(reqs, solo):
        assert r.output_ids == s[: len(r.output_ids)], r.request_id


def test_engine_streaming(engine):
    q = queue.Queue()
    req = engine.generate(
        GenRequest(
            prompt_ids=[72, 102, 109], max_tokens=6, temperature=0.0, stream=q
        ),
        timeout=120,
    )
    pieces = []
    while True:
        item = q.get(timeout=10)
        if item is None:
            break
        pieces.append(item)
    assert pieces, "stream delivered nothing"
    assert "".join(p for _, p in pieces) == engine.tokenizer.decode(
        req.output_ids
    )


def test_engine_stop_ids(engine):
    # Find a token greedy emits later in the sequence (distinct from the
    # earlier ones), then rerun with it as a stop id.
    prompt = [9, 9, 9]
    probe = engine.generate(
        GenRequest(prompt_ids=prompt, max_tokens=6, temperature=0.0),
        timeout=120,
    )
    idx = next(
        (
            i
            for i, t in enumerate(probe.output_ids)
            if i > 0 and t not in probe.output_ids[:i]
        ),
        None,
    )
    if idx is None:
        pytest.skip("tiny model repeated a single token; no distinct stop id")
    stop = probe.output_ids[idx]
    req = engine.generate(
        GenRequest(
            prompt_ids=prompt, max_tokens=10, temperature=0.0,
            stop_ids=(stop,),
        ),
        timeout=120,
    )
    assert req.finish_reason == "stop"
    assert stop not in req.output_ids
    assert req.output_ids == probe.output_ids[:idx]


def test_engine_stop_texts(engine):
    """Text stop sequences truncate output and upgrade finish_reason."""
    prompt = [9, 9, 9]
    probe = engine.generate(
        GenRequest(prompt_ids=prompt, max_tokens=6, temperature=0.0),
        timeout=120,
    )
    full_text = probe.output_text
    if len(full_text) < 2:
        pytest.skip("tiny model produced too little text to split")
    stop = full_text[1:2]
    if stop in full_text[:1]:
        pytest.skip("stop char appears earlier; ambiguous")
    req = engine.generate(
        GenRequest(
            prompt_ids=prompt, max_tokens=10, temperature=0.0,
            stop_texts=(stop,),
        ),
        timeout=120,
    )
    assert req.finish_reason == "stop"
    assert stop not in req.output_text
    assert req.output_text == full_text[:1]


def test_checkpoint_roundtrip_quantized(tmp_path):
    from gpustack_tpu.engine.weights import load_checkpoint, save_checkpoint
    from gpustack_tpu.models.quant import QuantW

    cfg = get_config("tiny")
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(params, path)
    loaded = load_checkpoint(path)
    assert isinstance(loaded["layers"]["wq"], QuantW)
    toks = jnp.asarray([[5, 17, 42]], jnp.int32)
    pos = jnp.arange(3, dtype=jnp.int32)[None, :]
    ref, _ = forward(params, cfg, toks, pos)
    out, _ = forward(loaded, cfg, toks, pos)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_engine_rejects_oversized_prompt(engine):
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.submit(GenRequest(prompt_ids=list(range(64)), max_tokens=1))


def test_engine_health(engine):
    h = engine.health()
    assert h["status"] == "ok" and h["slots_total"] == 4


def test_the_engine_says_how_its_decode_step_attends(caplog):
    """The form is static (the platform, the mesh, the cache's shape),
    so the engine says it once: the runner's start-up line and
    ``decode_attention`` in ``health()``. On the CPU it is the XLA form."""
    import logging

    cfg = get_config("tiny")
    with caplog.at_level(logging.INFO, logger="gpustack_tpu.engine.runner"):
        eng = LLMEngine(
            cfg, init_params(cfg, jax.random.key(0)),
            max_slots=2, max_seq_len=64,
        )
    assert eng.health()["decode_attention"] == "xla"
    assert "decode attention: xla" in caplog.text


def test_sampling_greedy_and_filters():
    logits = jnp.asarray(
        [[1.0, 2.0, 3.0, 0.5], [10.0, 0.0, 0.0, 0.0]], jnp.float32
    )
    st = SamplingState(
        temperature=jnp.asarray([0.0, 1.0], jnp.float32),
        top_k=jnp.asarray([0, 1], jnp.int32),
        top_p=jnp.asarray([1.0, 1.0], jnp.float32),
        seed=jnp.zeros((2,), jnp.uint32),
        seeded=jnp.zeros((2,), jnp.bool_),
        bias_ids=jnp.full((2, 64), -1, jnp.int32),
        bias_vals=jnp.zeros((2, 64), jnp.float32),
    )
    toks, tok_lp, top_ids, top_lps = sample(logits, st, jax.random.key(0))
    assert int(toks[0]) == 2            # greedy row
    assert int(toks[1]) == 0            # top_k=1 forces argmax
    # logprob extras: sampled-token logprob matches its rank entry and
    # candidates are sorted descending
    lp = np.asarray(top_lps)
    assert np.all(np.diff(lp, axis=1) <= 1e-6)
    assert int(top_ids[0, 0]) == 2
    assert abs(float(tok_lp[0]) - float(lp[0, 0])) < 1e-5
    # exact normalization: softmax over the full row sums the top-4 to 1
    assert abs(np.exp(lp[0]).sum() - 1.0) < 1e-4


def test_sampling_top_p_excludes_tail():
    # One dominant token (p≈0.88); top_p=0.5 must always pick it.
    logits = jnp.asarray([[5.0, 3.0, 1.0, 0.0]] * 8, jnp.float32)
    st = SamplingState(
        temperature=jnp.ones((8,), jnp.float32),
        top_k=jnp.zeros((8,), jnp.int32),
        top_p=jnp.full((8,), 0.5, jnp.float32),
        seed=jnp.zeros((8,), jnp.uint32),
        seeded=jnp.zeros((8,), jnp.bool_),
        bias_ids=jnp.full((8, 64), -1, jnp.int32),
        bias_vals=jnp.zeros((8, 64), jnp.float32),
    )
    for seed in range(5):
        toks, *_ = sample(logits, st, jax.random.key(seed))
        assert np.all(np.asarray(toks) == 0)


def test_sampling_seeded_rows_replay():
    logits = jnp.asarray([[2.0, 1.9, 1.8, 1.7]] * 4, jnp.float32)
    st = SamplingState(
        temperature=jnp.ones((4,), jnp.float32),
        top_k=jnp.zeros((4,), jnp.int32),
        top_p=jnp.ones((4,), jnp.float32),
        seed=jnp.asarray([7, 7, 8, 8], jnp.uint32),
        seeded=jnp.ones((4,), jnp.bool_),
        bias_ids=jnp.full((4, 64), -1, jnp.int32),
        bias_vals=jnp.zeros((4, 64), jnp.float32),
    )
    pos = jnp.asarray([3, 3, 3, 9], jnp.int32)
    # seeded rows ignore the step key entirely: different keys, same draw
    a, *_ = sample(logits, st, jax.random.key(0), pos)
    b, *_ = sample(logits, st, jax.random.key(123), pos)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    # same (seed, position) -> same token; row 3 differs in position so
    # it draws from a different stream than row 2
    assert int(a[0]) == int(a[1])


def test_quantized_params_close_and_smaller():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    qparams = quantize_params(params)
    # int8 tensor + per-channel scale reconstructs within quant error
    w = np.asarray(params["layers"]["wq"], np.float32)
    wq = np.asarray(
        dequantize("wq", qparams["layers"]["wq"]), np.float32
    )
    err = np.abs(w - wq).max() / (np.abs(w).max() + 1e-9)
    assert err < 0.01, err
    # quantized forward is close to bf16 forward
    toks = jnp.asarray([[5, 17, 42, 99]], jnp.int32)
    pos = jnp.arange(4, dtype=jnp.int32)[None, :]
    ref, _ = forward(params, cfg, toks, pos)
    out, _ = forward(qparams, cfg, toks, pos)
    # logits drift under int8 but ranking of the top token should hold
    assert int(jnp.argmax(out[0, -1])) == int(jnp.argmax(ref[0, -1]))


def test_init_quantized_params_matches_structure():
    from gpustack_tpu.models.quant import init_quantized_params

    cfg = get_config("tiny-moe")
    ref = quantize_params(init_params(cfg, jax.random.key(0)))
    fast = init_quantized_params(cfg, seed=0)
    ref_shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)), ref)
    fast_shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)), fast)
    assert ref_shapes == fast_shapes
    toks = jnp.asarray([[1, 2, 3]], jnp.int32)
    pos = jnp.arange(3, dtype=jnp.int32)[None, :]
    logits, _ = forward(fast, cfg, toks, pos)
    assert np.isfinite(np.asarray(logits)).all()


def test_quantized_engine_generates():
    cfg = get_config("tiny")
    params = quantize_params(init_params(cfg, jax.random.key(0)))
    eng = LLMEngine(cfg, params, max_slots=2, max_seq_len=64)
    eng.start()
    try:
        req = eng.generate(
            GenRequest(prompt_ids=[1, 2, 3], max_tokens=4, temperature=0.0),
            timeout=120,
        )
        assert len(req.output_ids) >= 1
    finally:
        eng.stop()


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe"])
def test_flight_says_which_expert_dispatch_a_prefill_ran(preset):
    """Every prompt token of a model with experts is counted under the
    dispatch its prefill program was traced with (on the CPU: dense);
    a dense model's records and counters do not know the word."""
    cfg = get_config(preset)
    eng = LLMEngine(
        cfg, init_params(cfg, jax.random.key(0)), max_slots=2,
        max_seq_len=64,
    )
    eng.start()
    try:
        eng.generate(
            GenRequest(prompt_ids=[1, 2, 3, 4, 5], max_tokens=2,
                       temperature=0.0),
            timeout=120,
        )
    finally:
        eng.stop()
    seen = [
        e["moe_dispatch"] for e in eng.flight.snapshot()
        if "moe_dispatch" in e
    ]
    text = "\n".join(eng.flight.metrics_lines())
    if cfg.is_moe:
        assert seen == [{"dense": 5}]
        assert (
            'gpustack_engine_moe_prompt_tokens_total{dispatch="dense"} 5'
            in text
        )
    else:
        assert seen == [] and "moe_prompt_tokens" not in text


def test_a_finished_slot_s_neighbours_decode_as_before():
    """Under the decode kernel (interpret mode here) a slot whose request
    has ended attends nothing from then on (``live`` = ``state.active``):
    the requests beside it go on to the tokens the cacheless oracle
    gives each alone, and the steps' records say what share of the cache
    was live."""
    import dataclasses
    from unittest import mock

    from gpustack_tpu.models import transformer

    cfg = dataclasses.replace(
        get_config("tiny-qwen3"), head_dim=128, dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    chosen = []

    def interpreted(cfg, rows, max_len, platform, mesh):
        chosen.append(rows)
        return "kernel_interpret" if rows == 1 else "xla"

    prompts = [[3, 1, 4, 1, 5], [15, 9, 2, 6], [5, 3, 5, 8, 9, 7]]
    lengths = [12, 2, 9]          # the middle slot's tenant ends first
    with mock.patch.object(transformer, "decode_attention_impl", interpreted):
        eng = LLMEngine(cfg, params, max_slots=3, max_seq_len=64)
        eng.start()
        try:
            reqs = [
                eng.submit(GenRequest(
                    prompt_ids=p, max_tokens=n, temperature=0.0,
                ))
                for p, n in zip(prompts, lengths)
            ]
            for r in reqs:
                assert r.done.wait(180), r.request_id
        finally:
            eng.stop()
    assert 1 in chosen                  # the decode program took the kernel
    for r, p, n in zip(reqs, prompts, lengths):
        assert r.output_ids == _greedy_reference(cfg, params, p, n)
    live = [
        e["kv_live_pct"] for e in eng.flight.snapshot() if "kv_live_pct" in e
    ]
    # three slots of 64: at most (5 + 12) + (6 + 9) + (4 + 2) positions
    assert live and 0 < min(live) and max(live) <= 100 * 38 / 192
    text = "\n".join(eng.flight.metrics_lines())
    assert (
        'gpustack_engine_decode_kv_positions_total{kind="allocated"} '
        f"{192 * len(live)}"
    ) in text


def test_abort_frees_slot_mid_generation(engine):
    """A client-side abort (SSE disconnect) terminates the request at
    the engine's next delivery instead of decoding to max_tokens
    (advisor r4): the slot frees and the stream gets its sentinel."""
    import time as _time

    q = queue.Queue()
    req = engine.submit(GenRequest(
        prompt_ids=[3, 9, 27], max_tokens=40, temperature=0.0,
        stop_ids=(), stream=q,
    ))
    # wait for generation to actually start
    first = q.get(timeout=120)
    assert first is not None
    req.abort()
    assert req.done.wait(60), "aborted request never finished"
    assert req.finish_reason == "abort"
    assert len(req.output_ids) < 40
    # the sentinel still arrives so pumps unblock
    deadline = _time.time() + 30
    saw_sentinel = False
    while _time.time() < deadline:
        item = q.get(timeout=30)
        if item is None:
            saw_sentinel = True
            break
    assert saw_sentinel
    # slot is free again: a fresh request completes
    req2 = engine.generate(
        GenRequest(prompt_ids=[5, 1], max_tokens=2, temperature=0.0),
        timeout=120,
    )
    assert len(req2.output_ids) >= 1


def test_abort_while_queued_never_prefills(engine):
    """Aborting before admission skips the slot entirely."""
    req = GenRequest(prompt_ids=[8, 8, 8], max_tokens=4, temperature=0.0)
    req.abort()
    engine.submit(req)
    assert req.done.wait(60)
    assert req.finish_reason == "abort"
    assert req.output_ids == []
