"""Chunked prefill: token parity with one-shot prefill + decode
interleaving (vLLM enable-chunked-prefill role, TPU-native formulation:
chunks ride the prefix-continuation jit path)."""

import jax
import numpy as np
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.models import init_params
from gpustack_tpu.models.config import get_config
from gpustack_tpu.testing.oracle import greedy_reference as _greedy_reference


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _prompt(cfg, n, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, n).tolist()


def test_chunked_prefill_token_parity(setup):
    """Chunked engine output == unchunked == cacheless oracle."""
    cfg, params = setup
    prompt = _prompt(cfg, 100)  # 4 chunks of 32 (last partial)
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=192, prefill_chunk=32
    )
    eng.start()
    try:
        req = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=6, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        eng.stop()
    oracle = _greedy_reference(cfg, params, prompt, 6)
    assert req.output_ids == oracle


def test_chunked_prefill_interleaves_decode(setup):
    """While a long prompt prefills chunk-by-chunk, an already-running
    request keeps producing tokens between chunks."""
    cfg, params = setup
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=256, prefill_chunk=32
    )
    # no background thread: drive step() manually to observe interleaving
    short = GenRequest(
        prompt_ids=_prompt(cfg, 8, seed=1), max_tokens=64,
        temperature=0.0, stop_ids=(),
    )
    short.request_id = "short"
    eng.submit(short)
    for _ in range(4):
        eng.step()
    assert 0 in eng._slots or 1 in eng._slots  # short is decoding

    long = GenRequest(
        prompt_ids=_prompt(cfg, 180, seed=2), max_tokens=4,
        temperature=0.0, stop_ids=(),
    )
    long.request_id = "long"
    eng.submit(long)
    eng.step()  # admits → registers the chunk job
    assert eng._chunk_jobs, "long prompt should be chunking"

    # every further step advances at most one chunk AND decodes the
    # short request: its output grows while the job is still in flight
    tokens_before = len(short.output_ids)
    steps_with_job = 0
    while eng._chunk_jobs:
        eng.step()
        steps_with_job += 1
        assert steps_with_job < 50
    assert steps_with_job >= 3  # 180 tokens / 32-token chunks
    eng._drain_pending()
    assert len(short.output_ids) > tokens_before

    # long request finalizes and completes correctly
    while not long.done.is_set():
        if not eng.step():
            eng._drain_pending()
    oracle = _greedy_reference(cfg, params, long.prompt_ids, 4)
    assert long.output_ids == oracle[: len(long.output_ids)]


def test_chunked_prefill_with_host_kv_cache(setup):
    """A chunked prefill stores its KV blocks; an identical follow-up
    prompt matches them and chunk-prefills only the unmatched tail."""
    cfg, params = setup
    prompt = _prompt(cfg, 70)
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=192,
        prefill_chunk=32, host_kv_cache_mb=64, kv_block_tokens=16,
    )
    eng.start()
    try:
        r1 = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=4, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
        # wait for the async host copy to land
        eng._kv_copy_pool.shutdown(wait=True)
        assert eng.host_kv_cache is not None
        r2 = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=4, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        eng.stop()
    assert r1.output_ids == r2.output_ids
    assert eng.host_kv_cache.hits >= 1
    # 70-token prompt = 4 full 16-blocks, all reused on the repeat
    assert r2.prefix_tokens_reused >= 64


def test_chunked_prefill_flash_continuation_parity(setup, monkeypatch):
    """Chunk continuations through the pallas flash kernel (q_offset,
    interpret mode) produce the same tokens as the XLA path.

    fp32 compute: in bf16 the two kernels differ by 1-2 output ulps,
    which flips argmax near-ties on a random tiny model — kernel-level
    equivalence (incl. offsets) is asserted at tight fp32 tolerance in
    tests/ops/test_flash_attention.py."""
    import dataclasses

    cfg, params = setup
    cfg = dataclasses.replace(cfg, dtype="float32")
    prompt = _prompt(cfg, 90, seed=7)

    from gpustack_tpu.engine.runner import ModelRunner

    def run(attn_impl):
        # steer the kernel choice from the test: the serving path has
        # no way into the pallas interpreter
        monkeypatch.setattr(
            ModelRunner, "attn_impl_for", lambda self, bucket: attn_impl
        )
        eng = LLMEngine(
            cfg, params, max_slots=1, max_seq_len=192, prefill_chunk=32
        )
        eng.start()
        try:
            return eng.generate(
                GenRequest(
                    prompt_ids=prompt, max_tokens=5, temperature=0.0,
                    stop_ids=(),
                ),
                timeout=600,
            ).output_ids
        finally:
            eng.stop()

    assert run("flash_interpret") == run("xla") == _greedy_reference(
        cfg, params, prompt, 5
    )


def test_prefill_chunk_clamped_to_top_bucket(setup):
    """chunk >= max bucket degrades to a no-op, not a startup crash."""
    cfg, params = setup
    eng = LLMEngine(
        cfg, params, max_slots=1, max_seq_len=128, prefill_chunk=4096
    )
    prompt = _prompt(cfg, 60, seed=9)
    eng.start()
    try:
        req = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=3, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        eng.stop()
    assert req.output_ids == _greedy_reference(cfg, params, prompt, 3)


def test_chunk_overflow_falls_back_to_one_shot(setup):
    """A chunk schedule whose continuation would overflow the top
    bucket (non-power-of-two max_seq_len) falls back to one-shot
    prefill instead of corrupting the cache or killing the loop."""
    cfg, params = setup
    # buckets: 32,64,128,150 — prompt 140 with chunk 64 needs a
    # continuation at start=128 with sb=32 -> 160 > 150
    eng = LLMEngine(
        cfg, params, max_slots=1, max_seq_len=150, prefill_chunk=64
    )
    prompt = _prompt(cfg, 140, seed=11)
    eng.start()
    try:
        req = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=4, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        eng.stop()
    assert not eng._chunk_jobs
    assert req.output_ids == _greedy_reference(cfg, params, prompt, 4)


def test_chunked_prefill_seeds_from_cached_prefix(setup):
    """A chunked job starts from the host cache's longest prefix
    instead of re-prefilling tokens the cache already holds."""
    cfg, params = setup
    base = _prompt(cfg, 60, seed=13)
    eng = LLMEngine(
        cfg, params, max_slots=1, max_seq_len=256,
        prefill_chunk=32, host_kv_cache_mb=64, kv_block_tokens=16,
    )
    eng.start()
    try:
        eng.generate(
            GenRequest(
                prompt_ids=base, max_tokens=2, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
        # wait for the async host copy to land (don't shut the pool
        # down — later prefills still store through it)
        import time as _time

        deadline = _time.time() + 60
        while not eng.host_kv_cache.entries and _time.time() < deadline:
            _time.sleep(0.05)
        hits_before = eng.host_kv_cache.prefix_hits
        extended = base + _prompt(cfg, 60, seed=14)
        req = eng.generate(
            GenRequest(
                prompt_ids=extended, max_tokens=4, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        eng.stop()
    assert eng.host_kv_cache.prefix_hits > hits_before
    assert req.output_ids == _greedy_reference(cfg, params, extended, 4)


def test_chunked_prefix_seeded_vs_cold_token_parity(setup):
    """Satellite coverage: greedy outputs are IDENTICAL for the same
    prompt run as a cold chunk job (cache off) and as a prefix-seeded
    chunk job (cache on, warm) — and the fits() overflow fallback keeps
    holding with a warm cache on a non-power-of-two max_seq_len."""
    cfg, params = setup
    base = _prompt(cfg, 60, seed=21)
    extended = base + _prompt(cfg, 50, seed=22)

    cold = LLMEngine(
        cfg, params, max_slots=1, max_seq_len=256, prefill_chunk=32
    )
    cold.start()
    try:
        want = cold.generate(
            GenRequest(
                prompt_ids=extended, max_tokens=5, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        ).output_ids
    finally:
        cold.stop()

    warm = LLMEngine(
        cfg, params, max_slots=1, max_seq_len=256,
        prefill_chunk=32, host_kv_cache_mb=64, kv_block_tokens=16,
    )
    warm.start()
    try:
        warm.generate(
            GenRequest(
                prompt_ids=base, max_tokens=2, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
        warm._kv_copy_pool.shutdown(wait=True)
        req = warm.generate(
            GenRequest(
                prompt_ids=extended, max_tokens=5, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        warm.stop()
    assert req.prefix_tokens_reused >= 48          # 3 of base's blocks
    assert req.output_ids == want
    assert req.output_ids == _greedy_reference(cfg, params, extended, 5)


def test_chunk_overflow_fallback_with_warm_cache(setup):
    """fits() bounds guard with a MATCHED prefix on a non-power-of-two
    max_seq_len (buckets 32..128,150): the full 128-token match
    overflows (128 + 32 > 150), so the planner must TRIM the matched
    run block-by-block to an offset whose continuation fits — and the
    output must stay bit-identical to the cold run either way."""
    cfg, params = setup
    prompt = _prompt(cfg, 140, seed=23)
    eng = LLMEngine(
        cfg, params, max_slots=1, max_seq_len=150,
        prefill_chunk=64, host_kv_cache_mb=64, kv_block_tokens=16,
    )
    eng.start()
    try:
        r1 = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=4, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
        eng._kv_copy_pool.shutdown(wait=True)
        # warm repeat: blocks exist now, but any continuation from a
        # 16-aligned offset still overflows (plen + sb > 150 for every
        # plen the 64-token chunk schedule would use) — the non-chunked
        # prefix path may still serve what fits within the top bucket
        r2 = eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=4, temperature=0.0,
                stop_ids=(),
            ),
            timeout=300,
        )
    finally:
        eng.stop()
    assert not eng._chunk_jobs
    oracle = _greedy_reference(cfg, params, prompt, 4)
    assert r1.output_ids == oracle
    assert r2.output_ids == oracle
