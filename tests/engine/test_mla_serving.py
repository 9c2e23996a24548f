"""A latent-attention (MLA) model on the served path: the engine's
scheduler, slots, host cache tier and transfer frames over a cache whose
``k`` and ``v`` differ in width, and what the engine says about it."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.models.config import config_from_hf
from gpustack_tpu.models.quant import quantize_params
from gpustack_tpu.models.transformer import forward, init_params
from tests.models.test_axk1 import HF, share


def model(hf, int8=False):
    cfg = dataclasses.replace(config_from_hf(hf, "tiny-axk1"), dtype="float32")
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    return cfg, quantize_params(params) if int8 else params


def greedy_oracle(cfg, params, prompt, n):
    """``n`` greedy tokens by a full forward a token, no cache."""
    ids = list(prompt)
    for _ in range(n):
        toks = jnp.asarray(ids, jnp.int32)[None]
        pos = jnp.arange(len(ids), dtype=jnp.int32)[None]
        logits, _ = forward(params, cfg, toks, pos)
        ids.append(int(jnp.argmax(logits[0, -1])))
    return ids[len(prompt):]


def gen(eng, prompt, n=6):
    return eng.generate(
        GenRequest(prompt_ids=list(prompt), max_tokens=n, temperature=0.0,
                   stop_ids=()),
        timeout=300,
    )


PROMPT = [5, 17, 42, 99, 7, 23, 81, 3] * 5     # 40 tokens, past YaRN's 16


@pytest.mark.parametrize("hf", [HF, share(4, 4)], ids=["all", "share"])
def test_the_engine_serves_the_oracle_s_tokens_and_says_what_it_ran(hf):
    cfg, params = model(hf)
    want = greedy_oracle(cfg, params, PROMPT, 6)
    eng = LLMEngine(cfg, params, max_slots=2, max_seq_len=128)
    eng.start()
    try:
        got = gen(eng, PROMPT)
        health = eng.health()
    finally:
        eng.stop()
    assert got.output_ids == want
    # the cache is the latent: (32 + 8) values of float32 here
    assert health["kv_cache_bytes_per_token"] == (32 + 8) * 4
    assert health["kv_cache_bytes"] == 3 * 2 * 128 * (32 + 8) * 4
    by_mode = {}
    for e in eng.flight.snapshot():
        by_mode.setdefault(e["mode"], set()).add(e.get("attn"))
    assert by_mode["prefill"] == {"mla_xla"}       # the CPU's prefill
    assert by_mode["decode"] == {"mla_absorbed"}
    pairs = health["moe_pairs"]
    if hf is HF:
        assert pairs is None
    else:
        # one prefill of the 64 bucket: 64 rows, 4 a token, 2 layers
        assert pairs["held"] + pairs["absent"] == 64 * 4 * 2
        assert 0 < pairs["held"] < pairs["absent"]


def test_a_gqa_model_s_records_do_not_know_the_word():
    from gpustack_tpu.models.config import get_config

    cfg = get_config("tiny")
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)),
                    max_slots=2, max_seq_len=64)
    eng.start()
    try:
        gen(eng, [1, 2, 3], 3)
        health = eng.health()
    finally:
        eng.stop()
    assert all("attn" not in e for e in eng.flight.snapshot())
    assert health["moe_pairs"] is None
    assert health["kv_cache_bytes_per_token"] == 2 * 2 * 16 * 2


def wait_blocks(eng, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if eng.health()["kv_cache_blocks"] >= 1:
            return
        time.sleep(0.05)
    raise AssertionError("host KV store never landed")


@pytest.mark.parametrize("int8_tier", [False, True], ids=["plain", "int8-tier"])
def test_the_host_tier_carries_latent_rows_and_a_prefix_continues_absorbed(int8_tier):
    """A repeated prompt is a prefix hit: the latent rows come back from
    the host tier (two blocks of different widths) and the suffix is
    prefilled over them in the absorbed form."""
    cfg, params = model(HF)
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=128,
        host_kv_cache_mb=64, kv_block_tokens=16, kv_cache_int8=int8_tier,
    )
    eng.start()
    try:
        first = gen(eng, PROMPT, 8)
        wait_blocks(eng)
        again = gen(eng, PROMPT + [9, 9, 9], 8)
        health = eng.health()
    finally:
        eng.stop()
    assert health["kv_cache_prefix_hits"] == 1
    assert again.prefix_tokens_reused >= 32
    if not int8_tier:
        assert again.output_ids == greedy_oracle(cfg, params, PROMPT + [9, 9, 9], 8)
        assert first.output_ids == greedy_oracle(cfg, params, PROMPT, 8)


def test_a_transfer_frame_carries_blocks_of_two_widths():
    from gpustack_tpu.engine import kv_transfer

    rng = np.random.default_rng(0)
    k = rng.normal(size=(3, 16, 1, 32)).astype(np.float32)
    v = rng.normal(size=(3, 16, 1, 8)).astype(np.float32)
    wire = b"".join(kv_transfer.encode_stream([
        kv_transfer.encode_frame("ab", range(16), k=k, v=v)
    ]))
    (frame,) = kv_transfer.decode_stream(wire)
    assert not frame.skipped and frame.tokens == tuple(range(16))
    np.testing.assert_array_equal(frame.k, k)
    np.testing.assert_array_equal(frame.v, v)


def test_chunked_prefill_of_a_latent_model_is_the_oracle_s():
    cfg, params = model(HF)
    eng = LLMEngine(cfg, params, max_slots=2, max_seq_len=128, prefill_chunk=32)
    eng.start()
    try:
        got = gen(eng, PROMPT * 2, 4)          # 80 tokens: three chunks
    finally:
        eng.stop()
    assert got.output_ids == greedy_oracle(cfg, params, PROMPT * 2, 4)


def test_a_position_sharded_runner_refuses_a_latent_cache():
    from gpustack_tpu.engine.runner import ModelRunner
    from gpustack_tpu.parallel.mesh import MeshPlan

    cfg, params = model(HF)
    with pytest.raises(ValueError, match="latent"):
        ModelRunner(cfg, params, plan=MeshPlan(sp=2), max_seq_len=128)
