"""The final norm and the vocabulary head on the rows a caller names
(``transformer.head``, ``forward(..., logits_at=)``): a prefill keeps one
row of its bucket and computes that row alone. The families here are the
ones that differ at the head: an untied int8-able matrix, a tied
embedding, a delta-gain norm under a soft cap, a latent-attention stack,
the hybrid's own layer loop, and a LayerNorm under ``logit_scale``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.engine.runner import ModelRunner
from gpustack_tpu.models.config import config_from_hf, get_config
from gpustack_tpu.models.transformer import KVCache, forward, init_params
from tests.models.test_cohere2_moe import HF as WINDOW_HF
from tests.models.test_nemotron_h import HF as HYBRID_HF

TINY = dict(
    vocab_size=264, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, max_position_embeddings=256,
)
T = 16                     # the bucket
TRUE_LEN = (11, 5)         # a sequence's real tokens, each under T


def _config(family: str):
    if family == "qwen3":
        cfg = get_config("tiny-qwen3")
    elif family == "tied":
        cfg = dataclasses.replace(
            get_config("tiny"), tie_word_embeddings=True
        )
    elif family == "softcap":
        cfg = dataclasses.replace(
            get_config("gemma2-9b"), **TINY, head_dim=16,
            query_pre_attn_scalar=16.0, sliding_window=8,
            layer_sliding=(True, False),
        )
    elif family == "mla":
        cfg = dataclasses.replace(
            get_config("deepseek-v2-lite"), **{**TINY, "num_kv_heads": 4},
            head_dim=24, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=32,
            n_shared_experts=1, shared_expert_intermediate_size=32,
            rope_scaling=None,
        )
    elif family == "hybrid":
        cfg = config_from_hf(HYBRID_HF, "tiny-nemotron-h")
    else:
        cfg = config_from_hf(
            {**WINDOW_HF, "head_dim": 16, "logit_scale": 0.25},
            "tiny-command-a-plus",
        )
    return dataclasses.replace(cfg, dtype="float32").validate()


FAMILIES = ("qwen3", "tied", "softcap", "mla", "hybrid", "window")


@pytest.fixture(scope="module", params=FAMILIES)
def model(request):
    cfg = _config(request.param)
    return cfg, init_params(cfg, jax.random.key(0), jnp.float32)


@pytest.fixture(scope="module")
def whole(model):
    """A padded prefill of two sequences, every row's logits, and the
    jitted ``forward`` that made them."""
    cfg, params = model
    tokens = jax.random.randint(
        jax.random.key(1), (2, T), 5, cfg.vocab_size
    )
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T))
    beside = (
        {"true_len": jnp.asarray(TRUE_LEN, jnp.int32)}
        if cfg.layer_kinds is not None or cfg.window_rows else {}
    )

    @jax.jit
    def run(params, logits_at):
        return forward(
            params, cfg, tokens, positions, KVCache.create(cfg, 2, T),
            logits_at=logits_at, **beside,
        )[0]

    want = np.asarray(run(params, None))
    assert want.shape == (2, T, cfg.vocab_size)
    return run, want


@pytest.mark.parametrize("row", ["first", "last", "tail_edge"])
def test_the_named_row_s_logits_are_the_whole_forward_s(model, whole, row):
    """``forward(..., logits_at=p)`` is ``forward(...)[:, p]``, a row a
    sequence: the first, the last, and the last real one of a padded
    bucket, which is what a prefill names."""
    cfg, params = model
    run, want = whole
    p = {
        "first": (0, 0), "last": (T - 1, T - 1),
        "tail_edge": tuple(n - 1 for n in TRUE_LEN),
    }[row]
    got = run(params, jnp.asarray(p, jnp.int32))
    assert got.shape == (2, 1, cfg.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got)[:, 0], want[np.arange(2), list(p)],
        rtol=1e-5, atol=1e-5,
    )


def test_a_prefill_returns_the_row_it_returned(model):
    """``ModelRunner.prefill`` still hands on float32 ``[V]``, the last
    real position's logits of the whole forward over the prompt."""
    cfg, params = model
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=64)
    n = 11
    ids = [(5 + 7 * i) % 250 + 5 for i in range(n)]
    bucket = runner.bucket_for(n)
    last, *_ = runner.prefill(ids + [0] * (bucket - n), n)
    assert last.shape == (cfg.vocab_size,) and last.dtype == jnp.float32
    want, _ = forward(
        params, cfg, jnp.asarray(ids, jnp.int32)[None],
        jnp.arange(n, dtype=jnp.int32)[None],
    )
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(want)[0, n - 1], rtol=2e-2, atol=2e-2
    )
    assert int(jnp.argmax(last)) == int(jnp.argmax(want[0, n - 1]))
