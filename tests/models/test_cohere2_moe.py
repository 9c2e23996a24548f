"""Command A+ (``cohere2_moe``: window and full attention layers in one
stack, the window layers' rows kept at window size, a parallel block
behind one LayerNorm, sigmoid top-k experts and averaged shared
experts): the engine's model code against the plain float32 reference
(``perfbench/reference/cohere2_moe.py``) at a small size with every
mechanism present, and what ``ModelConfig`` says of the published
file."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import (
    FAMILIES,
    config_from_hf,
    load_hf_config,
)
from gpustack_tpu.models.quant import quantize_params
from gpustack_tpu.models.transformer import (
    KVCache,
    _moe_mlp,
    forward,
    init_params,
    layer_norm,
    needs_xla_attention,
)
from perfbench.reference import cohere2_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG_DIR = os.path.join(
    ROOT, "perfbench", "configs", "command-a-plus-int8-ep8-l8"
)
WINDOW = 8
# window 8, two periods of three sliding layers and a full one, 8 experts
# of which 4 are held here, 4 query heads on 2; heads of 128 so that the
# decode kernel's merged view of positions and heads is the stored one
HF = {
    "architectures": ["Cohere2MoeForCausalLM"], "model_type": "cohere2_moe",
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 264, "sliding_window": WINDOW, "num_experts": 4,
    "experts_held": {"of": 8, "first": 2}, "num_experts_per_tok": 2,
    "num_shared_experts": 4, "layer_norm_eps": 1e-5, "rope_theta": 50000,
    "logit_scale": 1, "tie_word_embeddings": True, "norm_topk_prob": True,
    "expert_selection_fn": "sigmoid", "use_parallel_block": True,
    "shared_expert_combination_strategy": "average",
    "position_embedding_type": "rope_gptj", "first_k_dense_replace": 0,
}
BUCKET, STEPS = 16, 12
# one compile a program, whatever the test: every step of a decode loop
# would otherwise trace and look its scan up anew
forward = jax.jit(
    forward, static_argnames=("cfg", "attn_impl", "decode_attn_impl")
)


def model(hf=HF, int8=False):
    """Float32 activations either way; ``int8``: the matrices quantized,
    their scales bf16 as served."""
    cfg = dataclasses.replace(
        config_from_hf(hf, "tiny-command-a-plus"), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    return cfg, quantize_params(params) if int8 else params


def tokens_of(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(5, 260, size=n).tolist()


def through_the_cache(cfg, params, seq, n, prefill_impl, decode_impl):
    """``seq``'s first ``n`` tokens through a padded prefill into slot 1
    of a two-slot cache (slot 0 dead), the rest one decode step each:
    ``(logits at positions 0 .. len(seq) - 1, the cache)``."""
    padded = jnp.asarray([seq[:n] + [0] * (BUCKET - n)], jnp.int32)
    logits, small = forward(
        params, cfg=cfg, tokens=padded,
        positions=jnp.arange(BUCKET, dtype=jnp.int32)[None],
        cache=KVCache.create(cfg, 1, BUCKET, jnp.float32),
        attn_impl=prefill_impl, true_len=jnp.asarray([n], jnp.int32),
    )
    out = [logits[0, :n]]
    cache = KVCache.create(cfg, 2, 32, jnp.float32)
    rows = small.wk.shape[2]
    cache = KVCache(
        k=cache.k.at[:, 1, :BUCKET].set(small.k[:, 0]),
        v=cache.v.at[:, 1, :BUCKET].set(small.v[:, 0]),
        wk=cache.wk.at[:, 1, :rows].set(small.wk[:, 0]),
        wv=cache.wv.at[:, 1, :rows].set(small.wv[:, 0]),
    )
    for t in range(n, len(seq)):
        logits, cache = forward(
            params, cfg=cfg, tokens=jnp.asarray([[0], [seq[t]]], jnp.int32),
            positions=jnp.asarray([[0], [t]], jnp.int32), cache=cache,
            decode_attn_impl=decode_impl,
            live=jnp.asarray([False, True]),
        )
        out.append(logits[1])
    return jnp.concatenate(out), cache


@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize(
    "n", [5, WINDOW, 13],
    ids=["shorter_than_the_window", "the_window", "longer_than_the_window"],
)
@pytest.mark.parametrize(
    "prefill_impl,decode_impl",
    [("xla", "xla"), ("flash_interpret", "kernel_interpret")],
    ids=["einsum", "kernels"],
)
def test_prefill_then_decode_through_the_ring_is_the_reference_s_forward(
    n, prefill_impl, decode_impl, int8
):
    """Logits, not tokens: a padded prefill of ``n`` tokens, its rows into
    a slot, then 12 decode steps that wrap the ring of 8 rows at least
    once, against the reference's one forward over the whole sequence;
    and the ring the slot ends with against the reference's last 8 keys
    of every sliding layer."""
    cfg, params = model(int8=int8)
    seq = tokens_of(n + STEPS, seed=n)
    got, cache = through_the_cache(
        cfg, params, seq, n, prefill_impl, decode_impl
    )
    want, readings = ref.forward(
        params, HF, seq, list(range(len(seq))), rings=cache.wk[:, 1],
    )
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert readings["ring_err"] < 1e-5
    # the ring never grew: 8 rows a slot, whatever the slot's length
    assert cache.wk.shape == (6, 2, WINDOW, 2, 128)
    assert cache.k.shape == (2, 2, 32, 2, 128)


def test_the_padding_of_a_bucket_writes_nothing_into_the_ring():
    """A prefill of 5 real tokens in a bucket of 16 leaves rows 0..4 of
    the ring and zeros above; one of 13 leaves positions 8..12 in rows
    0..4 and 5..7 in rows 5..7, and nothing of the padding."""
    cfg, params = model()
    for n in (5, 13):
        seq = tokens_of(n, seed=1)
        _, small = forward(
            params, cfg=cfg,
            tokens=jnp.asarray([seq + [0] * (BUCKET - n)], jnp.int32),
            positions=jnp.arange(BUCKET, dtype=jnp.int32)[None],
            cache=KVCache.create(cfg, 1, BUCKET, jnp.float32),
            true_len=jnp.asarray([n], jnp.int32),
        )
        _, readings = ref.forward(
            params, HF, seq, [n - 1], rings=small.wk[:, 0]
        )
        assert readings["ring_err"] < 1e-5
        if n < WINDOW:
            assert not np.any(np.asarray(small.wk[:, 0, n:]))


def test_the_cacheless_forward_is_the_reference_s():
    cfg, params = model()
    seq = tokens_of(21)
    got, _ = forward(
        params, cfg=cfg, tokens=jnp.asarray([seq], jnp.int32),
        positions=jnp.arange(21, dtype=jnp.int32)[None],
    )
    want, _ = ref.forward(params, HF, seq, list(range(21)))
    np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize(
    "fault",
    [f for f in ref.FAULTS if f not in ("ring_at_position", "bf16_stated")],
)
def test_the_program_is_none_of_the_reference_s_faults(fault):
    """The window's edge on either side, rotary on the sliding layers
    only and in pairs, the shared experts' mean, the parallel block and
    the LayerNorm: the reference with any one of them wrong is far from
    the program, which agrees with the sound one (above)."""
    cfg, params = model()
    seq = tokens_of(21, seed=3)
    got, _ = forward(
        params, cfg=cfg, tokens=jnp.asarray([seq], jnp.int32),
        positions=jnp.arange(21, dtype=jnp.int32)[None],
    )
    wrong, _ = ref.forward(params, HF, seq, list(range(21)), fault=fault)
    assert float(jnp.max(jnp.abs(got[0] - wrong))) > 5e-3


def test_a_ring_written_at_position_is_not_what_the_program_keeps():
    cfg, params = model()
    seq = tokens_of(13 + STEPS, seed=13)
    _, cache = through_the_cache(cfg, params, seq, 13, "xla", "xla")
    _, readings = ref.forward(
        params, HF, seq, [0], rings=cache.wk[:, 1], fault="ring_at_position"
    )
    assert readings["ring_err"] > 0.1


def test_layer_norm_takes_the_mean_off_and_has_no_bias():
    x = jnp.asarray(np.random.default_rng(0).normal(3.0, 2.0, (4, 64)), jnp.float32)
    g = jnp.linspace(0.5, 1.5, 64)
    y = layer_norm(x, g, 1e-5)
    np.testing.assert_allclose(
        y, (x - x.mean(-1, keepdims=True))
        / np.sqrt(np.var(np.asarray(x), -1, keepdims=True) + 1e-5) * g,
        atol=1e-5,
    )


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """The share test: 8 experts whole (the reference, uncut) against
    two shares of 4 held experts each through the program's own expert
    layer, the shared experts (which every chip computes alike) counted
    once."""
    whole = {**HF, "num_experts": 8}
    del whole["experts_held"]
    cfg_whole = dataclasses.replace(
        config_from_hf(whole, "whole"), dtype="float32"
    )
    assert cfg_whole.experts_held == 0 and cfg_whole.num_experts == 8
    params = init_params(cfg_whole, jax.random.key(1), jnp.float32)
    lw = params["layers"]
    h = jnp.asarray(
        np.random.default_rng(2).normal(size=(1, 11, 64)), jnp.float32
    )
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(h[0], lw, (3,), whole, "", None)
        # what every chip computes alike: the layer without its routed
        # experts' down matrices
        shared, _ = ref.experts(
            h[0], {**lw, "we_down": jnp.zeros_like(lw["we_down"])}, (3,),
            whole, "", None,
        )
        parts = []
        for first in (0, 4):
            cfg = dataclasses.replace(
                config_from_hf(
                    {**HF, "experts_held": {"of": 8, "first": first}}, "share"
                ),
                dtype="float32",
            )
            assert (cfg.num_experts, cfg.experts_held) == (8, 4)
            held = slice(first, first + 4)
            parts.append(_moe_mlp(
                h, lw["router"][3], lw["we_gate"][3, held],
                lw["we_up"][3, held], lw["we_down"][3, held], cfg,
                shared=(
                    lw["ws_gate"][3], lw["ws_up"][3], lw["ws_down"][3], None
                ),
            )[0])
    np.testing.assert_allclose(
        parts[0] + parts[1] - shared, want, atol=1e-5, rtol=1e-5
    )
    # and a share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 1e-3


def test_the_published_file_counts_218_billion_and_the_cut_9_33():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        cut = json.load(f)
    with open(os.path.join(CONFIG_DIR, "deployment.json")) as f:
        deployment = json.load(f)
    assert deployment["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"
    ]
    published = {**cut, **deployment["published"]}
    del published["experts_held"]
    cfg = config_from_hf(published, "command-a-plus")
    assert cfg.num_layers == 32 and cfg.num_experts == 128
    assert round(cfg.param_count() / 1e9, 1) == 218.3
    held = load_hf_config(CONFIG_DIR)
    assert round(held.param_count() / 1e9, 2) == 9.33
    assert (held.num_experts, held.experts_held, held.first_held_expert) == (
        128, 16, 0
    )
    # every width, the window and the period as published
    for key in (
        "hidden_size", "num_heads", "num_kv_heads", "head_dim",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_experts_per_tok", "sliding_window",
    ):
        assert getattr(held, key) == getattr(cfg, key), key
    assert held.window_period == cfg.window_period == (True, True, True, False)
    assert (held.num_window_layers, held.num_kv_layers) == (6, 2)
    # 16 slots of 8,192: the window store is 60 % of the cache
    window = 16 * held.window_bytes_per_slot(8192)
    full = 16 * 8192 * held.kv_cache_bytes_per_token()
    assert (window, full) == (1610612736, 1073741824)


def test_the_family_is_read_by_name_and_takes_the_blocked_kernels():
    assert "Cohere2Moe" in FAMILIES
    cfg, _ = model()
    assert cfg.window_rows and cfg.parallel_block and cfg.layer_norm
    assert not needs_xla_attention(cfg)
    # a window that is a mask over S_max rows still takes the einsum
    from gpustack_tpu.models.config import get_config

    assert needs_xla_attention(get_config("gemma2-9b"))
    with pytest.raises(ValueError, match="average"):
        config_from_hf(
            {**HF, "shared_expert_combination_strategy": "sum"}, "x"
        )
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        config_from_hf({**HF, "first_k_dense_replace": 1}, "x")


def test_several_rows_a_slot_over_a_longer_cache_are_refused():
    """A chunk, a prefix or a draft would need rows the ring dropped."""
    cfg, params = model()
    with pytest.raises(ValueError, match="window size"):
        forward(
            params, cfg=cfg, tokens=jnp.zeros((1, 4), jnp.int32),
            positions=jnp.arange(4, dtype=jnp.int32)[None],
            cache=KVCache.create(cfg, 1, 32, jnp.float32),
        )
