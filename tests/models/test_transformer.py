"""Transformer core correctness: shapes, causality, cache parity, MoE."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models import (
    KVCache,
    ModelConfig,
    PRESETS,
    forward,
    init_params,
)
from gpustack_tpu.models.config import config_from_hf, get_config


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _tokens(cfg, b, t, seed=1):
    return jax.random.randint(
        jax.random.key(seed), (b, t), 0, cfg.vocab_size, dtype=jnp.int32
    )


def test_forward_shapes(tiny):
    cfg, params = tiny
    toks = _tokens(cfg, 2, 8)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    logits, cache = forward(params, cfg, toks, pos)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


def test_causality(tiny):
    cfg, params = tiny
    toks = _tokens(cfg, 1, 8)
    pos = jnp.arange(8, dtype=jnp.int32)[None, :]
    logits1, _ = forward(params, cfg, toks, pos)
    toks2 = toks.at[0, 5].set((toks[0, 5] + 1) % cfg.vocab_size)
    logits2, _ = forward(params, cfg, toks2, pos)
    # Positions before the edit are unaffected; position 5+ change.
    np.testing.assert_allclose(logits1[0, :5], logits2[0, :5], atol=1e-5)
    assert not np.allclose(logits1[0, 5], logits2[0, 5])


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe"])
def test_prefill_decode_matches_full_forward(preset):
    """The load-bearing engine invariant: prefill + N decode steps produce
    the same logits as one full causal forward."""
    cfg = get_config(preset)
    params = init_params(cfg, jax.random.key(0))
    B, T_pre, T_total, S = 2, 5, 9, 16
    toks = _tokens(cfg, B, T_total)
    pos_full = jnp.broadcast_to(jnp.arange(T_total, dtype=jnp.int32), (B, T_total))
    full_logits, _ = forward(params, cfg, toks, pos_full)

    cache = KVCache.create(cfg, B, S)
    pre_logits, cache = forward(
        params, cfg, toks[:, :T_pre], pos_full[:, :T_pre], cache
    )
    np.testing.assert_allclose(
        full_logits[:, :T_pre], pre_logits, rtol=5e-2, atol=5e-2
    )
    for t in range(T_pre, T_total):
        step_logits, cache = forward(
            params, cfg, toks[:, t : t + 1], pos_full[:, t : t + 1], cache
        )
        np.testing.assert_allclose(
            full_logits[:, t], step_logits[:, 0], rtol=5e-2, atol=5e-2
        )


def test_qkv_bias_and_sliding_window_run():
    cfg = dataclasses.replace(
        get_config("tiny"), qkv_bias=True, sliding_window=4
    )
    params = init_params(cfg, jax.random.key(0))
    toks = _tokens(cfg, 1, 8)
    pos = jnp.arange(8, dtype=jnp.int32)[None, :]
    logits, _ = forward(params, cfg, toks, pos)
    assert jnp.isfinite(logits).all()


def test_sliding_window_limits_attention():
    cfg = dataclasses.replace(get_config("tiny"), sliding_window=3)
    params = init_params(cfg, jax.random.key(0))
    toks = _tokens(cfg, 1, 10)
    pos = jnp.arange(10, dtype=jnp.int32)[None, :]
    logits1, _ = forward(params, cfg, toks, pos)
    # Tokens outside every remaining window can change freely.
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab_size)
    logits2, _ = forward(params, cfg, toks2, pos)
    np.testing.assert_allclose(logits1[0, -1], logits2[0, -1], atol=1e-5)


def test_llama3_rope_inv_freq_matches_hf_formula():
    """Numeric check of the llama3 band-wise frequency scaling."""
    from gpustack_tpu.models.transformer import rope_inv_freq

    cfg = dataclasses.replace(
        PRESETS["llama3-8b"],
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 8192,
        },
    )
    inv = np.asarray(rope_inv_freq(cfg))
    half = cfg.head_dim // 2
    base = 1.0 / (
        cfg.rope_theta ** (np.arange(0, half, dtype=np.float64) / half)
    )
    ref = np.empty_like(base)
    for i, f in enumerate(base):
        wavelen = 2 * np.pi / f
        if wavelen < 8192 / 4.0:          # high-freq band: unscaled
            ref[i] = f
        elif wavelen > 8192 / 1.0:        # low-freq band: /factor
            ref[i] = f / 8.0
        else:                              # medium band: interpolate
            smooth = (8192 / wavelen - 1.0) / (4.0 - 1.0)
            ref[i] = (1 - smooth) * f / 8.0 + smooth * f
    np.testing.assert_allclose(inv, ref, rtol=1e-6)


def test_llama3_rope_scaling_runs():
    cfg = dataclasses.replace(
        get_config("tiny"),
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )
    params = init_params(cfg, jax.random.key(0))
    toks = _tokens(cfg, 1, 8)
    pos = jnp.arange(8, dtype=jnp.int32)[None, :]
    logits, _ = forward(params, cfg, toks, pos)
    assert jnp.isfinite(logits).all()


def test_moe_matches_per_token_loop():
    """Dense-dispatch MoE == explicit per-token top-k loop."""
    from gpustack_tpu.models.transformer import _moe_mlp

    cfg = get_config("tiny-moe")
    key = jax.random.key(3)
    ks = jax.random.split(key, 5)
    d, fm, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    x = jax.random.normal(ks[0], (1, 6, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.1
    wg = jax.random.normal(ks[2], (E, d, fm), jnp.float32) * 0.1
    wu = jax.random.normal(ks[3], (E, d, fm), jnp.float32) * 0.1
    wd = jax.random.normal(ks[4], (E, fm, d), jnp.float32) * 0.1

    out = _moe_mlp(x, router, wg, wu, wd, cfg)

    ref = np.zeros_like(np.asarray(x))
    gates = jax.nn.softmax(x @ router, axis=-1)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            g = np.asarray(gates[b, t])
            topk = np.argsort(-g)[: cfg.num_experts_per_tok]
            w = g[topk] / g[topk].sum()
            for wi, e in zip(w, topk):
                h = np.asarray(x[b, t]) @ np.asarray(wg[e])
                u = np.asarray(x[b, t]) @ np.asarray(wu[e])
                act = np.asarray(jax.nn.silu(h)) * u
                ref[b, t] += wi * (act @ np.asarray(wd[e]))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_param_count_matches_init():
    for preset in ["tiny", "tiny-moe"]:
        cfg = get_config(preset)
        params = init_params(cfg, jax.random.key(0))
        n = sum(x.size for x in jax.tree.leaves(params))
        assert n == cfg.param_count(), preset


def test_config_from_hf_llama():
    hf = {
        "architectures": ["LlamaForCausalLM"],
        "hidden_size": 4096,
        "intermediate_size": 14336,
        "num_hidden_layers": 32,
        "num_attention_heads": 32,
        "num_key_value_heads": 8,
        "vocab_size": 128256,
        "rope_theta": 500000.0,
        "rms_norm_eps": 1e-5,
        "max_position_embeddings": 8192,
    }
    cfg = config_from_hf(hf, "llama")
    assert cfg.head_dim == 128 and cfg.attention_type == "GQA"
    assert cfg.param_count() == PRESETS["llama3-8b"].param_count()
    # ~8.03B params for Llama-3-8B
    assert 7.9e9 < cfg.param_count() < 8.1e9


def test_prefill_flash_matches_xla(tiny):
    """Engine prefill path with the pallas flash kernel (interpret mode)
    must match the XLA attention path bit-closely."""
    from gpustack_tpu.models.transformer import KVCache

    cfg, params = tiny
    B, T = 1, 160  # non-block-multiple: exercises pad masking
    toks = _tokens(cfg, B, T)
    positions = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32), (B, T)
    )
    logits_xla, cache_xla = forward(
        params, cfg, toks, positions, KVCache.create(cfg, B, T)
    )
    logits_fl, cache_fl = forward(
        params, cfg, toks, positions, KVCache.create(cfg, B, T),
        attn_impl="flash_interpret",
    )
    # flash keeps the PV matmul fp32 where the XLA path drops to
    # bf16 — small logit-level skew is expected; tight correctness is
    # asserted at kernel level in tests/ops/test_flash_attention.py
    np.testing.assert_allclose(
        np.asarray(logits_fl), np.asarray(logits_xla),
        rtol=0.1, atol=0.12,
    )
    # layer-0 cache writes are bit-identical (they precede the first
    # attention read; later layers inherit the tiny bf16 skew via x)
    np.testing.assert_array_equal(
        np.asarray(cache_fl.k[0]), np.asarray(cache_xla.k[0])
    )


@pytest.mark.parametrize(
    "feature",
    [
        {"sliding_window": 16},
        {"attn_logit_softcap": 50.0},
        {"attn_sinks": True},
    ],
    ids=lambda f: next(iter(f)),
)
def test_flash_on_a_model_the_kernel_refuses_raises(tiny, feature):
    """The caller chooses the kernel (engine/runner.py
    prefill_attention); asked for flash on a windowed, softcapped or
    sink model, ``forward`` says so instead of running XLA in silence."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, **feature)
    B, T = 1, 32
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    with pytest.raises(ValueError, match="flash_interpret"):
        forward(
            params, cfg, _tokens(cfg, B, T), positions,
            KVCache.create(cfg, B, T), attn_impl="flash_interpret",
        )


# ---------------------------------------------------------------------------
# The cache rides the layer scan as its carry; a step writes only its rows
# ---------------------------------------------------------------------------

_TINY, _TINY_MOE = get_config("tiny"), get_config("tiny-moe")
_CARRY_PRESETS = {
    "dense": _TINY,
    "moe": _TINY_MOE,
    # leading dense layers: two scans over one carried cache
    "two-stack": dataclasses.replace(
        _TINY_MOE, num_layers=3, first_k_dense=1
    ),
    "sliding-per-layer": dataclasses.replace(
        _TINY, num_layers=4, sliding_window=3,
        layer_sliding=(True, False, True, False),
    ),
}


def _loop_forward(params, cfg, toks):
    """The whole causal forward of ``toks`` [B, n] as a plain Python loop
    over the layers (no scan, no cache): ``(logits [B, n, V], k, v
    [L, B, n, H_kv, hd])``, from the module's per-layer pieces."""
    from gpustack_tpu.models.transformer import (
        _attend, _moe_mlp, apply_rope, rms_norm, rope_params, rope_sin_cos,
    )

    B, n = toks.shape
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
    sin, cos = rope_sin_cos(pos, rope_params(cfg)[0])
    causal = pos[:, :, None] >= pos[:, None, :]
    near = (pos[:, :, None] - pos[:, None, :]) < (cfg.sliding_window or n)
    x = params["embed"][toks]
    stacks = [(params["layers"], cfg.is_moe)]
    if "dense_layers" in params:
        stacks.insert(0, (params["dense_layers"], False))
    ks, vs = [], []
    for stack, moe in stacks:
        for i in range(len(stack["attn_norm"])):
            lp = jax.tree.map(lambda a: a[i], stack)
            slides = (
                cfg.layer_sliding[len(ks)] if cfg.layer_sliding
                else bool(cfg.sliding_window)
            )
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = (h @ lp["wq"]).reshape(B, n, cfg.num_heads, cfg.head_dim)
            k = (h @ lp["wk"]).reshape(B, n, cfg.num_kv_heads, cfg.head_dim)
            v = (h @ lp["wv"]).reshape(B, n, cfg.num_kv_heads, cfg.head_dim)
            q = apply_rope(q, sin, cos).reshape(
                B, n, cfg.num_kv_heads, cfg.group_size, cfg.head_dim
            )
            k = apply_rope(k, sin, cos)
            attn = _attend(
                q, k, v, causal & near if slides else causal,
                cfg.head_dim ** -0.5,
            )
            x = x + attn @ lp["wo"]
            h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            if moe:
                x = x + _moe_mlp(
                    h2, lp["router"], lp["we_gate"], lp["we_up"],
                    lp["we_down"], cfg,
                )
            else:
                x = x + (
                    jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])
                ) @ lp["w_down"]
            ks.append(k)
            vs.append(v)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x @ params["lm_head"], jnp.stack(ks), jnp.stack(vs)


@pytest.mark.parametrize("T", [1, 3], ids=["decode", "block"])
@pytest.mark.parametrize("preset", list(_CARRY_PRESETS))
def test_a_step_writes_only_its_rows_into_the_carried_cache(preset, T):
    cfg = dataclasses.replace(_CARRY_PRESETS[preset], dtype="float32")
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    B, S, n = 3, 16, 13
    lens = np.array([5, 9, 2])                   # ragged contexts
    toks = _tokens(cfg, B, n)
    ref_logits, ref_k, ref_v = _loop_forward(params, cfg, toks)

    # a cache of junk, each row's context in place below its position
    shape = (cfg.num_layers, B, S, cfg.num_kv_heads, cfg.head_dim)
    old_k = np.array(jax.random.normal(jax.random.key(7), shape))
    old_v = np.array(jax.random.normal(jax.random.key(8), shape))
    written = np.zeros(shape, bool)
    for b, m in enumerate(lens):
        old_k[:, b, :m] = ref_k[:, b, :m]
        old_v[:, b, :m] = ref_v[:, b, :m]
        written[:, b, m:m + T] = True
    step = np.stack([np.arange(m, m + T) for m in lens]).astype(np.int32)
    step_toks = jnp.take_along_axis(toks, jnp.asarray(step), axis=1)

    def run(k, v):
        return forward(
            params, cfg, step_toks, jnp.asarray(step), KVCache(k=k, v=v)
        )

    logits, new = jax.jit(run)(jnp.asarray(old_k), jnp.asarray(old_v))
    for got, old, ref in (
        (np.asarray(new.k), old_k, ref_k), (np.asarray(new.v), old_v, ref_v)
    ):
        # every other row of the cache is bit-identical to before
        np.testing.assert_array_equal(got[~written], old[~written])
        for b, m in enumerate(lens):
            np.testing.assert_allclose(
                got[:, b, m:m + T], ref[:, b, m:m + T], rtol=1e-5, atol=1e-5
            )
    for b, m in enumerate(lens):
        np.testing.assert_allclose(
            logits[b], ref_logits[b, m:m + T], rtol=1e-4, atol=1e-4
        )

    # the cache passes through every scan as carry alone: nothing of its
    # shape (or of a stack's share of it) among consts, xs or ys
    def cache_like(var):
        s = var.aval.shape
        return len(s) == 5 and s[1:] == shape[1:]

    scans = [
        e for e in jax.make_jaxpr(run)(old_k, old_v).eqns
        if e.primitive.name == "scan"
    ]
    assert len(scans) == (2 if preset == "two-stack" else 1)
    for e in scans:
        nc, ncar = e.params["num_consts"], e.params["num_carry"]
        carry_in = e.invars[nc:nc + ncar]
        assert [v.aval.shape for v in carry_in if cache_like(v)] == [shape] * 2
        outside = (
            e.invars[:nc] + e.invars[nc + ncar:] + e.outvars[ncar:]
        )
        assert not [v.aval.shape for v in outside if cache_like(v)]


@pytest.mark.parametrize("T", [1, 3, 16], ids=["row", "block", "whole"])
def test_row_write_by_position_equals_the_scatter(T):
    """The write a position-sharded cache gets (``by_position``) is the
    scatter's, bit for bit, a start past ``max_len - T`` clamped alike."""
    from gpustack_tpu.models.transformer import _write_rows

    L, B, S, H, D = 3, 4, 16, 2, 8
    buf = jax.random.normal(jax.random.key(0), (L, B, S, H, D), jnp.bfloat16)
    rows = jax.random.normal(jax.random.key(1), (B, T, H, D), jnp.bfloat16)
    start = jnp.asarray([0, 5, S - T, S - 1], jnp.int32)   # last: clamped
    got = [
        np.asarray(_write_rows(
            buf, rows, jnp.int32(1), start, by_position=by_position
        ).astype(jnp.float32))
        for by_position in (False, True)
    ]
    np.testing.assert_array_equal(got[0], got[1])
    want = np.array(buf.astype(jnp.float32))
    for b, s0 in enumerate(np.minimum(np.asarray(start), S - T)):
        want[1, b, s0:s0 + T] = np.asarray(rows[b].astype(jnp.float32))
    np.testing.assert_array_equal(got[0], want)


@pytest.mark.parametrize("T", [1, 3], ids=["decode", "ragged-prefill"])
@pytest.mark.parametrize("weights", ["float32", "int8"])
@pytest.mark.parametrize("preset", ["tiny", "tiny-qwen3"])   # qk-norm: no, yes
def test_finished_products_change_no_number(
    with_and_without_the_barrier, preset, weights, T
):
    """A decode step finishes ``wq``'s, ``wk``'s and ``wv``'s products
    behind a barrier so that the TPU reads the weights where they lie
    (``transformer.finish_products``). Same mathematics: the logits and
    every row of the cache, written or not, are bit for bit what the
    program without the barrier gives: a decode step and a block of
    rows at ragged positions, float32 weights and int8 under bf16."""
    from gpustack_tpu.models.quant import quantize_params

    cfg = get_config(preset)
    if weights == "float32":
        cfg = dataclasses.replace(cfg, dtype="float32")
        params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        dtype = jnp.float32
    else:
        params = quantize_params(init_params(cfg, jax.random.key(0)))
        dtype = jnp.bfloat16
    B, S = 3, 16
    lens = np.array([5, 9, 2])                   # ragged contexts
    shape = (cfg.num_layers, B, S, cfg.num_kv_heads, cfg.head_dim)
    k = jax.random.normal(jax.random.key(7), shape).astype(dtype)
    v = jax.random.normal(jax.random.key(8), shape).astype(dtype)
    positions = jnp.asarray(
        np.stack([np.arange(m, m + T) for m in lens]).astype(np.int32)
    )
    toks = _tokens(cfg, B, T)

    def program():
        def step(k, v):
            logits, cache = forward(
                params, cfg, toks, positions, KVCache(k=k, v=v)
            )
            return logits, cache.k, cache.v

        return step

    got, want = with_and_without_the_barrier(
        program, k, v, barriers=1 if T == 1 else 0
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.array_equal(np.asarray(got[1]), np.asarray(k))  # rows written


def test_no_barrier_stands_in_what_the_trainer_differentiates(tiny):
    """Without a cache (training, scoring) the products are handed on as
    they are, whatever ``T``: the barrier is a decode step's alone."""
    cfg, params = tiny
    toks = _tokens(cfg, 2, 1)
    pos = jnp.zeros((2, 1), jnp.int32)

    def loss(params):
        return forward(params, cfg, toks, pos)[0].sum()

    assert "optimization_barrier" not in str(jax.make_jaxpr(jax.grad(loss))(params))
