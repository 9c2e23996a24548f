"""Nemotron-H (one mixer a layer: Mamba-2, two-matrix relu2 experts,
GQA without rotary): the engine's model code against the plain float32
reference (``perfbench/reference/nemotron_h.py``) at a small size with
every mechanism present, and what ``ModelConfig`` says of the published
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
from gpustack_tpu.models.hybrid import forward_hybrid, pad_expert_width
from gpustack_tpu.models.quant import QuantW, quantize_params
from gpustack_tpu.models.transformer import (
    KVCache,
    _moe_mlp,
    forward,
    head,
    init_params,
    make_step,
)
from perfbench.reference import nemotron_h as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HF = {
    "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 264,
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
}
T = 21


def model(hf=HF, int8=False):
    """Float32 activations either way (the CPU's bf16 products, compiled
    without optimizations as the suite compiles, accumulate in bf16: no
    model's rounding); ``int8``: the matrices quantized, their scales
    bf16 as served."""
    cfg = dataclasses.replace(
        config_from_hf(hf, "tiny-nemotron-h"), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    return cfg, quantize_params(params) if int8 else params


def tokens(n=T):
    return jax.random.randint(jax.random.key(1), (1, n), 0, HF["vocab_size"])


def test_the_config_reads_the_pattern_and_the_two_kinds_of_slot_memory():
    cfg = config_from_hf(HF)
    assert cfg.layer_kinds == tuple("MEM*EME") and cfg.num_layers == 7
    assert (cfg.layers_of("M"), cfg.num_moe_layers, cfg.num_kv_layers) == (
        3, 3, 1
    )
    assert cfg.moe_act == "relu2" and cfg.moe_scoring == "sigmoid"
    assert not cfg.rope and cfg.mamba_conv_dim == 32 + 2 * 2 * 16
    # rows a position: the one attention layer's; a state a slot: three
    # layers of [4, 8, 16] float32 and 3 rows of 96
    assert cfg.kv_cache_bytes_per_token(16) == 1 * 2 * 2 * 16 * 2
    assert cfg.state_bytes_per_slot(16) == 3 * (4 * 8 * 16 * 4 + 3 * 96 * 2)
    cache = KVCache.create(dataclasses.replace(cfg, dtype="float32"), 3, 32)
    assert cache.k.shape == (1, 3, 32, 2, 16)
    assert cache.ssm.shape == (3, 3, 4, 8, 16) and cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (3, 3, 3 * 96)
    # a model without such layers keeps neither
    plain = KVCache.create(config_from_hf({
        "hidden_size": 64, "num_attention_heads": 4, "vocab_size": 264,
        "num_hidden_layers": 2,
    }), 1, 8)
    assert plain.ssm is None and plain.conv is None


def test_the_published_file_counts_the_published_parameters():
    """``param_count`` of the catalog's config = the sum written down from
    its widths: 23 x 38.7 M + 23 x 1,297.5 M + 6 x 23.4 M + 704.6 M =
    31.6 B; the benchmark's cut holds 5.26 B of them."""
    with open(os.path.join(
        ROOT, "perfbench", "configs", "nemotron-3-nano-30b-a3b-int8-ep8",
        "config.json",
    )) as f:
        cut = json.load(f)
    whole = {k: v for k, v in cut.items() if k != "experts_held"}
    whole.update(n_routed_experts=128, vocab_size=131072)
    d, v = 2688, 131072
    mamba = (
        d * (4096 + 6144 + 64) + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * d + d
    )
    experts = d * 128 + 128 + 128 * 2 * d * 1856 + 2 * d * 3712 + d
    attention = 2 * d * 4096 + 2 * d * 256 + d
    total = 23 * mamba + 23 * experts + 6 * attention + 2 * v * d + d
    cfg = config_from_hf(whole)
    assert cfg.param_count() == total == 31_577_940_288
    assert round(mamba / 1e6, 1) == 38.7 and round(experts / 1e6, 1) == 1297.5
    held = config_from_hf(cut)
    assert (held.num_experts, held.num_held_experts) == (128, 16)
    assert held.param_count() == 5_258_420_544
    # 6 layers have rows, not 52; a slot's state whatever its length
    assert held.kv_cache_bytes_per_token(16) == 6 * 2 * 2 * 128 * 2
    assert held.state_bytes_per_slot(16) == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)


@pytest.mark.parametrize("arch", ["CommandAForCausalLM", "Zaya1ForCausalLM"])
def test_an_architecture_of_no_family_is_refused_by_name(arch):
    hf = {**HF, "architectures": [arch], "model_type": "other"}
    with pytest.raises(ValueError, match=arch):
        config_from_hf(hf)


def test_a_file_that_names_no_architecture_is_read_by_its_keys():
    cfg = config_from_hf({
        "hidden_size": 64, "num_attention_heads": 4, "vocab_size": 264,
        "num_hidden_layers": 2,
    })
    assert cfg.layer_kinds is None and cfg.num_kv_layers == 2
    assert "NemotronH" in FAMILIES and "Deepseek" in FAMILIES
    # every file the benchmark has still loads
    configs = os.path.join(ROOT, "perfbench", "configs")
    for name in os.listdir(configs):
        assert load_hf_config(os.path.join(configs, name)).num_layers > 0


def test_a_dense_mlp_layer_of_the_older_family_is_refused():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        config_from_hf({**HF, "hybrid_override_pattern": "M-M*EME"})


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_the_full_forward_is_the_reference_s(jitted):
    cfg, params = model()
    toks = tokens()
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    def run(params, tokens, positions):
        step, x = make_step(params, cfg, tokens, positions)
        x, _, _ = forward_hybrid(params, step, x)
        return head(x, params, cfg)

    if jitted:
        run = jax.jit(run)
    logits = run(params, tokens=toks, positions=pos)
    want, _ = ref.forward(params, HF, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=2e-4, atol=2e-4)


def test_the_int8_tree_is_read_alike_by_the_program_and_the_reference():
    cfg, params = model(int8=True)
    toks = tokens()
    logits, _, routing = forward(
        params, cfg, toks, jnp.arange(T, dtype=jnp.int32)[None],
        routing_out=True,
    )
    # a router that takes 2 of 8 turns on a rounding: the reference goes
    # where the program went, and says how far its own scores were
    want, readings = ref.forward(
        params, HF, toks[0].tolist(), list(range(T)),
        routing=tuple(r[:, 0] for r in routing),
    )
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(logits[0], want, rtol=2e-3, atol=2e-3)
    assert readings["score_err"] < 1e-3
    assert isinstance(params["ssm_layers"]["w_in"], QuantW)
    assert isinstance(params["moe_layers"]["we_up"], QuantW)
    assert params["ssm_layers"]["A_log"].dtype == jnp.float32


@pytest.mark.parametrize("update", ["xla", "kernel_interpret"])
def test_a_padded_prefill_then_decode_through_the_state_at_every_position(update):
    """13 tokens in a bucket of 16, the state handed to slot 1 of 3 whose
    neighbours are dead, then every further position through the cache:
    each logit is the reference's full forward's."""
    cfg, params = model()
    toks = tokens()
    want, _ = ref.forward(params, HF, toks[0].tolist(), list(range(T)))
    n, bucket = 13, 16
    padded = jnp.concatenate(
        [toks[:, :n], jnp.zeros((1, bucket - n), jnp.int32)], axis=1
    )
    logits, one = forward(
        params, cfg, padded, jnp.arange(bucket, dtype=jnp.int32)[None],
        KVCache.create(cfg, 1, bucket), true_len=jnp.array([n]),
    )
    np.testing.assert_allclose(logits[0, :n], want[:n], rtol=2e-4, atol=2e-4)
    # without true_len the state is the bucket's, not the prompt's
    _, wrong = forward(
        params, cfg, padded, jnp.arange(bucket, dtype=jnp.int32)[None],
        KVCache.create(cfg, 1, bucket),
    )
    assert float(jnp.abs(wrong.ssm - one.ssm).max()) > 1e-3
    cache = KVCache.create(cfg, 3, 32)
    cache = KVCache(
        k=cache.k.at[:, 1, :bucket].set(one.k[:, 0]),
        v=cache.v.at[:, 1, :bucket].set(one.v[:, 0]),
        ssm=cache.ssm.at[:, 1].set(one.ssm[:, 0]),
        conv=cache.conv.at[:, 1].set(one.conv[:, 0]),
    )
    live = jnp.array([False, True, False])
    step = jax.jit(lambda t, p, c: forward(
        params, cfg, t, p, c, live=live, ssm_impl=update,
        decode_attn_impl="xla",
    ))
    for t in range(n, T):
        tok = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(toks[0, t])
        at = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(t)
        logits, cache = step(tok, at, cache)
        np.testing.assert_allclose(
            logits[1, 0], want[t], rtol=2e-4, atol=2e-4
        )
    if update == "kernel_interpret":
        # the kernel never touched the neighbours' state
        assert not np.asarray(cache.ssm[:, 0]).any()
        assert not np.asarray(cache.ssm[:, 2]).any()


def test_a_continuation_goes_on_from_the_cache_s_state():
    """Several rows a slot over a cache (what a chunk or an ingest would
    run): the chunked scan starts from the slot's state."""
    cfg, params = model()
    toks = tokens()
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    whole, _ = forward(params, cfg, toks, pos, KVCache.create(cfg, 1, 32))
    _, cache = forward(
        params, cfg, toks[:, :9], pos[:, :9], KVCache.create(cfg, 1, 32)
    )
    rest, _ = forward(params, cfg, toks[:, 9:], pos[:, 9:], cache)
    np.testing.assert_allclose(rest[0], whole[0, 9:], rtol=2e-4, atol=2e-4)


def test_the_eight_shares_parts_add_up_to_the_uncut_layer():
    """Eight chips of two experts each, the shared expert counted once:
    the sum is what the uncut layer gives, and the reference's."""
    hf = {**HF, "n_routed_experts": 16}
    cfg, params = model(hf)
    lw = {k: v[0] for k, v in params["moe_layers"].items()}
    x = jax.random.normal(jax.random.key(3), (1, 11, 64))
    shared = (None, lw["ws_up"], lw["ws_down"], None)

    def layer(cfg, up, down, shared):
        return _moe_mlp(
            x, lw["router"], None, up, down, cfg,
            router_bias=lw["router_bias"], shared=shared,
        )

    whole = layer(cfg, lw["we_up"], lw["we_down"], shared)
    parts = sum(
        layer(
            dataclasses.replace(cfg, experts_held=2, first_held_expert=f),
            lw["we_up"][f:f + 2], lw["we_down"][f:f + 2], None,
        )
        for f in range(0, 16, 2)
    )
    only_shared = layer(
        dataclasses.replace(cfg, experts_held=2, first_held_expert=0),
        jnp.zeros_like(lw["we_up"][:2]), jnp.zeros_like(lw["we_down"][:2]),
        shared,
    )
    np.testing.assert_allclose(
        parts + only_shared, whole, rtol=2e-5, atol=2e-5
    )
    want, _ = ref.experts(x[0], params["moe_layers"], (0,), hf, "")
    np.testing.assert_allclose(whole[0], want, rtol=2e-4, atol=2e-4)


def test_a_share_s_forward_is_the_reference_s_with_the_same_share():
    hf = {**HF, "n_routed_experts": 4,
          "experts_held": {"of": 8, "first": 2}}
    cfg, params = model(hf)
    assert params["moe_layers"]["we_up"].shape[:2] == (3, 4)
    toks = tokens()
    logits, _, held = forward(
        params, cfg, toks, jnp.arange(T, dtype=jnp.int32)[None],
        count_held_pairs=True,
    )
    want, _ = ref.forward(params, hf, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=2e-4, atol=2e-4)
    assert 0 < int(held) < 3 * T * 2


def test_the_experts_width_is_stored_in_whole_lane_tiles_of_zeros():
    w = jnp.ones((2, 3, 8, 200))
    padded = pad_expert_width(w, -1)
    assert padded.shape == (2, 3, 8, 256) and not np.asarray(padded[..., 200:]).any()
    assert pad_expert_width(jnp.ones((3, 256, 8)), -2).shape == (3, 256, 8)
    # at the published width: 1,856 -> 1,920, the result the same bits
    cfg, params = model({**HF, "moe_intermediate_size": 96})
    assert params["moe_layers"]["we_up"].shape[-1] == 128
    assert params["moe_layers"]["we_down"].shape[-2] == 128
    cut = {
        **params, "moe_layers": {
            **params["moe_layers"],
            "we_up": params["moe_layers"]["we_up"][..., :96],
            "we_down": params["moe_layers"]["we_down"][..., :96, :],
        },
    }
    toks, pos = tokens(), jnp.arange(T, dtype=jnp.int32)[None]
    a, _ = forward(params, cfg, toks, pos)
    b, _ = forward(cut, cfg, toks, pos)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_checkpoint_s_names_round_trip(tmp_path):
    """A tree written under the family's checkpoint names
    (``backbone.layers.N.mixer.*``, torch's ``[out, in]``) loads back as
    the tree, int8 or not; a share reads only its experts."""
    import torch
    from safetensors.torch import save_file

    from gpustack_tpu.engine.weights import load_hf_checkpoint

    cfg, params = model()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    tensors = {
        "backbone.embeddings.weight": t(params["embed"]),
        "backbone.norm_f.weight": t(params["final_norm"]),
        "lm_head.weight": t(params["lm_head"].T),
    }
    at = {"M": 0, "E": 0, "*": 0}
    fm = cfg.moe_intermediate_size
    for i, kind in enumerate(cfg.layer_kinds):
        j = at[kind]
        at[kind] += 1
        pre = f"backbone.layers.{i}."
        if kind == "M":
            lw = {k: v[j] for k, v in params["ssm_layers"].items()}
            tensors.update({
                pre + "mixer.in_proj.weight": t(lw["w_in"].T),
                pre + "mixer.conv1d.weight": t(lw["conv_w"].T[:, None, :]),
                pre + "mixer.conv1d.bias": t(lw["conv_b"]),
                pre + "mixer.dt_bias": t(lw["dt_bias"]),
                pre + "mixer.A_log": t(lw["A_log"]),
                pre + "mixer.D": t(lw["D"]),
                pre + "mixer.norm.weight": t(lw["gate_norm"]),
                pre + "mixer.out_proj.weight": t(lw["w_out"].T),
            })
        elif kind == "E":
            lw = {k: v[j] for k, v in params["moe_layers"].items()}
            tensors.update({
                pre + "mixer.gate.weight": t(lw["router"].T),
                pre + "mixer.gate.e_score_correction_bias": t(lw["router_bias"]),
                pre + "mixer.shared_experts.up_proj.weight": t(lw["ws_up"].T),
                pre + "mixer.shared_experts.down_proj.weight": t(lw["ws_down"].T),
            })
            for e in range(cfg.num_experts):
                tensors[pre + f"mixer.experts.{e}.up_proj.weight"] = t(
                    lw["we_up"][e, :, :fm].T
                )
                tensors[pre + f"mixer.experts.{e}.down_proj.weight"] = t(
                    lw["we_down"][e, :fm].T
                )
        else:
            lw = {k: v[j] for k, v in params["attn_layers"].items()}
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                tensors[pre + f"mixer.{theirs}.weight"] = t(lw[ours].T)
        tensors[pre + "norm.weight"] = t(lw["norm"])
    save_file(
        {k: v.contiguous() for k, v in tensors.items()},
        str(tmp_path / "model.safetensors"),
    )
    loaded = load_hf_checkpoint(cfg, str(tmp_path))
    flat_a = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_b = dict(jax.tree_util.tree_flatten_with_path(loaded)[0])
    assert {p for p, _ in flat_a} == set(flat_b)
    for path, leaf in flat_a:
        np.testing.assert_allclose(
            np.asarray(flat_b[path], np.float32), np.asarray(leaf, np.float32),
            rtol=1e-2, atol=1e-3, err_msg=str(path),
        )
    toks, pos = tokens(), jnp.arange(T, dtype=jnp.int32)[None]
    a, _ = forward(params, cfg, toks, pos)
    b, _ = forward(
        jax.tree.map(lambda x: x.astype(jnp.float32), loaded), cfg, toks, pos
    )
    assert float(jnp.abs(a - b).max()) < 0.1
    # int8: the matrices quantized, the recurrence's own numbers not
    q = load_hf_checkpoint(cfg, str(tmp_path), "int8")
    assert isinstance(q["ssm_layers"]["w_in"], QuantW)
    assert isinstance(q["moe_layers"]["we_up"], QuantW)
    assert q["ssm_layers"]["A_log"].dtype == jnp.float32
    assert q["ssm_layers"]["conv_w"].dtype == jnp.float32
    # a share holds its own experts only
    share = config_from_hf({
        **HF, "n_routed_experts": 2,
        "experts_held": {"of": 8, "first": 4},
    })
    part = load_hf_checkpoint(share, str(tmp_path))
    np.testing.assert_allclose(
        np.asarray(part["moe_layers"]["we_up"], np.float32),
        np.asarray(params["moe_layers"]["we_up"][:, 4:6], np.float32),
        rtol=1e-2, atol=1e-3,
    )


@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
def test_the_attention_layer_s_finished_products_change_no_number(
    with_and_without_the_barrier, int8
):
    """The hybrid's GQA layers take ``wq``, ``wk`` and ``wv`` through the
    helper every other model's layer does (``transformer.
    qkv_projections``), with its barrier in a decode step, and every
    mixer reads its conv rows behind a barrier of its own
    (``hybrid.rows_read_a_layer``): logits, rows and both states are
    bit for bit the program's without any of them."""
    cfg, params = model(int8=int8)
    toks = tokens(2).reshape(2, 1)
    pos = jnp.asarray([[7], [3]], jnp.int32)
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, 2, 16))
    cache = jax.tree.map(
        lambda s: 0.1 * jax.random.normal(
            jax.random.key(s.size % 97), s.shape
        ).astype(s.dtype),
        shapes,
    )

    def program():
        def step(cache):
            return forward(params, cfg, toks, pos, cache)

        return step

    got, want = with_and_without_the_barrier(
        program, cache, barriers=1 + cfg.layers_of("M")
    )
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---- the conv rows: read as the step received them, written once (PR 64) ----

MIXERS = {**HF, "hybrid_override_pattern": "MMM", "num_hidden_layers": 3}


def layer_at_a_time(params, cfg, toks, pos, cache, *, live, true_len, impl):
    """A stack of mixers over a cache as every hybrid program ran it
    until PR 64, written out: each mixer is handed the cache the one
    before it left, reads its rows out of that and writes its new rows
    into the stack before the next one runs."""
    from gpustack_tpu.models import transformer as tf
    from gpustack_tpu.models.hybrid import mamba_mixer

    step, x = make_step(
        params, cfg, toks, pos, cache, live=live, true_len=true_len,
        ssm_impl=impl,
    )
    for i in range(cfg.layers_of("M")):
        lp = jax.tree.map(lambda a: a[i], params["ssm_layers"])
        out, cache, kept = mamba_mixer(
            tf.rms_norm(x, lp["norm"], cfg.rms_norm_eps), lp, cache,
            jnp.int32(i), step,
        )
        cache = dataclasses.replace(
            cache, conv=jax.lax.dynamic_update_index_in_dim(
                cache.conv, kept, i, 0
            ),
        )
        x = x + out
    return tf.head(x, params, cfg, None, False), cache


def same_bits(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("update", ["xla", "kernel_interpret"])
def test_eight_steps_leave_the_rows_a_layer_at_a_time_would(update, int8):
    """Eight decode steps over four slots, two of them dead and their
    rows not zero: ``KVCache.conv``, ``KVCache.ssm`` and every logit are
    bit for bit those of the loop that updates the stack a layer at a
    time, dead slots and all."""
    cfg, params = model(MIXERS, int8=int8)
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, 4, 16))
    cache = jax.tree.map(
        lambda s: 0.1 * jax.random.normal(
            jax.random.key(s.size % 89), s.shape
        ).astype(s.dtype),
        shapes,
    )
    live = jnp.array([True, False, True, False])
    step = jax.jit(lambda t, p, c: forward(
        params, cfg, t, p, c, live=live, ssm_impl=update,
        decode_attn_impl="xla",
    ))
    plain = jax.jit(lambda t, p, c: layer_at_a_time(
        params, cfg, t, p, c, live=live, true_len=None, impl=update,
    ))
    got = want = cache
    for t in range(8):
        toks = jax.random.randint(jax.random.key(t), (4, 1), 0, 264)
        pos = jnp.full((4, 1), t, jnp.int32)
        logits, got = step(toks, pos, got)
        ref_logits, want = plain(toks, pos, want)
        same_bits(
            (logits, got.conv, got.ssm), (ref_logits, want.conv, want.ssm)
        )
    # the rows moved, the dead slots' too (a mixer keeps every slot's)
    assert float(jnp.abs(got.conv - cache.conv).min(axis=-1).max()) > 0


def test_a_padded_prefill_its_insert_and_a_step_leave_those_rows_too():
    """13 tokens in a bucket of 16 (``true_len``), the slot's state and
    rows put into slot 1 of 3, then a step with slot 2 dead: at each of
    the three the cache and the logits are the layer-at-a-time loop's,
    bit for bit."""
    cfg, params = model(MIXERS)
    n, bucket = 13, 16
    padded = jnp.concatenate(
        [tokens()[:, :n], jnp.zeros((1, bucket - n), jnp.int32)], axis=1
    )
    pos = jnp.arange(bucket, dtype=jnp.int32)[None]
    true_len = jnp.array([n])
    # each side one program: XLA rounds a fused program's float32 and
    # an eager one's a last bit apart
    logits, one = jax.jit(lambda c: forward(
        params, cfg, padded, pos, c, true_len=true_len
    ))(KVCache.create(cfg, 1, bucket))
    ref_logits, ref_one = jax.jit(lambda c: layer_at_a_time(
        params, cfg, padded, pos, c, live=None, true_len=true_len,
        impl="scan",
    ))(KVCache.create(cfg, 1, bucket))
    same_bits(
        (logits, one.conv, one.ssm), (ref_logits, ref_one.conv, ref_one.ssm)
    )
    # padding is not kept: the rows end at the 13th position
    _, whole = forward(
        params, cfg, padded, pos, KVCache.create(cfg, 1, bucket)
    )
    assert float(jnp.abs(whole.conv - one.conv).max()) > 1e-3

    def insert(got):
        cache = KVCache.create(cfg, 3, 32)
        return dataclasses.replace(
            cache, ssm=cache.ssm.at[:, 1].set(got.ssm[:, 0]),
            conv=cache.conv.at[:, 1].set(got.conv[:, 0]),
        )

    live = jnp.array([True, True, False])
    tok = jnp.array([[5], [int(tokens()[0, n])], [0]], jnp.int32)
    at = jnp.array([[0], [n], [0]], jnp.int32)
    for update in ("xla", "kernel_interpret"):
        logits, got = jax.jit(lambda c: forward(
            params, cfg, tok, at, c, live=live, ssm_impl=update,
            decode_attn_impl="xla",
        ))(insert(one))
        ref_logits, want = jax.jit(lambda c: layer_at_a_time(
            params, cfg, tok, at, c, live=live, true_len=None, impl=update,
        ))(insert(ref_one))
        same_bits(
            (logits, got.conv, got.ssm), (ref_logits, want.conv, want.ssm)
        )


@pytest.mark.parametrize("update", ["xla", "kernel_interpret"])
def test_the_whole_pattern_s_step_reads_the_rows_as_it_received_them(
    update, monkeypatch
):
    """The same through every kind of layer (``MEM*EME``): a step whose
    mixers update the stack they are handed one after another, and read
    their rows out of what the ones before them left, gives the bits of
    the step whose mixers all read the stack as the step received it."""
    from gpustack_tpu.models import hybrid

    cfg, params = model()
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, 3, 16))
    cache = jax.tree.map(
        lambda s: 0.1 * jax.random.normal(
            jax.random.key(s.size % 83), s.shape
        ).astype(s.dtype),
        shapes,
    )
    live = jnp.array([True, False, True])
    toks = jnp.array([[3], [0], [9]], jnp.int32)
    pos = jnp.array([[7], [0], [2]], jnp.int32)

    def run():
        return forward(
            params, cfg, toks, pos, cache, live=live, ssm_impl=update,
            decode_attn_impl="xla",
        )

    got = run()
    mixer = hybrid.mamba_mixer

    def a_layer_at_a_time(h, lp, carried, i, step):
        out, carried, kept = mixer(h, lp, carried, i, step)
        return out, dataclasses.replace(
            carried, conv=jax.lax.dynamic_update_index_in_dim(
                carried.conv, kept, i, 0
            ),
        ), kept

    monkeypatch.setattr(hybrid, "mamba_mixer", a_layer_at_a_time)
    monkeypatch.setattr(hybrid, "rows_read_a_layer", lambda conv, x: conv)
    same_bits(got, run())
