"""A.X-K1 (DeepSeek-V3 family as SKT ships it): the engine's model code
against the plain float32 reference (``testing/reference_axk1.py``), at a
small size with every mechanism present: q-lora, the nope/rope split,
YaRN past its original window (the softmax scale's m^2), one leading
dense layer, a shared expert, sigmoid scores with a correction bias,
group-limited selection (2 of 4 groups of 4), the 2.5x on the routed
part, int8 weights, and one chip's share of the experts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import config_from_hf
from gpustack_tpu.models.quant import quantize_params
from gpustack_tpu.models.transformer import (
    KVCache,
    _moe_mlp,
    _route,
    forward,
    held_capacity,
    init_params,
    moe_dispatch,
)
from gpustack_tpu.testing import reference_axk1 as ref

HF = {
    "architectures": ["DeepseekV3ForCausalLM"], "model_type": "axk1",
    "vocab_size": 264, "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {
        "type": "yarn", "factor": 32, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16,
    },
    "max_position_embeddings": 512, "first_k_dense_replace": 1,
    "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "none",
    "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
}
T = 24   # prompt; past the YaRN window of 16


def share(first: int, held: int):
    """``HF`` as the chip that holds ``held`` experts from ``first`` on."""
    return {
        **HF, "n_routed_experts": held,
        "experts_held": {"published": 16, "first": first},
    }


def model(hf, int8=False, dtype="float32"):
    cfg = dataclasses.replace(config_from_hf(hf, "tiny-axk1"), dtype=dtype)
    params = init_params(
        cfg, jax.random.key(0),
        dtype=jnp.float32 if dtype == "float32" else jnp.bfloat16,
    )
    # a correction bias that matters: selection and weights must differ
    bias = jax.random.uniform(
        jax.random.key(9), params["layers"]["router_bias"].shape,
        minval=-0.3, maxval=0.3,
    )
    params["layers"]["router_bias"] = bias
    return cfg, quantize_params(params) if int8 else params


def tokens(n, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, HF["vocab_size"])


def test_the_file_loads_to_the_family_with_its_groups_and_its_share():
    cfg = config_from_hf(share(4, 4))
    assert cfg.is_mla and cfg.moe_scoring == "sigmoid"
    assert (cfg.n_group, cfg.topk_group) == (4, 2)
    assert (cfg.num_experts, cfg.num_held_experts) == (16, 4)
    assert cfg.first_held_expert == 4 and cfg.routed_scaling_factor == 2.5
    assert cfg.kv_row_shapes == ((1, 32), (1, 8))
    whole = config_from_hf(HF)
    assert whole.experts_held == 0 and whole.num_held_experts == 16


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize(
    "hf", [HF, share(0, 2)], ids=["all-experts", "share-of-2"]
)
@pytest.mark.parametrize("decode", ["xla", "kernel_interpret"])
def test_prefill_then_decode_through_the_latent_cache_against_the_reference(
    hf, int8, decode
):
    """The served order: a prefill from position 0 (decompressed), then
    five decode steps over the latent cache (absorbed), two slots live,
    on logits, against the reference's one full forward."""
    cfg, params = model(hf, int8)
    N = 5
    seqs = [tokens(T + N, seed=s) for s in (1, 2)]
    want = list(range(T - 1, T + N))
    expect = [
        np.asarray(ref.forward(params, hf, s.tolist(), want, block=T + N))
        for s in seqs
    ]
    toks = jnp.stack(seqs)
    pos = jnp.broadcast_to(jnp.arange(T + N, dtype=jnp.int32), toks.shape)
    scratch = KVCache.create(cfg, 2, T)
    logits, scratch = forward(params, cfg, toks[:, :T], pos[:, :T], scratch)
    got = [[np.asarray(logits[b, -1])] for b in range(2)]
    cache = KVCache.create(cfg, 2, 64)
    cache = KVCache(
        k=cache.k.at[:, :, :T].set(scratch.k),
        v=cache.v.at[:, :, :T].set(scratch.v),
    )
    for i in range(T, T + N):
        logits, cache = forward(
            params, cfg, toks[:, i:i + 1], pos[:, i:i + 1], cache,
            decode_attn_impl=decode,
        )
        for b in range(2):
            got[b].append(np.asarray(logits[b, 0]))
    for b in range(2):
        np.testing.assert_allclose(
            np.stack(got[b]), expect[b], atol=2e-4, rtol=2e-4
        )


def test_a_verify_step_and_a_continuation_attend_absorbed_like_the_reference():
    cfg, params = model(HF)
    seq = tokens(T + 8)
    expect = np.asarray(
        ref.forward(params, HF, seq.tolist(), list(range(T + 8)), block=T + 8)
    )
    toks, pos = seq[None], jnp.arange(T + 8, dtype=jnp.int32)[None]
    cache = KVCache.create(cfg, 1, 64)
    first, cache = forward(params, cfg, toks[:, :T], pos[:, :T], cache)
    more, cache = forward(params, cfg, toks[:, T:], pos[:, T:], cache)
    got = np.concatenate([np.asarray(first[0]), np.asarray(more[0])])
    np.testing.assert_allclose(got, expect, atol=2e-4, rtol=2e-4)


def test_the_cache_holds_the_latent_and_nothing_wider():
    cfg = config_from_hf(HF)
    cache = KVCache.create(cfg, 2, 64)
    assert cache.k.shape == (3, 2, 64, 1, 32)
    assert cache.v.shape == (3, 2, 64, 1, 8)
    per_token_layer = (cache.k.nbytes + cache.v.nbytes) // (3 * 2 * 64)
    assert per_token_layer == (32 + 8) * 2
    assert cfg.kv_cache_bytes_per_token() == 3 * (32 + 8) * 2
    # at the published widths: 512 + 64 values of bf16
    published = config_from_hf({
        **HF, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
        "qk_nope_head_dim": 128, "v_head_dim": 128,
    })
    assert published.kv_cache_bytes_per_token() == 3 * 1152


def route_of(cfg, h, router, bias):
    return _route(h[None], router, cfg, bias)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_selection_against_the_written_out_one(seed):
    cfg, params = model(HF)
    h = jax.random.normal(jax.random.key(seed), (64, 64))
    router, bias = (params["layers"][k][0] for k in ("router", "router_bias"))
    idx, w = route_of(cfg, h, router, bias)
    chosen, weights = ref.route(h, router, bias, HF)
    np.testing.assert_array_equal(np.asarray(idx[0]), np.asarray(chosen))
    np.testing.assert_allclose(
        np.asarray(w[0]), np.asarray(weights), rtol=1e-6
    )
    # never an expert outside the token's two kept groups
    groups = np.asarray(idx[0]) // 4
    assert all(len(set(row)) <= 2 for row in groups)
    # and not what plain top-k picks, for some token
    plain, _ = ref.route(h, router, bias, HF, fault="plain_topk")
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(chosen))).any()


def test_ties_go_to_the_lower_index_among_groups_and_among_experts():
    cfg, _ = model(HF)
    # a router of zeros: every score is sigmoid(0), every group ties
    h = jnp.ones((3, 64))
    router, bias = jnp.zeros((64, 16)), jnp.zeros((16,))
    idx, w = route_of(cfg, h, router, bias)
    chosen, weights = ref.route(h, router, bias, HF)
    np.testing.assert_array_equal(np.asarray(idx[0]), np.asarray(chosen))
    np.testing.assert_array_equal(np.asarray(chosen[0]), [0, 1, 2, 3])
    np.testing.assert_allclose(np.asarray(w[0]), 0.25)
    # two experts tie inside a kept group, two groups tie for second
    bias = jnp.zeros((16,)).at[jnp.array([5, 6])].set(0.5)
    idx, _ = route_of(cfg, h, router, bias)
    chosen, _ = ref.route(h, router, bias, HF)
    np.testing.assert_array_equal(np.asarray(idx[0]), np.asarray(chosen))
    np.testing.assert_array_equal(np.asarray(chosen[0]), [5, 6, 0, 1])


def layer_mlp(cfg, params, h, dispatch="dense"):
    lp = {k: v[0] for k, v in params["layers"].items()}
    return _moe_mlp(
        h[None], lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"],
        cfg, router_bias=lp["router_bias"],
        shared=(lp["ws_gate"], lp["ws_up"], lp["ws_down"], None),
        dispatch=dispatch,
    )[0]


def test_the_shares_add_up_to_the_uncut_layer():
    """Every chip's routed part, with what all chips compute alike (the
    shared expert) counted once, is the uncut reference layer."""
    cfg, params = model(HF)
    h = jax.random.normal(jax.random.key(3), (40, 64))
    lw = params["layers"]
    whole, *_ = ref.moe(h, lw, (0,), HF, capacity=40, fault="")
    shared = ref._swiglu(
        h, lw["ws_gate"][0], lw["ws_up"][0], lw["ws_down"][0]
    )
    routed = jnp.zeros_like(h)
    for first in range(0, 16, 4):
        hf = share(first, 4)
        part_cfg = config_from_hf(hf)
        part = {
            **params,
            "layers": {
                k: v[:, first:first + 4] if k.startswith("we_") else v
                for k, v in lw.items()
            },
        }
        ours = layer_mlp(
            dataclasses.replace(part_cfg, dtype="float32"), part, h
        )
        theirs, *_ = ref.moe(h, part["layers"], (0,), hf, 40, "")
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(theirs), atol=1e-5, rtol=1e-5
        )
        routed = routed + (ours - shared)
    np.testing.assert_allclose(
        np.asarray(routed + shared), np.asarray(whole), atol=2e-5, rtol=1e-5
    )


@pytest.mark.parametrize(
    "first,held,rows", [(0, 4, 96), (4, 4, 96), (8, 2, 640)],
    ids=["first-4", "second-4", "2-of-16-several-rounds"],
)
def test_grouped_dispatch_under_a_share_is_the_dense_one(first, held, rows):
    """Pairs on absent experts dropped, the held ones in rounds of
    ``held_capacity``: the same result as the one-hot over the held ids,
    also where the routing needs more than one round."""
    cfg, params = model(share(first, held))   # weights for the held only
    assert params["layers"]["we_gate"].shape[1] == held
    if rows == 640:
        # a correction bias that sends nearly every token to the two held
        bias = params["layers"]["router_bias"].at[:, first:first + held].add(2.0)
        params = {**params, "layers": {
            **params["layers"], "router_bias": bias
        }}
    h = jax.random.normal(jax.random.key(4), (rows, 64))
    dense = layer_mlp(cfg, params, h)
    grouped = layer_mlp(cfg, params, h, "grouped_interpret")
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(dense), atol=1e-5, rtol=1e-5
    )
    if rows == 640:
        # 640 x 4 pairs, 2 of 16 held: a round takes 640; the bias and
        # the groups send these two experts more than that
        assert held_capacity(rows * 4, cfg) == 640
        idx, _ = route_of(
            cfg, h, params["layers"]["router"][0],
            params["layers"]["router_bias"][0],
        )
        assert int(jnp.sum((idx >= first) & (idx < first + held))) > 640


def test_the_fill_rule_counts_an_expert_s_share_of_all_pairs():
    cfg = config_from_hf(share(0, 2))
    # 16 experts, 4 a token: 64 rows give 16 pairs an expert
    assert moe_dispatch(64, cfg, "tpu", None) == "grouped"
    assert moe_dispatch(63, cfg, "tpu", None) == "dense"
    assert moe_dispatch(4096, cfg, "cpu", None) == "dense"


def test_held_pairs_are_counted_over_the_layers_with_experts():
    cfg, params = model(share(0, 4))
    toks = tokens(T)[None]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    _, _, held = forward(params, cfg, toks, pos, count_held_pairs=True)
    # two layers with experts, T x 4 pairs each
    assert 0 < int(held) < 2 * T * 4


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_moves_the_reference_further_than_rounding_does(fault):
    """What the benchmark's tolerance must catch is visible at this size
    too: a reference with the fault differs from the sound one by far
    more than the engine does."""
    _, params = model(HF, int8=True)
    seq = tokens(T).tolist()
    sound = ref.forward(params, HF, seq, [T - 1])
    wrong = ref.forward(params, HF, seq, [T - 1], fault=fault)
    assert float(jnp.max(jnp.abs(wrong - sound))) > 5e-3


@pytest.mark.parametrize("hf", [HF, share(4, 4)], ids=["all-experts", "share-of-4"])
def test_routing_out_gives_the_choices_the_program_made_and_moves_nothing(hf):
    """``forward(routing_out=True)``: the same logits and cache, and of
    every layer with experts the chosen experts and the router's logits,
    from which the reference's written-out selection takes the same
    sets."""
    cfg, params = model(hf)
    toks, pos = tokens(T)[None], jnp.arange(T, dtype=jnp.int32)[None]
    plain, cache = forward(params, cfg, toks, pos, KVCache.create(cfg, 1, T))
    again, cache2, (chosen, logits) = forward(
        params, cfg, toks, pos, KVCache.create(cfg, 1, T), routing_out=True
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(again))
    np.testing.assert_array_equal(np.asarray(cache.k), np.asarray(cache2.k))
    assert chosen.shape == (2, 1, T, 4) and logits.shape == (2, 1, T, 16)
    bias = params["layers"]["router_bias"]
    for layer in range(2):
        mine = ref.select(jax.nn.sigmoid(logits[layer, 0]), bias[layer], hf)
        np.testing.assert_array_equal(
            np.sort(np.asarray(mine), -1), np.sort(np.asarray(chosen[layer, 0]), -1)
        )
    # with the count of held pairs before it, as the prefill program asks
    *_, held, routing = forward(
        params, cfg, toks, pos, KVCache.create(cfg, 1, T),
        count_held_pairs=True, routing_out=True,
    )
    assert held.shape == () and routing[0].shape == chosen.shape


def test_the_reference_follows_a_program_s_routing_and_says_where_it_would_not():
    """``forward_following``: with the reference's own choices it is
    ``forward``; with another program's choices the logits are that
    routing's, and ``differs`` counts the tokens a layer whose set the
    selection rule would not have made from that program's scores."""
    hf = share(4, 4)
    cfg, params = model(hf)
    seq = tokens(T)
    toks, pos = seq[None], jnp.arange(T, dtype=jnp.int32)[None]
    _, _, (chosen, logits) = forward(
        params, cfg, toks, pos, KVCache.create(cfg, 1, T), routing_out=True
    )
    want = [T - 2, T - 1]
    own = np.asarray(ref.forward(params, hf, seq.tolist(), want))
    got, agreement = ref.forward_following(
        params, hf, seq.tolist(), want, (chosen[:, 0], logits[:, 0])
    )
    np.testing.assert_allclose(np.asarray(got), own, atol=2e-4, rtol=2e-4)
    assert agreement["differs"] == [0, 0]
    assert max(agreement["score_err"]) < 1e-4
    # a program that takes plain top-k: its choices are followed (the
    # logits move), and every token outside the kept groups is counted
    _, _, (plain, _) = forward(
        params, dataclasses.replace(cfg, n_group=1, topk_group=1), toks, pos,
        KVCache.create(cfg, 1, T), routing_out=True,
    )
    moved, agreement = ref.forward_following(
        params, hf, seq.tolist(), want, (plain[:, 0], logits[:, 0])
    )
    other_sets = int(np.sum(np.any(
        np.sort(np.asarray(plain), -1) != np.sort(np.asarray(chosen), -1), -1
    )[0]))
    assert agreement["differs"][0] == other_sets > 0
    assert np.max(np.abs(np.asarray(moved) - own)) > 1e-3
    # the fault that stands for it: the reference selects without groups
    _, agreement = ref.forward_following(
        params, hf, seq.tolist(), want, (chosen[:, 0], logits[:, 0]),
        fault="plain_topk",
    )
    assert agreement["differs"][0] == other_sets


def test_float8_activations_read_further_from_the_engine_than_float32():
    cfg, params = model(HF, int8=True)
    seq = tokens(T)
    want = list(range(T))
    own = np.asarray(ref.forward(params, HF, seq.tolist(), want))
    low = np.asarray(ref.forward(params, HF, seq.tolist(), want, fault="fp8_activations"))
    bf16 = np.asarray(ref.forward(params, HF, seq.tolist(), want, fault="bf16_softmax"))
    assert np.max(np.abs(low - own)) > 10 * np.max(np.abs(bf16 - own)) > 0


@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
def test_wq_b_s_finished_product_moves_nothing_past_rounding(
    with_and_without_the_barrier, int8
):
    """``wq_b``'s product meets the fold a GQA layer's ``wq`` met, and a
    decode step finishes it behind the same barrier
    (``transformer.finish_products``): logits and the latent cache are
    the program's without it to float32's rounding (a compiler is free
    to associate the absorbed query's two products the other way when
    nothing stands between them, and the CPU's does: 6e-6 at most
    here, where the GQA layers' tests are bit for bit)."""
    cfg, params = model(HF, int8)
    toks = tokens(2).reshape(2, 1)
    pos = jnp.asarray([[7], [3]], jnp.int32)
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, 2, 16))
    k, v = (
        jax.random.normal(jax.random.key(i), s.shape).astype(s.dtype)
        for i, s in ((7, shapes.k), (8, shapes.v))
    )

    def program():
        def step(k, v):
            logits, cache = forward(params, cfg, toks, pos, KVCache(k=k, v=v))
            return logits, cache.k, cache.v

        return step

    # one in each stack's scan: the leading dense layer's, the others'
    got, want = with_and_without_the_barrier(program, k, v, barriers=2)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-5)
    assert not np.array_equal(np.asarray(got[1]), np.asarray(k))
