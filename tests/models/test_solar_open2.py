"""Solar-Open2 (KDA linear-attention layers with one decay a key channel
beside gated attention without positions, routed experts under a share
and one shared expert in every layer): the engine's model code against
the plain float32 reference (``perfbench/reference/solar_open2.py``) at a
small size (2 periods, 4 heads of 16, 16 experts of which 8 are held),
and what ``ModelConfig`` says of the catalog's row."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.config import FAMILIES, config_from_hf, load_hf_config
from gpustack_tpu.models.quant import QuantW, quantize_params
from gpustack_tpu.models.transformer import KVCache, forward, init_params
from gpustack_tpu.ops.delta_rule import state_heads
from perfbench.reference import solar_open2 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "solar-open2-250b-int8-ep8-l12"
HF = {
    "architectures": ["SolarOpen2ForCausalLM"], "model_type": "solar_open2",
    "partial_rotary_factor": 1,
    "linear_attn_config": {
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
        "num_kv_heads": None,
    },
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 264,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 16,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
}
# one chip's share: 8 of the 16 experts, from id 4 on
HF_SHARE = {
    **HF, "n_routed_experts": 8, "experts_held": {"of": 16, "first": 4},
}
T = 21
PERIOD = (
    "full_attention", "linear_attention", "linear_attention",
    "linear_attention",
)


def model(hf=HF_SHARE, int8=False):
    """Float32 activations either way (the CPU's bf16 products accumulate
    in bf16: no model's rounding); ``int8``: the matrices quantized."""
    cfg = dataclasses.replace(
        config_from_hf(hf, "tiny-solar-open2"), dtype="float32"
    )
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    # gains that are not ones, so that a norm in the wrong place shows
    for stack, name in (
        ("layers", "attn_norm"), ("layers", "mlp_norm"),
        ("delta_layers", "o_norm"),
    ):
        w = params[stack][name]
        params[stack][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.key(len(name)), w.shape, w.dtype
        )
    return cfg, quantize_params(params) if int8 else params


def tokens(n=T):
    return jax.random.randint(jax.random.key(1), (1, n), 0, HF["vocab_size"])


def test_the_config_reads_the_kinds_the_share_and_the_three_kda_fields():
    cfg = config_from_hf(HF_SHARE)
    assert "SolarOpen2" in FAMILIES
    assert cfg.layer_types == PERIOD * 2 and cfg.mixer_period == PERIOD
    assert (cfg.num_linear_layers, cfg.num_kv_layers) == (6, 2)
    assert cfg.state_mixer == "kda" and not cfg.rope
    assert cfg.linear_decay_a_channel and cfg.linear_low_rank == 16
    assert cfg.linear_gate_act == "sigmoid" and cfg.attn_output_gate
    assert cfg.linear_allow_neg_eigval
    assert cfg.is_moe and cfg.moe_scoring == "sigmoid"
    assert (cfg.num_experts, cfg.experts_held, cfg.first_held_expert) == (
        16, 8, 4
    )
    assert cfg.shared_expert_intermediate_size == 32
    assert cfg.num_moe_layers == 8
    # the state a slot: [Dk, H * Dv] float32 and 3 rows of q | k | v
    assert cfg.state_shapes == (6, (16, 64), (3 * 192,))
    assert cfg.state_bytes_per_slot(16) == 6 * (16 * 64 * 4 + 3 * 192 * 2)
    assert cfg.beside_rows.keeps == "has linear-attention layers"
    # a file with the read of one name and no architecture is the family's
    by_type = config_from_hf(
        {k: v for k, v in HF_SHARE.items() if k != "architectures"}
    )
    assert by_type == cfg
    params = init_params(cfg, jax.random.key(0))
    assert cfg.param_count() == sum(
        x.size for x in jax.tree.leaves(params)
    )
    stack = params["delta_layers"]
    assert stack["dt_bias"].shape == (6, 64)        # a key channel
    assert stack["A_log"].shape == (6, 4)           # a head
    assert stack["wf_a"].shape == (6, 64, 16)
    assert stack["wf_b"].shape == (6, 16, 64)
    assert stack["wg_a"].shape == (6, 64, 16)
    assert stack["wg_b"].shape == (6, 16, 64)
    assert "wg" not in stack and "wa" not in stack
    assert params["attn_layers"]["wg"].shape == (2, 64, 64)
    assert params["layers"]["we_gate"].shape == (8, 8, 64, 32)
    assert params["layers"]["router"].shape == (8, 64, 16)


@pytest.mark.parametrize(
    "change,names",
    [
        ({"kda_use_full_proj": True}, "kda_use_full_proj"),
        ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"attention_bias": True}, "attention_bias"),
        ({"use_rope": True}, "use_rope"),
        ({"linear_attn_config": {
            **HF["linear_attn_config"], "num_kv_heads": 2}}, "num_kv_heads"),
        ({"gqa_layers": [0, 4, 8]}, "gqa_layers"),
    ],
    ids=["full_projections", "a_dense_prefix", "another_activation",
         "a_bias", "rotary_positions", "fewer_key_heads",
         "a_layer_past_the_depth"],
)
def test_what_the_family_s_reader_does_not_serve_is_refused(change, names):
    with pytest.raises(ValueError, match=names):
        config_from_hf({**HF, **change})


def test_validate_lets_experts_stand_beside_a_mixer_by_kind_and_no_more():
    cfg = config_from_hf(HF_SHARE)
    with pytest.raises(AssertionError, match="latent attention"):
        dataclasses.replace(cfg, kv_lora_rank=8).validate()
    with pytest.raises(AssertionError, match="a key head a value head"):
        dataclasses.replace(cfg, linear_num_key_heads=2).validate()
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, first_k_dense=1).validate()
    with pytest.raises(AssertionError, match="output gate"):
        dataclasses.replace(
            config_from_hf({
                "hidden_size": 64, "num_attention_heads": 4,
                "num_hidden_layers": 2, "vocab_size": 64,
            }), attn_output_gate=True,
        ).validate()


def catalog_row():
    with open(CATALOG) as f:
        return next(
            row for row in map(json.loads, f)
            if row["name"] == "Solar-Open2-250B"
        )


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalog_s_row_counts_250_3_b_and_the_cut_9_52_b():
    """``param_count`` of the catalog's config and of the benchmark's
    file = the sums written down from their widths; a slot's state."""
    hf = {**catalog_row()["config"], "architectures": HF["architectures"]}
    whole = config_from_hf(hf)
    d, v, E, fm = 4096, 196608, 320, 1280
    kda = (
        4 * d * 8192                       # wq, wk, wv, wo
        + 2 * (d * 128 + 128 * 8192)       # the two bottlenecks
        + d * 64 + 64 + 8192               # wb; A_log, dt_bias
        + 4 * 3 * 8192 + 128               # convolutions, the norm's gain
    )
    attention = 2 * d * 8192 + 2 * d * 1024 + d * 8192     # the gate
    experts = lambda held: (  # noqa: E731
        d * E + E + held * 3 * d * fm + 3 * d * fm
    )
    total = (
        36 * kda + 12 * attention + 48 * (experts(E) + 2 * d)
        + 2 * v * d + d
    )
    assert whole.param_count() == total
    assert round(total / 1e9, 1) == 250.3
    assert round(kda / 1e6, 1) == 137.7
    assert round(attention / 1e6, 1) == 109.1
    cut = load_hf_config(os.path.join(ROOT, "perfbench", "configs", NAME))
    held = (
        9 * kda + 3 * attention + 12 * (experts(40) + 2 * d)
        + 2 * 24576 * d + d
    )
    assert cut.param_count() == held
    assert round(held / 1e9, 2) == 9.52
    assert (cut.num_experts, cut.num_held_experts) == (320, 40)
    assert cut.layer_types == PERIOD * 3
    # 9 states of [128, 64 * 128] float32 and 3 conv rows of 24,576
    assert cut.state_shapes == (9, (128, 8192), (3 * 24576,))
    assert round(cut.state_bytes_per_slot(16) / 1e6, 2) == 39.08
    assert cut.kv_cache_bytes_per_token(16) == 3 * 2 * 8 * 128 * 2 == 12288
    shapes = jax.eval_shape(lambda: KVCache.create(cut, 32, 2560))
    assert shapes.ssm.shape == (9, 32, 128, 8192)
    assert shapes.k.shape == shapes.v.shape == (3, 32, 2560, 8, 128)
    held_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in (shapes.ssm, shapes.conv, shapes.k, shapes.v)
    )
    assert round(held_bytes / 1e9, 2) == 2.26
    assert 32 * (
        cut.beside_bytes_per_slot(2560, 16)
        + 2560 * cut.kv_cache_bytes_per_token(16)
    ) == held_bytes


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_benchmark_s_file_is_the_catalog_s_row_but_for_what_it_lists():
    with open(os.path.join(
        ROOT, "perfbench", "configs", NAME, "config.json"
    )) as f:
        ours = json.load(f)
    with open(os.path.join(
        ROOT, "perfbench", "configs", NAME, "deployment.json"
    )) as f:
        deployment = json.load(f)
    published = catalog_row()["config"]
    differs = {k for k, v in published.items() if ours.get(k) != v}
    assert differs == set(deployment["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size",
    }
    assert set(ours) - set(published) == {"architectures", "experts_held"}
    assert ours["experts_held"] == {"of": 320, "first": 0}
    for key in deployment["reduced"]:
        assert deployment["published"][key] == published[key], key
    # no width touched
    for key in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "moe_intermediate_size",
        "num_experts_per_tok", "linear_attn_config", "intermediate_size",
    ):
        assert ours[key] == published[key], key


def test_the_placement_claim_at_the_published_widths():
    """The scheduler's claim for the deployment: 9.52 B parameters at a
    byte, a slot 39.1 MB of state whatever its context and 12 KB a
    position; one 16 GB chip, and never spread over more."""
    from gpustack_tpu.scheduler.calculator import (
        chips_for_claim,
        evaluate_model,
    )
    from gpustack_tpu.schemas.models import Model

    def claim(**spec):
        return evaluate_model(Model(
            name="m", quantization="int8",
            local_path=os.path.join(ROOT, "perfbench", "configs", NAME),
            **spec,
        ))

    ev = claim(max_seq_len=2560, max_slots=32)
    assert ev.config.state_mixer == "kda" and ev.config.is_moe
    assert 9.5e9 < ev.weight_bytes < 9.6e9
    state = 9 * (128 * 8192 * 4 + 3 * 24576 * 2)
    rows = 3 * 2 * 8 * 128 * 2 * 2560
    assert ev.kv_cache_bytes == 32 * (state + rows)
    assert round(state / 1e6, 1) == 39.1 and round(rows / 1e6, 1) == 31.5
    got = chips_for_claim(ev, hbm_per_chip=16 * 2**30, max_chips=8)
    assert got is not None and got.chips == 1
    more = claim(max_seq_len=2560, max_slots=128)
    assert more.total_bytes > 16 * 2**30
    assert chips_for_claim(more, hbm_per_chip=16 * 2**30, max_chips=8) is None


def test_the_state_s_layers_draw_a_decay_a_channel():
    _, params = model()
    stack = params["delta_layers"]
    A = np.exp(np.asarray(stack["A_log"]))
    assert (A > 0).all() and (A <= 16).all()
    dt = np.log1p(np.exp(np.asarray(stack["dt_bias"])))
    assert dt.shape == (6, 4 * 16)
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    assert len(np.unique(dt[0])) > 32       # one a channel, not one a head
    assert stack["conv_w"].dtype == stack["A_log"].dtype == jnp.float32


@pytest.mark.parametrize("hf", [HF, HF_SHARE], ids=["whole", "a_share"])
def test_the_full_forward_is_the_reference_s(hf):
    cfg, params = model(hf)
    toks = tokens()
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    logits, _ = jax.jit(lambda p, t, q: forward(p, cfg, t, q))(
        params, toks, pos
    )
    want, _ = ref.forward(params, hf, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_fault_the_reference_can_make_moves_its_logits(fault):
    """Each is a way the engine's code could be wrong; the sound
    reference is the engine's (above), so a fault that moved nothing
    would be one the comparison cannot see."""
    _, params = model()
    toks = tokens()[0].tolist()
    sound, _ = ref.forward(params, HF_SHARE, toks, list(range(T)))
    pads = (13, 11) if fault == "state_after_bucket" else None
    got, readings = ref.forward(
        params, HF_SHARE, toks, list(range(T)), fault=fault, pads=pads,
        states=jnp.ones((6, 4, 16, 16)),
    )
    if fault == "bf16_state":
        assert readings["state_narrow"] == 1.0
    assert float(jnp.max(jnp.abs(got - sound))) > 0.02


def test_the_other_reading_of_each_assumption_is_an_argument():
    _, params = model()
    toks = tokens()[0].tolist()
    sound, _ = ref.forward(params, HF_SHARE, toks, [T - 1])
    for other in (
        dict(rotary=True), dict(scoring="softmax"), dict(held=(0, 8)),
    ):
        got, _ = ref.forward(params, HF_SHARE, toks, [T - 1], **other)
        assert float(jnp.max(jnp.abs(got - sound))) > 1e-3, other
    # one gate a head: a W_gate of Hq columns, broadcast over the head
    narrow = {**params, "attn_layers": {
        **params["attn_layers"], "wg": params["attn_layers"]["wg"][..., :4],
    }}
    got, _ = ref.forward(narrow, HF_SHARE, toks, [T - 1])
    assert float(jnp.max(jnp.abs(got - sound))) > 1e-3


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share tied to the model: one layer's routed experts over all
    16 ids (the uncut reference), against the sum of what each of 8
    shares of 2 gives for its own experts, the shared expert counted
    once; and the engine's layer under one share is that share's part."""
    cfg, params = model(HF)
    every = params["layers"]
    h = jax.random.normal(jax.random.key(5), (T, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.experts(h, every, (3,), ref._Frozen(HF), "", (0, 16))
        no_shared = ref._Frozen({**HF, "n_shared_experts": 0})
        shared = whole - ref.experts(h, every, (3,), no_shared, "", (0, 16))[0]
        parts = []
        for first in range(0, 16, 2):
            mine = {
                **every, **{
                    n: every[n][:, first:first + 2]
                    for n in ("we_gate", "we_up", "we_down")
                },
            }
            part, _ = ref.experts(h, mine, (3,), no_shared, "", (first, 2))
            parts.append(part)
            # the engine's own experts under this share
            from gpustack_tpu.models.transformer import _moe_mlp

            share_cfg = dataclasses.replace(
                cfg, experts_held=2, first_held_expert=first
            )
            got = _moe_mlp(
                h[None], mine["router"][3], mine["we_gate"][3],
                mine["we_up"][3], mine["we_down"][3], share_cfg,
                router_bias=mine["router_bias"][3], dispatch="dense",
            )
            np.testing.assert_allclose(got[0], part, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        sum(parts) + shared, whole, rtol=2e-4, atol=2e-5
    )
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    assert all(float(jnp.max(jnp.abs(p))) > 1e-4 for p in parts)


def test_the_int8_tree_is_read_alike_by_the_program_and_the_reference():
    cfg, params = model(int8=True)
    for stack, names in (
        ("layers", ("we_gate", "we_up", "we_down", "ws_gate", "ws_up",
                    "ws_down")),
        ("attn_layers", ("wq", "wk", "wv", "wo", "wg")),
        ("delta_layers", ("wq", "wk", "wv", "wo")),
    ):
        for name in names:
            assert isinstance(params[stack][name], QuantW), (stack, name)
    assert isinstance(params["embed"], QuantW)
    assert isinstance(params["lm_head"], QuantW)
    # the four bottleneck matrices, Wb, the router and the norms bf16
    # (float32 here), A_log, dt_bias and the convolutions float32
    for name in ("wf_a", "wf_b", "wg_a", "wg_b", "wb", "conv_w", "A_log",
                 "dt_bias", "o_norm"):
        assert not isinstance(params["delta_layers"][name], QuantW), name
    assert not isinstance(params["layers"]["router"], QuantW)
    toks = tokens()
    logits, _ = forward(
        params, cfg, toks, jnp.arange(T, dtype=jnp.int32)[None]
    )
    want, _ = ref.forward(params, HF_SHARE, toks[0].tolist(), list(range(T)))
    np.testing.assert_allclose(logits[0], want, rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("update", ["xla", "kernel_interpret"])
@pytest.mark.parametrize(
    "n,bucket",
    [(13, 16), (64, 64), (70, 128)],
    ids=["13_of_16", "a_whole_chunk", "past_a_chunk"],
)
def test_a_padded_prefill_then_decode_through_the_state(n, bucket, update):
    """Logits, not tokens: the prefill's one kept row (the grouped
    experts), then every decode step with the slot between two dead ones
    (the touched experts, live rows only), against the reference's full
    forward; the state a head at a time after the last step; and the
    routing the programs hand out is what the reference follows."""
    cfg, params = model()
    steps = 4
    toks = tokens(n + steps)
    want, _ = ref.forward(
        params, HF_SHARE, toks[0].tolist(), list(range(n - 1, n + steps))
    )
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :n].set(toks[:, :n])
    got, cache, held, (chosen, logits) = forward(
        params, cfg, padded, jnp.arange(bucket, dtype=jnp.int32)[None],
        KVCache.create(cfg, 1, bucket), true_len=jnp.array([n]),
        logits_at=jnp.array([n - 1]), moe_dispatch_impl="grouped_interpret",
        count_held_pairs=True, routing_out=True,
    )
    np.testing.assert_allclose(got[0, 0], want[0], rtol=5e-4, atol=5e-4)
    assert chosen.shape == (8, 1, bucket, 4)
    assert logits.shape == (8, 1, bucket, 16)
    at = np.asarray(chosen) - 4
    assert int(held) == int(((at >= 0) & (at < 8)).sum())
    routes = [(chosen[:, 0, :n], logits[:, 0, :n])]
    state = KVCache.create(cfg, 3, 160).with_slot(
        1, cache.k[:, 0], cache.v[:, 0], cache.slot_share()
    )
    # a dead slot's state is left as it is
    state = dataclasses.replace(state, ssm=state.ssm.at[:, 2].set(7.0))
    live = jnp.array([False, True, False])
    for i in range(steps):
        tok = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(toks[0, n + i])
        pos = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(n + i)
        got, state, read, (chosen, logits) = forward(
            params, cfg, tok, pos, state, ssm_impl=update, live=live,
            moe_dispatch_impl="touched_interpret", count_experts_read=True,
            routing_out=True,
        )
        np.testing.assert_allclose(
            got[1, 0], want[i + 1], rtol=5e-4, atol=5e-4
        )
        # the experts-read counter counts in the period scan: one live
        # row reaches at most 4 of the 8 held experts a layer, 8 layers
        assert 0 <= int(read) <= 4 * 8
        routes.append((chosen[:, 1], logits[:, 1]))
    if update == "kernel_interpret":
        np.testing.assert_array_equal(state.ssm[:, 2], 7.0)
    routing = tuple(jnp.concatenate(r, axis=1) for r in zip(*routes))
    again, readings = ref.forward(
        params, HF_SHARE, toks[0].tolist(), list(range(n - 1, n + steps)),
        routing=routing, states=state_heads(state.ssm[:, 1], 4),
    )
    np.testing.assert_allclose(again, want, rtol=1e-5, atol=1e-5)
    assert readings["score_err"] < 1e-4
    assert readings["state_err"] < 1e-4
    assert readings["state_narrow"] < 0.01


def test_the_runner_s_prefill_then_decode_through_the_cache():
    """The engine's ``ModelRunner`` (its own prefill, insert and decode
    programs over its cache, the int8 tree) against the reference's full
    forward. Float32 activations (the CPU's bf16 products accumulate in
    bf16, no chip's rounding, and a router that takes 4 of 16 turns on
    it); tolerance 5e-3 nats: the int8 tree is read alike by both, what
    is left is the order of float32 sums through 8 layers."""
    from gpustack_tpu.engine.runner import ModelRunner

    cfg, params = model(int8=True)
    runner = ModelRunner(cfg, params, max_slots=2, max_seq_len=64)
    assert runner.ssm_scan == "chunked_einsum"
    assert runner.decode_moe_dispatch is not None
    n, steps = 13, 3
    bucket = min(b for b in runner.prefill_buckets if b >= n)
    prompt = tokens(n)[0].tolist()
    last, k, v, mixer = runner.prefill(prompt + [0] * (bucket - n), n)
    first = int(np.argmax(np.asarray(last, np.float32)))
    state = runner.insert(
        runner.new_state(), k, v, 0, n, first, 0.0, 0, 1.0, mixer=mixer
    )
    seq, tops = prompt + [first], []
    key = jax.random.key_data(jax.random.key(0))
    for _ in range(steps):
        state, (sampled, _lp, top_ids, top_lps, *_) = runner.decode_step(
            state, key
        )
        tops.append((np.asarray(top_ids[0]), np.asarray(top_lps[0])))
        seq.append(int(sampled[0]))
    want, _ = ref.forward(
        runner.params, HF_SHARE, seq[:n + steps],
        list(range(n - 1, n + steps)),
    )
    logp = np.asarray(jax.nn.log_softmax(want, axis=-1))
    got = np.asarray(jax.nn.log_softmax(jnp.asarray(last, jnp.float32)))
    assert np.max(np.abs(got - logp[0])) < 5e-3
    for i, (ids, lps) in enumerate(tops):
        assert np.max(np.abs(lps - logp[i + 1][ids])) < 5e-3


def test_a_state_is_not_sharded():
    cfg, params = model()
    with pytest.raises(ValueError, match="recurrent state is not sharded"):
        forward(
            params, cfg, tokens(8), jnp.arange(8, dtype=jnp.int32)[None],
            KVCache.create(cfg, 1, 8), attn_impl="ring",
        )
