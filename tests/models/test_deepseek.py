"""DeepSeek-V2/V3 family: MLA attention + DeepSeek-MoE HF parity.

The reference's Performance Lab headliners are DeepSeek models; this
engine serves them with MLA over a latent cache (decompressed inside a
prefill's program, absorbed over cached rows — models/transformer.py)
and DeepSeek MoE (shared experts, routed scaling, sigmoid scoring,
group-limited selection, first-k-dense prefix stack). Bit-parity against
transformers on tiny random checkpoints, same doctrine as the gemma/qwen
tests.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models import forward


def _logits_ours(model_dir, tokens):
    from gpustack_tpu.engine.weights import load_hf_checkpoint
    from gpustack_tpu.models.config import load_hf_config

    cfg = load_hf_config(model_dir)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = load_hf_checkpoint(cfg, model_dir)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16 else x,
        params,
    )
    ours, _ = forward(
        params,
        cfg,
        jnp.asarray(tokens),
        jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        ),
    )
    return cfg, np.asarray(ours)


TOKENS = np.array([[3, 17, 92, 5, 44, 8, 120, 63]], dtype=np.int32)


@pytest.fixture(scope="module")
def v2_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(0)
    hf_cfg = tfm.DeepseekV2Config(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        moe_intermediate_size=16,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_shared_experts=2,
        n_routed_experts=4,
        routed_scaling_factor=2.0,
        kv_lora_rank=16,
        q_lora_rank=24,
        qk_rope_head_dim=8,
        qk_nope_head_dim=8,
        v_head_dim=12,
        num_experts_per_tok=2,
        first_k_dense_replace=1,
        norm_topk_prob=False,
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attention_dropout=0.0,
        attention_bias=False,
    )
    model = tfm.DeepseekV2ForCausalLM(hf_cfg).eval()
    d = tmp_path_factory.mktemp("dsv2")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def test_deepseek_v2_logits_match_transformers(v2_checkpoint):
    torch = pytest.importorskip("torch")
    model, model_dir = v2_checkpoint
    cfg, ours = _logits_ours(model_dir, TOKENS)

    assert cfg.is_mla and cfg.is_moe
    assert cfg.first_k_dense == 1
    assert cfg.q_lora_rank == 24 and cfg.kv_lora_rank == 16
    assert cfg.head_dim == 16          # qk_nope + qk_rope
    assert cfg.v_head_dim == 12
    assert cfg.routed_scaling_factor == 2.0
    assert cfg.shared_expert_intermediate_size == 32   # 2 × 16
    assert cfg.moe_scoring == "softmax"

    with torch.no_grad():
        ref = model(torch.tensor(TOKENS, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, ref, atol=5e-3, rtol=2e-2)


@pytest.fixture(scope="module")
def v3_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(1)
    hf_cfg = tfm.DeepseekV3Config(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        moe_intermediate_size=16,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_shared_experts=1,
        n_routed_experts=4,
        routed_scaling_factor=1.5,
        kv_lora_rank=16,
        q_lora_rank=None,
        qk_rope_head_dim=8,
        qk_nope_head_dim=8,
        v_head_dim=8,
        num_experts_per_tok=2,
        n_group=1,
        topk_group=1,
        first_k_dense_replace=1,
        norm_topk_prob=True,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        max_position_embeddings=128,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attention_dropout=0.0,
        attention_bias=False,
    )
    model = tfm.DeepseekV3ForCausalLM(hf_cfg).eval()
    # make the correction bias nontrivial so the test catches a missing
    # selection-vs-weight split
    with torch.no_grad():
        for layer in model.model.layers:
            if hasattr(layer.mlp, "gate"):
                layer.mlp.gate.e_score_correction_bias.uniform_(-1, 1)
    d = tmp_path_factory.mktemp("dsv3")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def test_deepseek_v3_logits_match_transformers(v3_checkpoint):
    torch = pytest.importorskip("torch")
    model, model_dir = v3_checkpoint
    cfg, ours = _logits_ours(model_dir, TOKENS)

    assert cfg.is_mla and cfg.moe_scoring == "sigmoid"
    assert cfg.q_lora_rank == 0        # direct q projection

    with torch.no_grad():
        ref = model(torch.tensor(TOKENS, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, ref, atol=5e-3, rtol=2e-2)


@pytest.fixture(scope="module")
def yarn_checkpoint(tmp_path_factory):
    """V3 with the YaRN scaling real DeepSeek checkpoints ship.

    The oracle is DeepseekV3 (not V2): transformers' integrated V2 port
    omits the original code's softmax-scale correction
    (yarn_get_mscale(factor, mscale_all_dim)^2 — modeling_deepseek_v2
    remote code / vLLM deepseek_v2.py), while its V3 port applies it
    (modeling_deepseek_v3 DeepseekV3Attention.__init__). We follow the
    original/vLLM behavior for BOTH families, so V3 is the family where
    an HF parity check is meaningful. mscale != mscale_all_dim so the
    sin/cos attention factor is exercised too."""
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(2)
    hf_cfg = tfm.DeepseekV3Config(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        moe_intermediate_size=16,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_shared_experts=1,
        n_routed_experts=4,
        routed_scaling_factor=1.0,
        kv_lora_rank=16,
        q_lora_rank=None,
        qk_rope_head_dim=8,
        qk_nope_head_dim=8,
        v_head_dim=8,
        num_experts_per_tok=2,
        n_group=1,
        topk_group=1,
        first_k_dense_replace=0,
        norm_topk_prob=False,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
        max_position_embeddings=640,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "yarn",
            "factor": 10.0,
            "beta_fast": 32,
            "beta_slow": 1,
            "mscale": 1.0,
            "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 64,
        },
        tie_word_embeddings=False,
        attention_dropout=0.0,
        attention_bias=False,
    )
    model = tfm.DeepseekV3ForCausalLM(hf_cfg).eval()
    d = tmp_path_factory.mktemp("dsyarn")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def test_deepseek_yarn_rope_matches_transformers(yarn_checkpoint):
    """Positions PAST the original context window: yarn frequency
    blending + the mscale attention factor + the mscale^2 softmax-scale
    correction must all match HF's V3 port."""
    torch = pytest.importorskip("torch")
    model, model_dir = yarn_checkpoint
    # 8 tokens starting deep past original_max_position_embeddings=64
    tokens = np.array([[7, 3, 99, 12, 55, 31, 8, 77]], dtype=np.int32)
    positions = np.arange(200, 208, dtype=np.int64)[None, :]

    with torch.no_grad():
        ref = model(
            torch.tensor(tokens, dtype=torch.long),
            position_ids=torch.tensor(positions),
        ).logits.numpy()

    import dataclasses as _dc

    from gpustack_tpu.engine.weights import load_hf_checkpoint
    from gpustack_tpu.models.config import load_hf_config

    cfg = load_hf_config(model_dir)
    assert (cfg.rope_scaling or {}).get("rope_type") == "yarn"
    cfg = _dc.replace(cfg, dtype="float32")
    params = load_hf_checkpoint(cfg, model_dir)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16 else x,
        params,
    )
    ours, _ = forward(
        params, cfg, jnp.asarray(tokens),
        jnp.asarray(positions, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(ours), ref, atol=5e-3, rtol=2e-2
    )


def test_yarn_mscale_softmax_correction_value():
    """The V2/V3 shipped configs (factor=40, mscale_all_dim=0.707) imply
    a ~1.59x softmax-scale correction; pin the math so a regression back
    to HF-V2's missing-correction behavior is loud."""
    from gpustack_tpu.models.transformer import yarn_get_mscale

    m = yarn_get_mscale(40.0, 0.707)
    np.testing.assert_allclose(m * m, 1.5896, rtol=1e-3)
    # below the original window no correction applies
    assert yarn_get_mscale(0.5, 0.707) == 1.0


@pytest.fixture(scope="module")
def grouped_checkpoint(tmp_path_factory):
    """V3 with group-limited selection as the large checkpoints ship it:
    8 experts in 4 groups, 2 groups kept, 3 experts a token, a correction
    bias that matters."""
    torch = pytest.importorskip("torch")
    tfm = pytest.importorskip("transformers")

    torch.manual_seed(3)
    hf_cfg = tfm.DeepseekV3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, n_shared_experts=1,
        n_routed_experts=8, routed_scaling_factor=2.5, kv_lora_rank=16,
        q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
        v_head_dim=8, num_experts_per_tok=3, n_group=4, topk_group=2,
        first_k_dense_replace=1, norm_topk_prob=True,
        scoring_func="sigmoid", topk_method="noaux_tc",
        max_position_embeddings=128, rope_theta=10000.0,
        tie_word_embeddings=False, attention_dropout=0.0,
        attention_bias=False,
    )
    model = tfm.DeepseekV3ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for layer in model.model.layers:
            if hasattr(layer.mlp, "gate"):
                layer.mlp.gate.e_score_correction_bias.uniform_(-0.3, 0.3)
    d = tmp_path_factory.mktemp("dsgroups")
    model.save_pretrained(d, safe_serialization=True)
    return model, str(d)


def test_group_limited_routing_matches_transformers(grouped_checkpoint):
    """n_group > 1 was refused until PR 35; now the selection is the
    public port's (the sum of a group's two best, the kept groups, the
    top-k within them, the bias on the selection only)."""
    torch = pytest.importorskip("torch")
    model, model_dir = grouped_checkpoint
    cfg, ours = _logits_ours(model_dir, TOKENS)
    assert (cfg.n_group, cfg.topk_group) == (4, 2)
    assert cfg.moe_scoring == "sigmoid" and cfg.q_lora_rank == 24
    with torch.no_grad():
        ref = model(torch.tensor(TOKENS, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, ref, atol=5e-3, rtol=2e-2)


def test_a_position_sharded_cache_refuses_a_latent():
    """The one consumer that cannot carry a latent cache yet says so."""
    from gpustack_tpu.models.config import config_from_hf
    from gpustack_tpu.models.transformer import KVCache, init_params
    from gpustack_tpu.parallel.mesh import MeshPlan, make_mesh

    cfg = config_from_hf({
        "architectures": ["DeepseekV2ForCausalLM"],
        "hidden_size": 32, "num_attention_heads": 4,
        "vocab_size": 64, "num_hidden_layers": 1,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 8,
    })
    params = init_params(cfg, jax.random.key(0))
    toks = jnp.zeros((1, 8), jnp.int32)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="latent"):
        forward(
            params, cfg, toks, pos, KVCache.create(cfg, 1, 8),
            attn_impl="ring", mesh=make_mesh(MeshPlan(sp=2)),
        )


def test_deepseek_engine_greedy_serving(v2_checkpoint):
    """The full serving path (prefill→insert→decode over the latent
    cache) produces the oracle's greedy tokens."""
    _, model_dir = v2_checkpoint

    from gpustack_tpu.engine.engine import GenRequest, LLMEngine
    from gpustack_tpu.engine.weights import load_hf_checkpoint
    from gpustack_tpu.models.config import load_hf_config

    cfg = load_hf_config(model_dir)
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = load_hf_checkpoint(cfg, model_dir)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if x.dtype == jnp.bfloat16 else x,
        params,
    )

    prompt = [5, 17, 42, 9]
    # no-cache oracle
    ids = list(prompt)
    oracle = []
    for _ in range(5):
        toks = jnp.asarray(ids, jnp.int32)[None, :]
        pos = jnp.arange(len(ids), dtype=jnp.int32)[None, :]
        logits, _ = forward(params, cfg, toks, pos)
        nxt = int(jnp.argmax(logits[0, -1]))
        oracle.append(nxt)
        ids.append(nxt)

    engine = LLMEngine(cfg, params, max_slots=2, max_seq_len=64)
    engine.start()
    try:
        req = engine.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=5, temperature=0.0,
                stop_ids=(),
            ),
            timeout=600,
        )
    finally:
        engine.stop()
    assert req.output_ids == oracle[: len(req.output_ids)]
    assert len(req.output_ids) >= 1
