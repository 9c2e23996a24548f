"""Weight loading: HF safetensors checkpoints → stacked functional params.

Covers the LlamaForCausalLM / Qwen2ForCausalLM / MistralForCausalLM /
MixtralForCausalLM tensor naming. Torch stores linear weights as
``[out_features, in_features]``; our functional matmuls contract
``x @ W`` with ``W[in, out]``, so every projection transposes on load.

When no checkpoint directory is given (hermetic tests, synthetic
benchmarks under zero egress) params are randomly initialized from the
config instead.
"""

from __future__ import annotations

import glob
import json
import logging
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gpustack_tpu.models.config import ModelConfig
from gpustack_tpu.models.transformer import init_params

logger = logging.getLogger(__name__)


# MXFP4 e2m1 value table, nibble-indexed (sign bit high): the packing
# the GPT-OSS hub checkpoints use for expert weights (transformers
# integrations/mxfp4 FP4_VALUES)
_FP4_VALUES = (
    0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0,
    -0.0, -0.5, -1.0, -1.5, -2.0, -3.0, -4.0, -6.0,
)


def _mxfp4_dequant(blocks, scales) -> jax.Array:
    """MXFP4 blocks/scales → bf16 weight, matching
    convert_moe_packed_tensors: ``blocks`` uint8 [..., G, B] holds fp4
    PAIRS (low nibble = even element), ``scales`` uint8 e8m0 [..., G]
    biased by 127; output interleaves, applies 2^scale, flattens the
    block axes and swaps the last two dims into the [E, in, out]
    layout the bf16 exports use."""
    import numpy as np

    lut = np.asarray(_FP4_VALUES, np.float32)
    lo = lut[blocks & 0x0F]
    hi = lut[blocks >> 4]
    out = np.empty(
        (*blocks.shape[:-1], blocks.shape[-1] * 2), np.float32
    )
    out[..., 0::2] = lo
    out[..., 1::2] = hi
    out *= np.exp2(
        scales.astype(np.int32) - 127
    )[..., None].astype(np.float32)
    out = out.reshape(*blocks.shape[:-2], -1)      # [E, X, D]
    return jnp.asarray(out.swapaxes(-1, -2)).astype(jnp.bfloat16)


def _to_jnp(t, dtype=jnp.bfloat16) -> jax.Array:
    """torch tensor (possibly bf16) → jnp array."""
    import torch

    if t.dtype == torch.bfloat16:
        return jnp.asarray(t.float().numpy()).astype(jnp.bfloat16)
    return jnp.asarray(t.numpy()).astype(dtype)


def _read_safetensors(model_dir: str) -> Dict[str, Any]:
    """All tensors from a local HF model dir, keyed by checkpoint name."""
    from safetensors import safe_open

    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {model_dir}")
    tensors: Dict[str, Any] = {}
    for f in files:
        with safe_open(f, framework="pt") as st:
            for name in st.keys():
                tensors[name] = st.get_tensor(name)
    return tensors


def _taker(tensors: Dict[str, Any]):
    """take(name, transpose) popping from ``tensors`` — shared by the LM
    and Whisper loaders so dtype/transpose handling can't drift."""

    def take(name: str, transpose: bool = False) -> jax.Array:
        t = tensors.pop(name)
        if transpose:
            t = t.T
        return _to_jnp(t)

    return take


def load_hf_checkpoint(
    cfg: ModelConfig, model_dir: str, quantization: str = ""
) -> Dict[str, Any]:
    """Load *.safetensors from a local HF model dir into our param tree."""
    tensors = _read_safetensors(model_dir)
    return build_lm_params(cfg, tensors, quantization)


def load_gguf_checkpoint(
    cfg: ModelConfig, gguf_path: str, quantization: str = ""
) -> Dict[str, Any]:
    """Load a GGUF checkpoint: dequantize to the HF tensor names
    (engine/gguf.py), then reuse the exact same mapping as safetensors —
    one param-tree builder, two on-disk formats."""
    from gpustack_tpu.engine.gguf import load_gguf_tensors

    tensors = load_gguf_tensors(gguf_path)
    return build_lm_params(cfg, tensors, quantization)


class _QuantizeOnSet(dict):
    """A layer dict that quantizes each stacked weight as it is stored, so
    an int8 load never holds more than the int8 tree plus one bf16 leaf."""

    def __setitem__(self, name: str, w) -> None:
        from gpustack_tpu.models.quant import _CONTRACT_AXES, _quantize_leaf

        if name in _CONTRACT_AXES:
            w = _quantize_leaf(name, w)
        super().__setitem__(name, w)


def build_lm_params(
    cfg: ModelConfig, tensors: Dict[str, Any], quantization: str = ""
) -> Dict[str, Any]:
    """HF-named tensors → the stacked functional param tree; with
    ``quantization="int8"`` the tree ``quantize_params`` would give, each
    leaf quantized as soon as it is stacked.

    DeepSeek checkpoints split into a dense prefix stack
    (``first_k_dense`` layers) + a MoE remainder — forward scans them
    back-to-back (models/transformer.py)."""
    if cfg.layer_kinds is not None:
        return build_hybrid_params(cfg, tensors, quantization)
    if cfg.layer_types is not None:
        raise ValueError(
            f"{cfg.name}: no checkpoint of a stack with layer_types is "
            "read yet (the family's tensor names are not settled here); "
            "a directory without weights is served with seeded ones"
        )
    if cfg.parallel_block:
        raise ValueError(
            f"{cfg.name}: no checkpoint of a cohere2_moe stack is read "
            "yet (its tensors' names are not mapped); it is served with "
            "seeded weights from a directory that holds a config.json "
            "alone"
        )
    L = cfg.num_layers
    take = _taker(tensors)
    kd = cfg.first_k_dense if cfg.is_moe else 0
    int8 = quantization == "int8"

    def build_range(rng, moe: bool) -> Dict[str, Any]:
        def stack(fmt: str, transpose: bool = False) -> jax.Array:
            return jnp.stack(
                [take(fmt.format(i), transpose) for i in rng]
            )

        layers: Dict[str, Any] = _QuantizeOnSet() if int8 else {}
        layers["attn_norm"] = stack("model.layers.{}.input_layernorm.weight")
        if cfg.is_mla:
            # DeepSeek MLA projections; kv_b_proj is split once, here,
            # into its key and value columns (models/transformer.py
            # mla_attention absorbs them apart)
            if cfg.q_lora_rank:
                layers["wq_a"] = stack(
                    "model.layers.{}.self_attn.q_a_proj.weight", True
                )
                layers["q_a_norm"] = stack(
                    "model.layers.{}.self_attn.q_a_layernorm.weight"
                )
                layers["wq_b"] = stack(
                    "model.layers.{}.self_attn.q_b_proj.weight", True
                )
            else:
                layers["wq"] = stack(
                    "model.layers.{}.self_attn.q_proj.weight", True
                )
            layers["wkv_a"] = stack(
                "model.layers.{}.self_attn.kv_a_proj_with_mqa.weight",
                True,
            )
            layers["kv_a_norm"] = stack(
                "model.layers.{}.self_attn.kv_a_layernorm.weight"
            )
            kv_b = stack(
                "model.layers.{}.self_attn.kv_b_proj.weight", True
            ).reshape(
                len(rng), cfg.kv_lora_rank, cfg.num_heads,
                cfg.qk_nope_head_dim + cfg.v_head_dim,
            )
            layers["wk_b"] = kv_b[..., : cfg.qk_nope_head_dim].reshape(
                len(rng), cfg.kv_lora_rank, -1
            )
            layers["wv_b"] = kv_b[..., cfg.qk_nope_head_dim:].reshape(
                len(rng), cfg.kv_lora_rank, -1
            )
            layers["wo"] = stack(
                "model.layers.{}.self_attn.o_proj.weight", True
            )
        else:
            layers["wq"] = stack(
                "model.layers.{}.self_attn.q_proj.weight", True
            )
            layers["wk"] = stack(
                "model.layers.{}.self_attn.k_proj.weight", True
            )
            layers["wv"] = stack(
                "model.layers.{}.self_attn.v_proj.weight", True
            )
            layers["wo"] = stack(
                "model.layers.{}.self_attn.o_proj.weight", True
            )
        if cfg.post_norms:
            # gemma sandwich norms: HF post_attention_layernorm is the
            # POST-attention norm; the pre-MLP norm has its own name
            layers["post_attn_norm"] = stack(
                "model.layers.{}.post_attention_layernorm.weight"
            )
            layers["mlp_norm"] = stack(
                "model.layers.{}.pre_feedforward_layernorm.weight"
            )
            layers["post_mlp_norm"] = stack(
                "model.layers.{}.post_feedforward_layernorm.weight"
            )
        else:
            # llama-family: post_attention_layernorm IS the pre-MLP norm
            layers["mlp_norm"] = stack(
                "model.layers.{}.post_attention_layernorm.weight"
            )
        if cfg.qkv_bias:
            layers["bq"] = stack("model.layers.{}.self_attn.q_proj.bias")
            layers["bk"] = stack("model.layers.{}.self_attn.k_proj.bias")
            layers["bv"] = stack("model.layers.{}.self_attn.v_proj.bias")
        if cfg.o_bias:
            layers["bo"] = stack("model.layers.{}.self_attn.o_proj.bias")
        if cfg.attn_sinks:
            # fp32: sink logits join the softmax denominator directly
            layers["sinks"] = jnp.stack([
                _to_jnp(
                    tensors.pop(f"model.layers.{i}.self_attn.sinks"),
                    jnp.float32,
                )
                for i in rng
            ])
        if cfg.qk_norm:
            layers["q_norm"] = stack(
                "model.layers.{}.self_attn.q_norm.weight"
            )
            layers["k_norm"] = stack(
                "model.layers.{}.self_attn.k_norm.weight"
            )
        def pop_gptoss_expert(name: str, i: int):
            """GPT-OSS expert tensor, dequantizing the hub's MXFP4
            packing when present (openai/gpt-oss-* ship
            ``{name}_blocks`` uint8 fp4-pairs + ``{name}_scales`` e8m0
            per 32-value block — transformers integrations/mxfp4
            convert_moe_packed_tensors); dequantized bf16 re-exports
            carry the plain tensor."""
            base = f"model.layers.{i}.mlp.experts.{name}"
            if base in tensors:
                return _to_jnp(tensors.pop(base))
            blocks = tensors.pop(base + "_blocks").numpy()
            scales = tensors.pop(base + "_scales").numpy()
            return _mxfp4_dequant(blocks, scales)

        if moe and cfg.moe_act == "gptoss":
            # GPT-OSS fused expert tensors (modeling_gpt_oss
            # GptOssExperts/GptOssTopKRouter): gate_up_proj [E, D, 2F]
            # with gate/up INTERLEAVED on the last axis, biased
            # everywhere, router as a true affine map
            layers["router"] = stack(
                "model.layers.{}.mlp.router.weight", True
            )
            layers["router_bias"] = jnp.stack([
                _to_jnp(
                    tensors.pop(f"model.layers.{i}.mlp.router.bias"),
                    jnp.float32,
                )
                for i in rng
            ])

            def popb(name: str, i: int):
                return _to_jnp(
                    tensors.pop(f"model.layers.{i}.mlp.experts.{name}")
                )

            gu = [
                pop_gptoss_expert("gate_up_proj", i) for i in rng
            ]                                                # [E, D, 2F]
            gub = [popb("gate_up_proj_bias", i) for i in rng]  # [E, 2F]
            layers["we_gate"] = jnp.stack([t[..., 0::2] for t in gu])
            layers["we_up"] = jnp.stack([t[..., 1::2] for t in gu])
            layers["we_gate_b"] = jnp.stack([t[..., 0::2] for t in gub])
            layers["we_up_b"] = jnp.stack([t[..., 1::2] for t in gub])
            layers["we_down"] = jnp.stack(
                [pop_gptoss_expert("down_proj", i) for i in rng]
            )                                                # [E, F, D]
            layers["we_down_b"] = jnp.stack(
                [popb("down_proj_bias", i) for i in rng]     # [E, D]
            )
        elif moe:
            # Three HF MoE naming schemes: Mixtral (block_sparse_moe /
            # w1|w2|w3), Qwen-MoE and DeepSeek (mlp.gate /
            # experts.{e}.gate_proj|down_proj|up_proj)
            if "model.layers.0.block_sparse_moe.gate.weight" in tensors:
                block, wg, wd, wu = (
                    "block_sparse_moe", "w1", "w2", "w3"
                )
            else:
                block, wg, wd, wu = (
                    "mlp", "gate_proj", "down_proj", "up_proj"
                )
                if not cfg.shared_expert_intermediate_size and any(
                    "shared_expert" in name for name in tensors
                ):
                    # shared-expert tensors with no config support would
                    # be silently dropped -> wrong logits; fail loudly
                    raise ValueError(
                        "checkpoint has shared-expert weights but the "
                        "config declares no shared expert width"
                    )
            layers["router"] = stack(
                "model.layers.{}." + block + ".gate.weight", True
            )
            if cfg.moe_scoring == "sigmoid":
                # fp32 on purpose: the correction bias tie-breaks expert
                # SELECTION (checkpoints store it fp32); bf16 rounding
                # could flip top-k picks on finely-balanced experts
                layers["router_bias"] = jnp.stack([
                    _to_jnp(
                        tensors.pop(
                            f"model.layers.{i}.{block}"
                            ".gate.e_score_correction_bias"
                        ),
                        jnp.float32,
                    )
                    for i in rng
                ])
            # under a share only the held experts' weights are read
            held = range(
                cfg.first_held_expert,
                cfg.first_held_expert + cfg.num_held_experts,
            )

            def stack_experts(w: str, transpose: bool) -> jax.Array:
                return jnp.stack([
                    jnp.stack([
                        _to_jnp(
                            tensors.pop(
                                f"model.layers.{i}.{block}"
                                f".experts.{e}.{w}.weight"
                            ).T if transpose else tensors.pop(
                                f"model.layers.{i}.{block}"
                                f".experts.{e}.{w}.weight"
                            )
                        )
                        for e in held
                    ])
                    for i in rng
                ])

            layers["we_gate"] = stack_experts(wg, True)
            layers["we_down"] = stack_experts(wd, True)
            layers["we_up"] = stack_experts(wu, True)
            if cfg.shared_expert_intermediate_size:
                # DeepSeek: mlp.shared_experts.* (plural, ungated);
                # Qwen2-MoE: mlp.shared_expert.* + shared_expert_gate
                se = (
                    "shared_expert" if cfg.shared_expert_gated
                    else "shared_experts"
                )
                layers["ws_gate"] = stack(
                    "model.layers.{}.mlp." + se + ".gate_proj.weight",
                    True,
                )
                layers["ws_up"] = stack(
                    "model.layers.{}.mlp." + se + ".up_proj.weight",
                    True,
                )
                layers["ws_down"] = stack(
                    "model.layers.{}.mlp." + se + ".down_proj.weight",
                    True,
                )
                if cfg.shared_expert_gated:
                    layers["shared_gate"] = stack(
                        "model.layers.{}.mlp.shared_expert_gate.weight",
                        True,
                    )
        else:
            layers["w_gate"] = stack(
                "model.layers.{}.mlp.gate_proj.weight", True
            )
            layers["w_up"] = stack(
                "model.layers.{}.mlp.up_proj.weight", True
            )
            layers["w_down"] = stack(
                "model.layers.{}.mlp.down_proj.weight", True
            )
        return dict(layers)

    params: Dict[str, Any] = {
        "embed": take("model.embed_tokens.weight"),
        "layers": build_range(range(kd, L), cfg.is_moe),
        "final_norm": take("model.norm.weight"),
    }
    if kd:
        params["dense_layers"] = build_range(range(kd), False)
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in tensors:
            params["lm_head"] = take("lm_head.weight", True)
        else:
            logger.warning("no lm_head.weight; tying to embeddings")
            params["lm_head"] = params["embed"].T
    if tensors:
        logger.warning("unused checkpoint tensors: %s", sorted(tensors)[:8])
    if int8:
        from gpustack_tpu.models.quant import quantize_params

        # the layer stacks are int8 already; this takes embed / lm_head
        # (which one depends on the tie) and passes the rest through
        params = quantize_params(params)
    return params


def build_hybrid_params(
    cfg: ModelConfig, tensors: Dict[str, Any], quantization: str = ""
) -> Dict[str, Any]:
    """The Nemotron-H checkpoint's names (``backbone.layers.N.norm`` and
    ``backbone.layers.N.mixer.*``, the mixer's kind by the layer's place
    in ``hybrid_override_pattern``) -> the three stacks of
    ``models/hybrid.py``, a leaf at a time. Matrices transpose on load
    and go to int8 under ``quantization="int8"``; ``A_log``, ``D``,
    ``dt_bias`` and the convolution stay float32, norms bf16. Under a
    share of the experts only the held ids are read; the experts' width
    is filled up to whole lane tiles (``pad_expert_width``)."""
    from gpustack_tpu.models.hybrid import pad_expert_width

    take = _taker(tensors)
    int8 = quantization == "int8"
    by_kind: Dict[str, list] = {"M": [], "E": [], "*": []}
    for i, kind in enumerate(cfg.layer_kinds):
        by_kind[kind].append(i)

    def take32(name: str) -> jax.Array:
        return jnp.asarray(
            tensors.pop(name).float().numpy(), jnp.float32
        )

    def stacks(kind: str):
        layers_of = by_kind[kind]

        def stack(fmt: str, transpose: bool = False, get=take):
            if get is take:
                return jnp.stack(
                    [take(fmt.format(i), transpose) for i in layers_of]
                )
            return jnp.stack([get(fmt.format(i)) for i in layers_of])

        out: Dict[str, Any] = _QuantizeOnSet() if int8 else {}
        out["norm"] = stack("backbone.layers.{}.norm.weight")
        return out, stack

    params: Dict[str, Any] = {}
    mixer = "backbone.layers.{}.mixer."
    if by_kind["M"]:
        out, stack = stacks("M")
        out["w_in"] = stack(mixer + "in_proj.weight", True)
        # torch's depthwise conv1d weight is [C, 1, K]: ours [K, C]
        out["conv_w"] = jnp.swapaxes(
            stack(mixer + "conv1d.weight", get=take32)[:, :, 0, :], 1, 2
        )
        out["conv_b"] = stack(mixer + "conv1d.bias", get=take32)
        for ours, theirs in (
            ("dt_bias", "dt_bias"), ("A_log", "A_log"), ("D", "D"),
        ):
            out[ours] = stack(mixer + theirs, get=take32)
        out["gate_norm"] = stack(mixer + "norm.weight")
        out["w_out"] = stack(mixer + "out_proj.weight", True)
        params["ssm_layers"] = dict(out)
    if by_kind["E"]:
        out, stack = stacks("E")
        held = range(
            cfg.first_held_expert,
            cfg.first_held_expert + cfg.num_held_experts,
        )
        out["router"] = stack(mixer + "gate.weight", True)
        out["router_bias"] = stack(
            mixer + "gate.e_score_correction_bias", get=take32
        )

        def experts(which: str, axis: int):
            return pad_expert_width(jnp.stack([
                jnp.stack([
                    take(
                        f"backbone.layers.{i}.mixer.experts.{e}."
                        f"{which}.weight", True,
                    ) for e in held
                ]) for i in by_kind["E"]
            ]), axis)

        out["we_up"] = experts("up_proj", -1)
        out["we_down"] = experts("down_proj", -2)
        if cfg.shared_expert_intermediate_size:
            out["ws_up"] = stack(
                mixer + "shared_experts.up_proj.weight", True
            )
            out["ws_down"] = stack(
                mixer + "shared_experts.down_proj.weight", True
            )
        if cfg.experts_held:
            # the absent experts' tensors are some other chip's
            for name in [n for n in tensors if ".mixer.experts." in n]:
                del tensors[name]
        params["moe_layers"] = dict(out)
    if by_kind["*"]:
        out, stack = stacks("*")
        for ours, theirs in (
            ("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
            ("wo", "o_proj"),
        ):
            out[ours] = stack(mixer + theirs + ".weight", True)
        params["attn_layers"] = dict(out)
    params["embed"] = take("backbone.embeddings.weight")
    params["final_norm"] = take("backbone.norm_f.weight")
    if not cfg.tie_word_embeddings:
        params["lm_head"] = take("lm_head.weight", True)
    if tensors:
        logger.warning("unused checkpoint tensors: %s", sorted(tensors)[:8])
    if int8:
        from gpustack_tpu.models.quant import quantize_params

        params = quantize_params(params)
    return params


def load_npz_params(path: str, init_fn):
    """Load a flat-or-nested param tree saved as .npz ('/'-joined keys),
    falling back to ``init_fn()`` when no file exists — the checkpoint
    format for in-repo models without an HF counterpart (e.g. TTS)."""
    import numpy as np

    try:
        with np.load(path) as z:
            flat = {k: jnp.asarray(z[k]) for k in z.files}
    except OSError:
        logger.warning("no checkpoint at %r — random init", path)
        return init_fn()
    tree: dict = {}
    for key, value in flat.items():
        node = tree
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return tree


def load_whisper_params(cfg, model_dir: str):
    """Load an HF Whisper safetensors checkpoint into the
    models/whisper.py param tree (falls back to random init when no
    checkpoint is present — same contract as load_or_init_params)."""
    from gpustack_tpu.models.whisper import init_whisper_params

    try:
        tensors = _read_safetensors(model_dir)
    except FileNotFoundError:
        logger.warning(
            "no whisper checkpoint at %r — random init", model_dir
        )
        return init_whisper_params(cfg, jax.random.key(0))
    take = _taker(tensors)

    def stack(side: str, L: int, fmt: str, transpose=False) -> jax.Array:
        return jnp.stack(
            [
                take(f"model.{side}.layers.{i}.{fmt}", transpose)
                for i in range(L)
            ]
        )

    def attn_block(side: str, L: int, prefix: str, out: dict, tag: str):
        out[f"{tag}wq"] = stack(side, L, f"{prefix}.q_proj.weight", True)
        out[f"{tag}bq"] = stack(side, L, f"{prefix}.q_proj.bias")
        out[f"{tag}wk"] = stack(side, L, f"{prefix}.k_proj.weight", True)
        out[f"{tag}wv"] = stack(side, L, f"{prefix}.v_proj.weight", True)
        out[f"{tag}bv"] = stack(side, L, f"{prefix}.v_proj.bias")
        out[f"{tag}wo"] = stack(side, L, f"{prefix}.out_proj.weight", True)
        out[f"{tag}bo"] = stack(side, L, f"{prefix}.out_proj.bias")

    def layer_group(side: str, L: int) -> dict:
        out = {
            "ln1": stack(side, L, "self_attn_layer_norm.weight"),
            "ln1_b": stack(side, L, "self_attn_layer_norm.bias"),
            "ln2": stack(side, L, "final_layer_norm.weight"),
            "ln2_b": stack(side, L, "final_layer_norm.bias"),
            "w_up": stack(side, L, "fc1.weight", True),
            "b_up": stack(side, L, "fc1.bias"),
            "w_down": stack(side, L, "fc2.weight", True),
            "b_down": stack(side, L, "fc2.bias"),
        }
        attn_block(side, L, "self_attn", out, "")
        if side == "decoder":
            out["lnx"] = stack(side, L, "encoder_attn_layer_norm.weight")
            out["lnx_b"] = stack(side, L, "encoder_attn_layer_norm.bias")
            attn_block(side, L, "encoder_attn", out, "x")
        return out

    params = {
        # HF conv weights are [out, in, k] — ours are [k, in, out]
        "conv1": jnp.transpose(
            _to_jnp(tensors.pop("model.encoder.conv1.weight")), (2, 1, 0)
        ),
        "conv1_b": take("model.encoder.conv1.bias"),
        "conv2": jnp.transpose(
            _to_jnp(tensors.pop("model.encoder.conv2.weight")), (2, 1, 0)
        ),
        "conv2_b": take("model.encoder.conv2.bias"),
        "enc_layers": layer_group("encoder", cfg.encoder_layers),
        "enc_ln": take("model.encoder.layer_norm.weight"),
        "enc_ln_b": take("model.encoder.layer_norm.bias"),
        "tok_embed": take("model.decoder.embed_tokens.weight"),
        "pos_embed": take("model.decoder.embed_positions.weight"),
        "dec_layers": layer_group("decoder", cfg.decoder_layers),
        "dec_ln": take("model.decoder.layer_norm.weight"),
        "dec_ln_b": take("model.decoder.layer_norm.bias"),
    }
    # encoder position embeddings are fixed sinusoids (recomputed)
    tensors.pop("model.encoder.embed_positions.weight", None)
    tensors.pop("proj_out.weight", None)  # tied to tok_embed
    if tensors:
        logger.warning(
            "unused whisper tensors: %s", sorted(tensors)[:8]
        )
    return params


# HF PEFT module name -> our stacked layer param (torch Linear weights
# are [out, in]; ours are transposed [in, out], so the merged delta is
# (B @ A).T == A.T @ B.T)
_LORA_MODULES = {
    "q_proj": "wq",
    "k_proj": "wk",
    "v_proj": "wv",
    "o_proj": "wo",
    "gate_proj": "w_gate",
    "up_proj": "w_up",
    "down_proj": "w_down",
}


def merge_lora_adapters(cfg, params: Dict[str, Any], adapter_dirs):
    """Merge PEFT LoRA adapters into the base weights: W' = W + s·BA.

    Merged-at-load serving (the TPU-friendly LoRA shape: zero runtime
    overhead, one instance per adapter set — reference serves LoRA via
    engine flags + per-adapter ModelRoutes, server/lora_model_routes.py).
    Must run BEFORE int8 quantization. Returns the mutated param tree.
    """
    import json as _json
    import re as _re

    for adapter_dir in adapter_dirs:
        cfg_path = os.path.join(adapter_dir, "adapter_config.json")
        scale = 1.0
        try:
            with open(cfg_path) as f:
                acfg = _json.load(f)
            r = int(acfg.get("r", 0)) or 1
            alpha = float(acfg.get("lora_alpha", r))
            if acfg.get("use_rslora"):
                scale = alpha / (r ** 0.5)   # rsLoRA: alpha / sqrt(r)
            else:
                scale = alpha / r
        except (OSError, ValueError):
            logger.warning(
                "no adapter_config.json in %s; using scale 1.0",
                adapter_dir,
            )
        tensors = _read_safetensors(adapter_dir)
        pat = _re.compile(
            r"layers\.(\d+)\.(?:self_attn|mlp)\.(\w+)\.lora_A\.weight$"
        )
        merged = 0
        for name in sorted(tensors):
            m = pat.search(name)
            if m is None:
                continue
            layer_idx = int(m.group(1))
            module = m.group(2)
            ours = _LORA_MODULES.get(module)
            if layer_idx >= cfg.num_layers:
                # JAX scatter would silently drop the OOB update — a
                # half-applied adapter must be an error, not a mystery
                raise ValueError(
                    f"adapter {adapter_dir} targets layer {layer_idx} "
                    f"but the model has {cfg.num_layers} layers"
                )
            # heterogeneous stacks (DeepSeek first_k_dense): absolute HF
            # layer i lives in the dense prefix when i < kd, else at
            # offset i - kd in the MoE stack — indexing the MoE stack
            # with the absolute i would merge into the WRONG layer
            kd = (
                len(next(iter(params["dense_layers"].values())))
                if "dense_layers" in params else 0
            )
            if layer_idx < kd:
                stack_key, stack_idx = "dense_layers", layer_idx
            else:
                stack_key, stack_idx = "layers", layer_idx - kd
            if ours is None or ours not in params[stack_key]:
                logger.warning(
                    "skipping LoRA target %s (unsupported module)", name
                )
                continue
            b_name = name.replace("lora_A", "lora_B")
            if b_name not in tensors:
                raise ValueError(
                    f"adapter {adapter_dir} is missing {b_name} "
                    f"(truncated checkpoint?)"
                )
            # keep fp32 through the delta matmul — routing through the
            # default bf16 load dtype would cost ~8 mantissa bits twice
            a = _to_jnp(tensors[name], jnp.float32)
            b = _to_jnp(tensors[b_name], jnp.float32)
            delta = (a.T @ b.T) * scale                 # [in, out]
            base = params[stack_key][ours]
            params[stack_key][ours] = base.at[stack_idx].add(
                delta.astype(base.dtype)
            )
            merged += 1
        logger.info(
            "merged %d LoRA deltas from %s (scale %.3f)",
            merged, adapter_dir, scale,
        )
        if merged == 0:
            raise ValueError(
                f"adapter {adapter_dir} matched no mergeable weights"
            )
    return params


def checkpoint_source(model_dir: Optional[str]):
    """(kind, path) for a model source: ("safetensors", dir),
    ("gguf", file) or ("none", None). The ONE place format precedence
    lives — config resolution and weight loading must always pick the
    same checkpoint in a mixed directory."""
    if model_dir and glob.glob(os.path.join(model_dir, "*.safetensors")):
        return "safetensors", model_dir
    if model_dir:
        from gpustack_tpu.engine.gguf import gguf_file_in

        gguf_path = gguf_file_in(model_dir)
        if gguf_path:
            return "gguf", gguf_path
    return "none", None


def load_or_init_params(
    cfg: ModelConfig,
    model_dir: Optional[str],
    seed: int = 0,
    quantization: str = "",
) -> Dict[str, Any]:
    """The model's param tree from its checkpoint, or seeded random
    weights for a preset. With ``quantization="int8"`` the int8 tree is
    built leaf by leaf — the whole bf16 tree is never on the device."""
    kind, path = checkpoint_source(model_dir)
    if kind == "safetensors":
        logger.info("loading checkpoint from %s", path)
        return load_hf_checkpoint(cfg, path, quantization)
    if kind == "gguf":
        logger.info("loading GGUF checkpoint from %s", path)
        return load_gguf_checkpoint(cfg, path, quantization)
    logger.warning(
        "no checkpoint at %r — initializing random weights for %s",
        model_dir, cfg.name,
    )
    if quantization == "int8":
        from gpustack_tpu.models.quant import init_params_int8

        return init_params_int8(cfg, jax.random.key(seed))
    return init_params(cfg, jax.random.key(seed))


def save_checkpoint(params: Dict[str, Any], path: str) -> None:
    """Save params in our native stacked layout (orbax-free, npz-based) —
    used for engine-local caching of (possibly int8-quantized) weights.
    ``QuantW`` leaves round-trip via explicit ``::q`` / ``::s`` suffixes."""
    from gpustack_tpu.models.quant import QuantW

    flat: Dict[str, np.ndarray] = {}

    def to_np(leaf) -> tuple:
        """npz has no bfloat16; store as float32 with a dtype tag."""
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            return arr.astype(np.float32), "#bf16"
        return arr, ""

    def walk(node, prefix: str) -> None:
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{prefix}/{k}" if prefix else k)
        elif isinstance(node, QuantW):
            arr, tag = to_np(node.q)
            flat[prefix + "::q" + tag] = arr
            arr, tag = to_np(node.s)
            flat[prefix + "::s" + tag] = arr
        else:
            arr, tag = to_np(node)
            flat[prefix + tag] = arr

    walk(params, "")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    np.savez(path, **flat)


def load_checkpoint(path: str) -> Dict[str, Any]:
    from gpustack_tpu.models.quant import QuantW

    data = np.load(path)
    tree: Dict[str, Any] = {}
    pending_quant: Dict[str, Dict[str, Any]] = {}
    for name, arr in data.items():
        if name.endswith("#bf16"):
            name = name[: -len("#bf16")]
            arr = jnp.asarray(arr).astype(jnp.bfloat16)
        base, _, qs = name.partition("::")
        if qs:
            pending_quant.setdefault(base, {})[qs] = jnp.asarray(arr)
            continue
        parts = name.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(arr)
    for base, qs in pending_quant.items():
        parts = base.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = QuantW(q=qs["q"], s=qs["s"])
    return tree
