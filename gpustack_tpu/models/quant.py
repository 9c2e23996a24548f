"""Int8 weight-only quantization for the transformer.

Decode is HBM-bandwidth-bound: weight bytes read per token dominate. Storing
weights as int8 with per-output-channel scales halves (vs bf16) the bytes per
decode step; the matmul contracts int8-upcast-to-bf16 directly
(``x @ q.astype(bf16) * s``) so the dequantized tensor is never materialized
in HBM — XLA fuses the convert into the MXU feed.

Scale layout: for each weight, scales live on the *output* (non-contracted)
dims, so the rescale is a cheap elementwise multiply on the matmul result.

The reference exposes per-model quantization as engine flags (vLLM
``--quantization``); here it is a first-class transform over the param tree
(``quantize_params``) the engine applies at load time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantW:
    """An int8-quantized weight: ``q`` int8, ``s`` per-output-channel scale."""

    q: jax.Array
    s: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def size(self):
        return self.q.size


# Which axes of each (per-layer-sliced) weight are contracted in its matmul.
# Scales span the remaining (output) axes. Leaves not listed stay unquantized
# (norm gains, biases, the tiny router).
_CONTRACT_AXES: Dict[str, tuple] = {
    "embed": (1,),      # gather: scale per vocab row
    "lm_head": (0,),    # [d, v] contracts d
    "wq": (0,), "wk": (0,), "wv": (0,),   # [d, out] contract d
    "wo": (0,),                            # [q, d] contracts q
    "w_gate": (0,), "w_up": (0,),          # [d, f] contract d
    "w_down": (0,),                        # [f, d] contracts f
    "we_gate": (1,), "we_up": (1,),        # [E, d, f] contract d
    "we_down": (1,),                       # [E, f, d] contract f
    # DeepSeek MLA projections + shared experts (the tiny rank-sized
    # norms and router bias stay unquantized like other small leaves)
    "wq_a": (0,), "wq_b": (0,),
    "wkv_a": (0,), "wk_b": (0,), "wv_b": (0,),
    "ws_gate": (0,), "ws_up": (0,), "ws_down": (0,),
    # the hybrid's state-space mixer: in_proj [d, z|xBC|dt], out_proj
    # (its convolution, A_log, D, dt_bias and norms stay as they are)
    "w_in": (0,), "w_out": (0,),
    # a delta-rule mixer's output gate [d, H * Dv], beside its wq, wk,
    # wv, wo above (its convolution, A_log and dt_bias stay float32, its
    # norm and the two one-a-head projections wa, wb bf16)
    "wg": (0,),
}
# The stacks of layers in a param tree: one for most models, a dense
# prefix beside it for DeepSeek, three for the hybrid (models/hybrid.py),
# one a kind of mixer beside it for a stack with ``layer_types``
LAYER_STACKS = (
    "layers", "dense_layers", "ssm_layers", "moe_layers", "attn_layers",
    "delta_layers",
)
# Layer-stacked leaves carry a leading [L] axis not present at use time.
_STACKED = {
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down",
    "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b",
    "ws_gate", "ws_up", "ws_down", "w_in", "w_out", "wg",
}


def _quantize_leaf(name: str, w) -> QuantW:
    if isinstance(w, QuantW):
        return w  # quantized as it was loaded (engine/weights.py)
    axes = _CONTRACT_AXES[name]
    if name in _STACKED:
        axes = tuple(a + 1 for a in axes)
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axes, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return QuantW(q=q, s=jnp.squeeze(scale, axis=axes).astype(jnp.bfloat16))


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize all large weights of a transformer param tree to int8.

    Tied-embedding models keep ``embed`` unquantized (the transpose reuse
    would need a second scale layout).
    """
    out: Dict[str, Any] = {}
    tie = "lm_head" not in params
    for k, v in params.items():
        if k in LAYER_STACKS:
            out[k] = {
                lk: _quantize_leaf(lk, lv) if lk in _CONTRACT_AXES else lv
                for lk, lv in v.items()
            }
        elif k in _CONTRACT_AXES and not (k == "embed" and tie):
            out[k] = _quantize_leaf(k, v)
        else:
            out[k] = v
    return out


def init_params_int8(cfg, key: jax.Array) -> Dict[str, Any]:
    """``quantize_params(init_params(cfg, key))`` without ever holding the
    bf16 tree: one jitted program per quantized leaf, from which XLA drops
    every other leaf's PRNG work, so the peak on the device is the int8
    tree so far plus one bf16 leaf (an 8B model's bf16 tree does not fit
    a 16 GB chip; its int8 tree does). The one definition of a preset's
    seeded int8 weights: the engine start and the tests both call it."""
    from gpustack_tpu.models.transformer import init_params

    def build(k):
        return quantize_params(init_params(cfg, k))

    is_q = lambda x: isinstance(x, QuantW)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(build, key), is_leaf=is_q
    )

    def pick(paths):
        def f(k):
            by_path = dict(
                jax.tree_util.tree_flatten_with_path(build(k), is_leaf=is_q)[0]
            )
            return [by_path[p] for p in paths]

        return jax.jit(f)

    small = [p for p, leaf in flat if not is_q(leaf)]
    leaves = dict(zip(small, pick(small)(key)))
    for p, leaf in flat:
        if is_q(leaf):
            leaves[p] = pick([p])(key)[0]
    return jax.tree_util.tree_unflatten(treedef, [leaves[p] for p, _ in flat])


def quant_pspecs(specs: Dict[str, Any], params: Dict[str, Any]):
    """Adapt a PartitionSpec tree (from ``parallel.param_pspecs``) to a
    quantized param tree: ``q`` keeps the weight's spec, ``s`` keeps the
    spec's output-axis components."""
    from jax.sharding import PartitionSpec as P

    def adapt(name: str, spec, leaf):
        if not isinstance(leaf, QuantW):
            return spec
        axes = _CONTRACT_AXES[name]
        if name in _STACKED:
            axes = tuple(a + 1 for a in axes)
        s_spec = P(*(s for i, s in enumerate(spec) if i not in axes))
        return QuantW(q=spec, s=s_spec)

    out: Dict[str, Any] = {}
    for k, v in params.items():
        if k in LAYER_STACKS:
            out[k] = {
                lk: adapt(lk, specs[k][lk], lv) for lk, lv in v.items()
            }
        else:
            out[k] = adapt(k, specs[k], v)
    return out


def init_quantized_params(cfg, seed: int = 0):
    """Random int8 params generated *directly* (no bf16 detour).

    ``quantize_params(init_params(...))`` materializes the full bf16 tree
    first — 16 GB of jax PRNG work for an 8B model, minutes of host time.
    Synthetic benchmarks only need weight tensors of the right shape and
    scale, so this builds the QuantW tree straight from numpy int8 draws
    (~20x faster); statistics match the absmax-quantized normal init.
    """
    import math

    import numpy as np

    rng = np.random.default_rng(seed)
    d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers

    def qw(shape, fan_in, name):
        q = rng.integers(-127, 128, size=shape, dtype=np.int8)
        axes = _CONTRACT_AXES[name]
        if name in _STACKED:
            axes = tuple(a + 1 for a in axes)
        s_shape = tuple(
            n for i, n in enumerate(shape) if i not in axes
        )
        # absmax-normal scale: ~3 sigma of N(0, 1/sqrt(fan_in)) per 127
        s = np.full(
            s_shape, 3.0 / math.sqrt(fan_in) / 127.0, dtype=np.float32
        )
        return QuantW(
            q=jnp.asarray(q), s=jnp.asarray(s).astype(jnp.bfloat16)
        )

    ones = lambda *shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    zeros = lambda *shape: jnp.zeros(shape, jnp.bfloat16)  # noqa: E731

    gain = zeros if cfg.norm_delta_gain else ones  # gemma: delta gains
    layers = {
        "attn_norm": gain(L, d),
        "mlp_norm": gain(L, d),
        "wq": qw((L, d, cfg.q_dim), d, "wq"),
        "wk": qw((L, d, cfg.kv_dim), d, "wk"),
        "wv": qw((L, d, cfg.kv_dim), d, "wv"),
        "wo": qw((L, cfg.q_dim, d), cfg.q_dim, "wo"),
    }
    if cfg.qkv_bias:
        layers["bq"] = zeros(L, cfg.q_dim)
        layers["bk"] = zeros(L, cfg.kv_dim)
        layers["bv"] = zeros(L, cfg.kv_dim)
    if cfg.qk_norm:
        norm_init = zeros if cfg.norm_delta_gain else ones
        layers["q_norm"] = norm_init(L, cfg.head_dim)
        layers["k_norm"] = norm_init(L, cfg.head_dim)
    if cfg.post_norms:
        norm_init = zeros if cfg.norm_delta_gain else ones
        layers["post_attn_norm"] = norm_init(L, d)
        layers["post_mlp_norm"] = norm_init(L, d)
    if cfg.is_moe:
        fm, E = cfg.moe_intermediate_size, cfg.num_experts
        Eh = cfg.num_held_experts    # weights for the held experts only
        layers["router"] = (
            jnp.asarray(
                rng.standard_normal((L, d, E), dtype=np.float32)
                / math.sqrt(d)
            ).astype(jnp.bfloat16)
        )
        layers["we_gate"] = qw((L, Eh, d, fm), d, "we_gate")
        layers["we_up"] = qw((L, Eh, d, fm), d, "we_up")
        layers["we_down"] = qw((L, Eh, fm, d), fm, "we_down")
    else:
        layers["w_gate"] = qw((L, d, f), d, "w_gate")
        layers["w_up"] = qw((L, d, f), d, "w_up")
        layers["w_down"] = qw((L, f, d), f, "w_down")

    params = {
        "layers": layers,
        "final_norm": gain(d),
    }
    if cfg.tie_word_embeddings:
        # Tied models contract embed.T at the LM head (transformer.forward
        # uses a raw einsum there) — keep embed bf16, matching
        # quantize_params' tied-embedding rule above.
        params["embed"] = jnp.asarray(
            rng.standard_normal((cfg.vocab_size, d), dtype=np.float32)
            * 0.02
        ).astype(jnp.bfloat16)
    else:
        params["embed"] = qw((cfg.vocab_size, d), 2500, "embed")  # ~0.02
        params["lm_head"] = qw((d, cfg.vocab_size), d, "lm_head")
    return params


def dequantize(name: str, w, stacked: Optional[bool] = None) -> jax.Array:
    """Reference dequantization (tests / debugging). ``name`` identifies the
    weight's contraction layout; ``stacked`` overrides the [L]-axis default
    (pass False for a per-layer slice of a stacked weight)."""
    if not isinstance(w, QuantW):
        return w
    axes = _CONTRACT_AXES[name]
    if stacked if stacked is not None else name in _STACKED:
        axes = tuple(a + 1 for a in axes)
    return w.q.astype(jnp.bfloat16) * jnp.expand_dims(w.s, axes)
