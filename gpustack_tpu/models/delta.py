"""The gated-delta-rule mixer of a linear-attention layer
(``cfg.layer_types``: Olmo-Hybrid's, and KDA, Solar-Open2's, the same
function with three fields of the configuration set): one function over a layer's
input, its leaves, the carried cache and the layer's index among its
kind, beside ``models/hybrid.py mamba_mixer``. ``transformer.scan_periods``
calls it for a ``"linear_attention"`` layer; the layer's norms, its
residual adds and its MLP are ``transformer.after_mixer``'s.

``q~ = h Wq``, ``k~ = h Wk`` (``H * Dk`` wide), ``v~ = h Wv`` (``H * Dv``
wide), side by side through one causal depthwise convolution of ``K``
taps each (no bias), then ``silu``. A head at a time ``q = l2norm(q') /
sqrt(Dk)``, ``k = l2norm(k')``, ``v = v'``; ``beta = sigmoid(h Wb)``
(doubled under ``cfg.linear_allow_neg_eigval``), ``g = -exp(A_log) *
softplus(h Wa + dt_bias)``, one each a head, float32. The rule itself is
``ops/delta_rule.py``'s. Then ``y = rms_norm(o; w_o) * silu(h Wg)`` a
head (the norm first, then the gate) and ``out = y Wo``.

KDA (Kimi Linear, arXiv:2510.26692) differs in three things, each a
field of ``ModelConfig``: the decay is one number a head **and key
channel** (``cfg.linear_decay_a_channel``: ``g [B, T, H, Dk]``,
``dt_bias [H * Dk]``, ``A_log`` still one a head; every form of
``ops/delta_rule.py`` takes either shape of ``g``); the decay's and the
gate's projections go through a bottleneck (``cfg.linear_low_rank``:
``(h Wf_a) Wf_b`` and ``(h Wg_a) Wg_b`` where the other has ``h Wa`` and
``h Wg``); and the gate stands under a sigmoid (``cfg.linear_gate_act``).

What a slot keeps (``transformer.KVCache``, the shapes
``ModelConfig.state_shapes``'): each such layer's state ``ssm [L_lin, B,
Dk, H * Dv]`` (float32, nothing padded: ``ops/delta_rule.py``) and the
last ``K - 1`` rows of ``[q~ | k~ | v~]`` before the convolution, ``conv
[L_lin, B, (K-1) * C]``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from gpustack_tpu.models.config import ModelConfig

L2_EPS = 1e-6


def init_delta_layers(cfg: ModelConfig, key: jax.Array, dtype) -> Dict[str, Any]:
    """The linear-attention layers' stack, random, each leaf drawn whole
    at its own depth. ``A_log`` the log of a uniform draw in (0, 16] and
    ``dt_bias`` the inverse softplus of 0.001..0.1, as the rule's public
    code initialises them: a state that neither dies in a step nor never
    forgets. ``dt_bias`` is one a decay: a head's, or under
    ``cfg.linear_decay_a_channel`` a head's key channel's."""
    L, d = cfg.num_linear_layers, cfg.hidden_size
    H = cfg.linear_num_value_heads
    keys_w = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    values_w = H * cfg.linear_value_head_dim
    decays = keys_w if cfg.linear_decay_a_channel else H
    rank = cfg.linear_low_rank
    keys = iter(jax.random.split(key, 12))
    f32 = jnp.float32

    def w(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (
            jax.random.normal(next(keys), shape, f32) * scale
        ).astype(dtype)

    def gates():
        """The gate's and the decay's projections, each one matrix or
        through the bottleneck (its four matrices bf16 under int8:
        ``models/quant.py`` lists none of them)."""
        if not rank:
            return {"wg": w(L, d, values_w), "wa": w(L, d, decays)}
        return {
            "wg_a": w(L, d, rank), "wg_b": w(L, rank, values_w),
            "wf_a": w(L, d, rank), "wf_b": w(L, rank, decays),
        }

    return {
        "wq": w(L, d, keys_w),
        "wk": w(L, d, keys_w),
        "wv": w(L, d, values_w),
        **gates(),
        "wb": w(L, d, H),
        "conv_w": w(
            L, cfg.linear_conv_kernel_dim, cfg.linear_conv_dim, scale=0.5
        ).astype(f32),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(
            jax.random.uniform(
                next(keys), (L, decays), f32, math.log(1e-3), math.log(1e-1)
            )
        ))),
        "A_log": jnp.log(
            16.0 * (1.0 - jax.random.uniform(next(keys), (L, H), f32))
        ),
        "o_norm": jnp.ones((L, cfg.linear_value_head_dim), dtype),
        "wo": w(L, values_w, d),
    }


def delta_mixer(
    h: jax.Array,       # [B, T, D], behind whatever norm stands before it
    lp,                 # the layer's leaves
    carried,            # the cache (None: from zeros, nothing kept)
    i: jax.Array,       # int32: the layer's index among the linear layers
    step,               # transformer.Step: cfg, ssm_impl, real, alive
):
    """One gated-delta-rule mixer: ``(out [B, T, D], carried)``. A padded
    position (``step.real`` False) has ``g = 0`` and ``beta = 0``: it
    moves no state, and the kept conv rows end at the last real position.
    ``step.ssm_impl`` as ``models/hybrid.py mamba_mixer``'s."""
    from gpustack_tpu.models.transformer import _mm, finish_products
    from gpustack_tpu.ops.delta_rule import (
        delta_chunk_scan,
        delta_state_update,
        delta_step_xla,
        state_heads,
        state_layout,
    )

    B, T, _ = h.shape
    cfg, impl, real = step.cfg, step.ssm_impl, step.real
    H, Dk, Dv = (
        cfg.linear_num_value_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim,
    )
    K, C = cfg.linear_conv_kernel_dim, cfg.linear_conv_dim
    keys_w = H * Dk
    f32 = jnp.float32
    with jax.named_scope("delta_mixer"):
        qkv = jnp.concatenate(
            finish_products(
                carried is not None and T == 1,
                _mm("btd,dk->btk", h, lp["wq"]),
                _mm("btd,dk->btk", h, lp["wk"]),
                _mm("btd,dv->btv", h, lp["wv"]),
            ),
            axis=-1,
        )                                               # [B, T, C]
        beta = jax.nn.sigmoid(_mm("btd,dh->bth", h, lp["wb"]).astype(f32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        def projected(full, low):
            """``h W``, or ``(h W_a) W_b`` through the bottleneck."""
            if not cfg.linear_low_rank:
                return _mm("btd,dn->btn", h, lp[full])
            return _mm(
                "btr,rn->btn", _mm("btd,dr->btr", h, lp[low + "_a"]),
                lp[low + "_b"],
            )

        rate = -jnp.exp(lp["A_log"].astype(f32))         # one a head
        dt = jax.nn.softplus(
            projected("wa", "wf").astype(f32) + lp["dt_bias"].astype(f32)
        )
        if cfg.linear_decay_a_channel:
            # one a head and key channel: [B, T, H, Dk]
            g = rate[:, None] * dt.reshape(B, T, H, Dk)
            g = jnp.where(real[..., None, None], g, 0.0)
        else:
            g = rate * dt
            g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        # the K - 1 rows before the step's first, oldest first
        if carried is not None:
            before = lax.dynamic_index_in_dim(
                carried.conv, i, 0, keepdims=False
            ).reshape(B, K - 1, C)
        else:
            before = jnp.zeros((B, K - 1, C), qkv.dtype)
        window = jnp.concatenate([before.astype(qkv.dtype), qkv], axis=1)
        act = jax.nn.silu(sum(
            window[:, j:j + T].astype(f32) * lp["conv_w"][j].astype(f32)
            for j in range(K)
        ))
        q = act[..., :keys_w].reshape(B, T, H, Dk)
        k = act[..., keys_w:2 * keys_w].reshape(B, T, H, Dk)
        v = act[..., 2 * keys_w:].reshape(B, T, H, Dv)
        q = q * lax.rsqrt(
            jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS
        ) * Dk ** -0.5
        k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
        if carried is None:
            o, _ = delta_chunk_scan(
                q, k, v, g, beta, jnp.zeros((B, H, Dk, Dv), f32)
            )
        else:
            # the last K - 1 rows that count: rows n .. n + K - 2 of the
            # window, n the row's real length
            n = jnp.sum(real, axis=1, dtype=jnp.int32)
            rows = n[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
            kept = jnp.take_along_axis(
                window, rows[..., None], axis=1
            ).reshape(B, (K - 1) * C)
            new_conv = lax.dynamic_update_index_in_dim(
                carried.conv, kept.astype(carried.conv.dtype), i, 0
            )
            if impl == "scan":
                h0 = state_heads(lax.dynamic_index_in_dim(
                    carried.ssm, i, 0, keepdims=False
                ), H)
                o, last = delta_chunk_scan(q, k, v, g, beta, h0)
                new_ssm = lax.dynamic_update_index_in_dim(
                    carried.ssm,
                    state_layout(last).astype(carried.ssm.dtype), i, 0,
                )
            else:
                row = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                if impl == "xla":
                    o, new_ssm = delta_step_xla(carried.ssm, i, *row)
                else:
                    o, new_ssm = delta_state_update(
                        carried.ssm, i, *row, step.alive,
                        interpret=impl == "kernel_interpret",
                    )
                o = o[:, None]
            carried = dataclasses.replace(
                carried, ssm=new_ssm, conv=new_conv
            )
        # the norm a head first, then the gate
        o = o * lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps
        ) * lp["o_norm"].astype(f32)
        gate = projected("wg", "wg")
        gate_act = (
            jax.nn.sigmoid if cfg.linear_gate_act == "sigmoid" else jax.nn.silu
        )
        y = (
            o * gate_act(gate.reshape(B, T, H, Dv).astype(f32))
        ).astype(h.dtype)
        return _mm("btv,vd->btd", y.reshape(B, T, H * Dv), lp["wo"]), carried
