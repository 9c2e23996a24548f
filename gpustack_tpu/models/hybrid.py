"""The Mamba-2 mixer, and the Nemotron-H hybrid that is made of it.

:func:`mamba_mixer` is the one Mamba-2 state-space mixer, for both ways
a stack can hold it: Nemotron-H's one mixer a layer
(:func:`forward_hybrid` below, ``cfg.layer_kinds``) and a stack whose
every layer is a mixer by kind and then an MLP (``cfg.layer_types``'
``"mamba"``, Granite 4.0-H: :func:`mamba_layer`, which
``transformer.scan_periods`` calls where it calls ``models/delta.py``'s
mixer for a delta-rule layer; the layer's norms, residual adds and MLP
are ``transformer.after_mixer``'s).

The Nemotron-H hybrid: one mixer a layer, three kinds of layer.
``cfg.layer_kinds`` names each layer's mixer: ``"M"`` a Mamba-2
state-space mixer, ``"E"`` a mixture of two-matrix ``relu2`` experts
with a shared expert, ``"*"`` grouped-query attention without rotary
embedding. A layer is ``x <- x + mixer(rms_norm(x))``. The weights are
three stacks, one a kind (``ssm_layers``, ``moe_layers``,
``attn_layers``, each ``[L_kind, ...]`` and drawn a leaf at a time), and
the layers are visited in the pattern's order, each reading its own
stack at its index within its kind.

What a slot keeps (``transformer.KVCache``): rows a position for the
attention layers only (``k, v [L_*, B, S, Hkv, hd]``), and for every
state-space layer a recurrent state ``ssm [L_M, B, H, P, N]`` (float32,
as the family's serving notes ask) and the last ``conv_kernel - 1`` rows
of ``xBC`` before its causal convolution, ``conv [L_M, B, (K-1) * C]``
(the rows side by side on the lanes: as ``[.., K-1, C]`` the TPU pads
the three rows to a tile of sixteen). A step's mixers read their rows
out of ``conv`` as the step received it and the step writes the stack
once, after its last layer.

``transformer.forward`` hands a model with ``layer_kinds``, and the
Step it made, to :func:`forward_hybrid`; no other model's layer loop
passes through here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from gpustack_tpu.models.config import ModelConfig

STACKS = {"M": "ssm_layers", "E": "moe_layers", "*": "attn_layers"}


def init_hybrid_layers(cfg: ModelConfig, key: jax.Array, dtype) -> Dict[str, Any]:
    """The three stacks, random, each leaf drawn whole at its own depth
    (a stack drawn at the model's depth and cut is materialised in
    float32 before its slices: ``transformer.init_params``)."""
    d = cfg.hidden_size
    keys = iter(jax.random.split(key, 16))

    def w(*shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * scale
        ).astype(dtype)

    Lm, Le, La = (cfg.layers_of(k) for k in "ME*")
    out: Dict[str, Any] = {}
    if Lm:
        out["ssm_layers"] = {
            "norm": jnp.ones((Lm, d), dtype),
            **init_mamba_layers(cfg, Lm, w, keys, dtype),
        }
    if Le:
        fm, E, Eh = (
            cfg.moe_intermediate_size, cfg.num_experts, cfg.num_held_experts
        )
        fs = cfg.shared_expert_intermediate_size
        out["moe_layers"] = {
            "norm": jnp.ones((Le, d), dtype),
            "router": w(Le, d, E),
            "router_bias": jnp.zeros((Le, E), jnp.float32),
            "we_up": pad_expert_width(w(Le, Eh, d, fm), -1),
            "we_down": pad_expert_width(w(Le, Eh, fm, d), -2),
        }
        if fs:
            out["moe_layers"]["ws_up"] = w(Le, d, fs)
            out["moe_layers"]["ws_down"] = w(Le, fs, d)
    if La:
        out["attn_layers"] = {
            "norm": jnp.ones((La, d), dtype),
            "wq": w(La, d, cfg.q_dim),
            "wk": w(La, d, cfg.kv_dim),
            "wv": w(La, d, cfg.kv_dim),
            "wo": w(La, cfg.q_dim, d),
        }
    return out


def init_mamba_layers(cfg: ModelConfig, Lm: int, w, keys, dtype):
    """``Lm`` Mamba-2 mixers' leaves, random, each drawn whole at its
    depth by ``w(*shape, scale=)`` and from ``keys`` (an iterator; five
    are taken): what either kind of stack holds a mixer (a stack of one
    mixer a layer adds the layer's norm)."""
    d, f32 = cfg.hidden_size, jnp.float32
    H, inner, conv = cfg.mamba_num_heads, cfg.mamba_inner, cfg.mamba_conv_dim
    return {
        # [z | xBC | dt]
        "w_in": w(Lm, d, inner + conv + H),
        "conv_w": w(Lm, cfg.conv_kernel, conv, scale=0.5).astype(f32),
        "conv_b": jnp.zeros((Lm, conv), f32),
        # dt around softplus^-1 of 0.001..0.1, A in -(1..16), as the
        # family initialises them: a state that neither dies in a
        # step nor never forgets
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(
            jax.random.uniform(
                next(keys), (Lm, H), f32,
                math.log(1e-3), math.log(1e-1),
            )
        ))),
        "A_log": jnp.log(
            jax.random.uniform(next(keys), (Lm, H), f32, 1.0, 16.0)
        ),
        "D": jnp.ones((Lm, H), f32),
        "gate_norm": jnp.ones((Lm, inner), dtype),
        "w_out": w(Lm, inner, d),
    }


def pad_expert_width(w: jax.Array, axis: int) -> jax.Array:
    """An expert matrix with its intermediate width (``axis``) filled up
    with zeros to whole lane tiles of 128 (1,856 -> 1,920). The TPU
    stores a last dimension in tiles of 128, so row-major the columns
    take that room anyway; left at 1,856 it stores the array transposed
    instead, which the experts' kernels cannot read in place: the decode
    program then copied all 1.8 GB of ``we_up`` into the other layout
    every step (compiled for a described v5e, PR 46). A zero column
    gives ``relu(0)^2 = 0`` into a zero row of the down matrix: the
    result is the published width's, bit for bit."""
    pad = -w.shape[axis] % 128
    if not pad:
        return w
    widths = [(0, 0)] * w.ndim
    widths[axis] = (0, pad)
    return jnp.pad(w, widths)


def ssm_update_impl(rows: int, platform: str, mesh) -> str:
    """How a step of ``rows`` tokens a slot moves the recurrent state,
    of either kind (``ModelConfig.state_mixer``): ``"kernel"`` (``ops/
    ssm.py ssm_state_update``, ``ops/delta_rule.py delta_state_update``:
    the stacked state in place, live slots only) for a decode step on
    one TPU chip; the chunked form for several rows a slot; ``"xla"``
    for one row anywhere else."""
    one_chip = platform == "tpu" and (mesh is None or mesh.size == 1)
    if rows > 1:
        return "scan"
    return "kernel" if one_chip else "xla"


def grouped_rms_norm(y, gate, w, eps: float, groups: int):
    """``rms_norm(y * silu(gate))`` over ``groups`` equal groups of the
    last axis, each with its own mean of squares, then one gain over the
    whole width (the family's ``MambaRMSNormGated``, ``norm_before_gate``
    false)."""
    f32 = jnp.float32
    h = y.astype(f32) * jax.nn.silu(gate.astype(f32))
    lead, width = h.shape[:-1], h.shape[-1]
    hg = h.reshape(*lead, groups, width // groups)
    hg = hg * lax.rsqrt(jnp.mean(hg * hg, axis=-1, keepdims=True) + eps)
    return hg.reshape(*lead, width).astype(y.dtype) * w


def mamba_mixer(
    h: jax.Array,       # [B, T, D], behind whatever norm stands before it
    lp,                 # the layer's leaves
    carried,            # the cache (None: from zeros, nothing kept)
    i: jax.Array,       # int32: the layer's index among the Mamba-2 layers
    step,               # transformer.Step: cfg, ssm_impl, real, alive
):
    """One Mamba-2 mixer: ``(out [B, T, D], carried, kept)``, under the
    scope ``ssm_mixer`` wherever it is called from. ``[z | xBC | dt] = h
    W_in``; ``xBC`` through a causal depthwise convolution of ``K`` taps
    with bias, then ``silu``; ``xBC -> x [H, P], B [G, N], C [G, N]``;
    ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the rule
    itself is ``ops/ssm.py``'s; ``y + D x`` through the gated norm over
    ``G`` groups (:func:`grouped_rms_norm`) and ``W_out``. A padded
    position (``real`` False) has ``dt = 0``: it moves no state, and the
    kept conv rows end at the last real position.

    The state is moved in the stack (``carried.ssm``; the kernel is
    aliased). The layer's rows before its convolution are read from
    ``carried.conv``, which is handed on as it was received: the rows
    the layer leaves, ``kept [B, (K - 1) * conv_dim]`` (None without a
    cache), are the caller's to place, since the two callers differ in
    kind (:func:`mamba_layer` for a scan over the layers, whose carry
    XLA updates in place; :func:`forward_hybrid`, which writes the
    stack once a step: an update a layer of an unrolled program carried
    all of it through the chip's second memory every layer).
    ``step.ssm_impl`` (``"scan" | "xla" | "kernel" |
    "kernel_interpret"``) says how a step over a cache moves the state."""
    from gpustack_tpu.models import transformer as tf
    from gpustack_tpu.ops.ssm import (
        ssm_chunk_scan,
        ssm_state_update,
        ssm_step_xla,
    )

    B, T, _ = h.shape
    cfg, impl, real = step.cfg, step.ssm_impl, step.real
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.mamba_n_groups, cfg.ssm_state_size
    inner, conv_dim, K = cfg.mamba_inner, cfg.mamba_conv_dim, cfg.conv_kernel
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    f32 = jnp.float32
    eps = cfg.rms_norm_eps
    with jax.named_scope("ssm_mixer"):
        zxd = tf._mm("btd,df->btf", h, lp["w_in"])
        z = zxd[..., :inner]
        xbc = zxd[..., inner:inner + conv_dim]
        dt = jax.nn.softplus(
            zxd[..., inner + conv_dim:].astype(f32) + lp["dt_bias"]
        )
        dt = jnp.where(real[..., None], dt, 0.0)
        A = -jnp.exp(lp["A_log"].astype(f32))
        # the K - 1 rows before the step's first, oldest first
        if carried is not None:
            before = lax.dynamic_index_in_dim(
                carried.conv, i, 0, keepdims=False
            ).reshape(B, K - 1, conv_dim)
        else:
            before = jnp.zeros((B, K - 1, conv_dim), xbc.dtype)
        window = jnp.concatenate([before.astype(xbc.dtype), xbc], axis=1)
        conv = lp["conv_b"].astype(f32) + sum(
            window[:, j:j + T].astype(f32) * lp["conv_w"][j].astype(f32)
            for j in range(K)
        )
        xbc_a = jax.nn.silu(conv).astype(dtype)
        xs = xbc_a[..., :inner].reshape(B, T, H, P)
        Bm = xbc_a[..., inner:inner + G * N].reshape(B, T, G, N)
        Cm = xbc_a[..., inner + G * N:].reshape(B, T, G, N)
        kept = None
        if carried is not None:
            # the last K - 1 rows that count: rows n .. n + K - 2 of
            # the window, n the row's real length
            n = jnp.sum(real, axis=1, dtype=jnp.int32)
            rows = n[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
            kept = jnp.take_along_axis(
                window, rows[..., None], axis=1
            ).reshape(B, (K - 1) * conv_dim).astype(carried.conv.dtype)
        if carried is None:
            y, _ = ssm_chunk_scan(
                xs, dt, A, Bm, Cm, jnp.zeros((B, H, P, N), f32),
                cfg.ssm_chunk_size,
            )
        elif impl == "scan":
            h0 = lax.dynamic_index_in_dim(
                carried.ssm, i, 0, keepdims=False
            )
            y, last = ssm_chunk_scan(
                xs, dt, A, Bm, Cm, h0, cfg.ssm_chunk_size
            )
            new_ssm = lax.dynamic_update_index_in_dim(
                carried.ssm, last.astype(carried.ssm.dtype), i, 0
            )
        elif impl == "xla":
            y, new_ssm = ssm_step_xla(
                carried.ssm, i, xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0]
            )
            y = y[:, None]
        else:
            y, new_ssm = ssm_state_update(
                carried.ssm, i, xs[:, 0], dt[:, 0], A, Bm[:, 0],
                Cm[:, 0], step.alive,
                interpret=impl == "kernel_interpret",
            )
            y = y[:, None]
        if carried is not None:
            carried = dataclasses.replace(carried, ssm=new_ssm)
        y = y + lp["D"].astype(f32)[:, None] * xs.astype(f32)
        y = grouped_rms_norm(
            y.astype(dtype).reshape(B, T, inner), z, lp["gate_norm"],
            eps, G,
        )
        return tf._mm("btf,fd->btd", y, lp["w_out"]), carried, kept


def rows_read_a_layer(conv, x):
    """``conv`` as it is, behind a barrier that ties the stacked rows to
    a layer's input ``x`` (which goes on as it was: a barrier in its own
    way changes what XLA fuses round it), so that each layer of an
    unrolled program fetches its own rows when it runs (1.2 MB of the
    cell's 27). Left alone, XLA takes the 23 reads of one array for
    siblings and cuts every layer's rows out in one fusion before the
    first layer: the whole stack fetched into the chip's second memory,
    split into 23 arrays, most of them written out and fetched again
    (compiled for a described v5e, PR 64)."""
    return lax.optimization_barrier((conv, x))[0]


def mamba_layer(h, lp, carried, i, step):
    """:func:`mamba_mixer` as a layer of a scan over the layers
    (``cfg.layer_types``' ``"mamba"``): ``(out, carried)``, the rows the
    mixer leaves written into the scan's carry, which XLA updates in
    place."""
    out, carried, kept = mamba_mixer(h, lp, carried, i, step)
    if carried is not None:
        carried = dataclasses.replace(
            carried,
            conv=lax.dynamic_update_index_in_dim(carried.conv, kept, i, 0),
        )
    return out, carried


def experts_layer(h, lp, i, step):
    """One layer of two-matrix ``relu2`` experts with a shared expert
    (``"E"``): ``(out, held pairs, experts read, routing)``, the counts
    0 and the routing None unless the Step asks."""
    from gpustack_tpu.models.transformer import _moe_mlp

    stacked = step.stacked
    shared = (
        (None, lp["ws_up"], lp["ws_down"], None) if "ws_up" in lp else None
    )
    out = _moe_mlp(
        h, lp["router"], None,
        stacked.get("we_up", lp.get("we_up")),
        stacked.get("we_down", lp.get("we_down")), step.cfg,
        router_bias=lp.get("router_bias"), shared=shared,
        dispatch=step.moe_dispatch_impl,
        layer=i if stacked else None,
        count_held=step.count_held_pairs, routing_out=step.routing_out,
        live=step.live, count_read=step.count_experts_read,
    )
    out, *extras = out if isinstance(out, tuple) else (out,)
    routing = extras.pop() if step.routing_out else None
    held = extras.pop(0) if step.count_held_pairs else jnp.int32(0)
    read = extras.pop(0) if step.count_experts_read else jnp.int32(0)
    return out, held, read, routing


def attention_layer(h, lp, carried, i, step):
    """One GQA layer without rotary embedding (``"*"``): ``(out,
    carried)``. Its queries go grouped from the projection, in one
    reshape where ``transformer.gqa_attention`` has two: the same values
    and another lowered text (ROADMAP C17)."""
    from gpustack_tpu.models import transformer as tf

    cfg, B, T = step.cfg, step.B, step.T
    q, k, v = tf.qkv_projections(h, lp, decode=carried is not None and T == 1)
    q = q.reshape(B, T, cfg.num_kv_heads, cfg.group_size, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if carried is None:
        attn = tf._attend(q, k, v, step.mask, step.scale)
    else:
        attn, new_k, new_v = tf.attend_over_cache(
            q, k, v, carried.k, carried.v, i, step.positions[:, 0],
            positions=step.positions, mask=step.mask, scale=step.scale,
            decode_attn_impl=step.decode_attn_impl, walk=step.walk,
            attn_impl=step.attn_impl, mesh=step.mesh,
        )
        carried = dataclasses.replace(carried, k=new_k, v=new_v)
    return tf._mm("btq,qd->btd", attn.reshape(B, T, -1), lp["wo"]), carried


def forward_hybrid(params, step, x: jax.Array, cache=None):
    """``transformer.forward``'s driver for a model with ``layer_kinds``:
    the layers in the pattern's order from the embedded tokens ``x``,
    under the Step ``forward`` made: ``(x, cache, extras)``.

    With a cache a state-space layer starts from the cache's state of
    its slot (zeros in a fresh prefill cache) and leaves its new one
    there; without one every layer starts from zeros and keeps nothing.
    A state and its kept ``xBC`` rows end after a row's real positions
    (``step.real``) and not after the padding of a bucket; attention
    needs nothing of the kind (a padded row is above every real query).
    """
    from gpustack_tpu.models import transformer as tf

    cfg, stacked, eps = step.cfg, step.stacked, step.cfg.rms_norm_eps
    moe = params.get("moe_layers", {})

    def at(stack, i):
        """Layer ``i``'s leaves of a stack, without the matrices that go
        to a kernel whole."""
        return {
            k: jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                w,
            )
            for k, w in stack.items() if k not in stacked
        }

    # One function a kind of layer, traced once: the layers are visited in
    # a Python loop (every layer's operations are in the program, which
    # XLA inlines), but each kind is a jitted function called with its
    # stack and the layer's index in it, so the 23 mixers are one trace
    # and one lowered function, not 23. A ``lax.scan`` over the layers
    # with a ``lax.switch`` over the kind lowers faster still and is not
    # taken: compiled for a described v5e, XLA copies the stacked state
    # whole through the conditional every step (0.72 GB of temporaries
    # against 0.09 GB, a ``copy`` of ``f32[23, 32, 64, 64, 128]``).
    def m_layer(x, carried, stack, i):
        lp = at(stack, i)
        out, carried, kept = mamba_mixer(
            tf.rms_norm(x, lp["norm"], eps), lp, carried, i, step
        )
        return x + out, carried, kept

    def e_layer(x, stack, i):
        lp = at(stack, i)
        out, *more = experts_layer(
            tf.rms_norm(x, lp["norm"], eps), lp, i, step
        )
        return (x + out, *more)

    def a_layer(x, carried, stack, i):
        lp = at(stack, i)
        out, carried = attention_layer(
            tf.rms_norm(x, lp["norm"], eps), lp, carried, i, step
        )
        return x + out, carried

    m_layer, e_layer, a_layer = map(jax.jit, (m_layer, e_layer, a_layer))
    held = read = jnp.int32(0)
    routings = []
    # every mixer reads its rows from ``cache.conv`` as the step received
    # it, and the layers' new rows go into it in one write after the last
    conv_rows = []
    index = {"M": 0, "E": 0, "*": 0}
    for kind in cfg.layer_kinds:
        i = jnp.int32(index[kind])
        index[kind] += 1
        if kind == "M":
            if cache is not None:
                cache = dataclasses.replace(
                    cache, conv=rows_read_a_layer(cache.conv, x)
                )
            x, cache, kept = m_layer(x, cache, params["ssm_layers"], i)
            conv_rows.append(kept)
        elif kind == "*":
            x, cache = a_layer(x, cache, params["attn_layers"], i)
        else:
            x, n_held, n_read, routing = e_layer(x, moe, i)
            held, read = held + n_held, read + n_read
            if step.routing_out:
                routings.append(routing)

    if cache is not None and conv_rows:
        cache = dataclasses.replace(cache, conv=jnp.stack(conv_rows))
    extras = []
    if step.count_held_pairs:
        extras.append(held)
    if step.count_experts_read:
        extras.append(read)
    if step.routing_out:
        extras.append(tuple(jnp.stack(r) for r in zip(*routings)))
    return x, cache, extras
