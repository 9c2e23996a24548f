"""Olmo-Hybrid (``olmo_hybrid``) forward pass, plain: the reference the
engine's programs are compared with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No cache, no kernel, no
batching, no chunked form, and nothing imported from
``gpustack_tpu/models`` or ``gpustack_tpu/ops``: the layer equations are
written out here from the published description of the gated delta rule
(Yang et al., arXiv:2412.06464; negative eigenvalues, Grazzi et al.,
arXiv:2411.12537), so a fault in the engine's model code is not shared.
One sequence, one layer at a time (a layer's weights are dequantised
when the layer is computed, so the whole fits a chip).

What it takes from the engine is the **weights** (the engine's own
parameter tree, int8 leaves dequantised here, ``q * s``, so that the
quantisation is part of what is compared) and the hub's ``config.json``
as a dict.

Every layer is a mixer and then an MLP, the mixer by ``layer_types``;
``h`` is the layer's input behind whatever norm stands before the
sublayer:

- ``linear_attention`` (``H`` heads, key width ``Dk``, value width
  ``Dv``): ``q~ = h Wq``, ``k~ = h Wk``, ``v~ = h Wv``; each through its
  own causal depthwise convolution of ``K`` taps, no bias, then
  ``silu``. A head at a time ``q = l2norm(q') / sqrt(Dk)``, ``k =
  l2norm(k')`` (eps 1e-6), ``v = v'``. ``beta = 2 sigmoid(h Wb)`` (the 2
  is ``linear_allow_neg_eigval``), ``g = -exp(A_log) softplus(h Wa +
  dt_bias)``, ``a = exp(g)``. The state ``S [Dk, Dv]`` a head, zeros
  before position 0, **one position at a time** (``lax.scan`` over
  time): ``S_t = a_t S_{t-1} + k_t (outer) beta_t (v_t - (a_t
  S_{t-1})^T k_t)``, ``o_t = S_t^T q_t``. Then ``y = rms_norm(o_t; w_o)
  silu(h Wg)`` a head (the norm first, then the gate), ``out = y Wo``.
- ``full_attention``: ``q = rms_norm(h Wq; w_q)``, ``k = rms_norm(h Wk;
  w_k)`` over the **whole projection**, before the heads are split; ``v
  = h Wv``; causal softmax attention a head, scores scaled by
  ``1 / sqrt(head_dim)``; ``out = a Wo``. No bias.
- the MLP: ``down(silu(gate(h)) up(h))``.

What the hub file does not settle is an argument, so that a correction
is one line (``deployment.json`` lists each under ``assumed``):
``norm_after`` (the kinds of layer whose two norms stand on each
sublayer's *output* inside the residual, ``x + norm(f(x))``; the others
norm each sublayer's *input*, ``x + f(norm(x))``; assumed
``("full_attention",)``, as the family's earlier models place them) and
``rotary`` (assumed False: ``rope_parameters.rope_theta`` is null and the
convolutions carry position).

``fault`` computes one thing wrongly, on purpose, to measure that the
comparison's limits catch it (``perfbench/check_noise/``). One of them
is a fault of a **padded prefill**, which a reference without padding
cannot make by itself: ``pads=(n, count)`` says that the program ran
``count`` padding tokens (id 0) after the ``n`` of the prompt, and under
that fault the linear layers take them in where a sound program skips
them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "beta_not_doubled",    # beta = sigmoid(.), eigenvalues in (0, 1)
    "no_l2norm",           # q, k as the convolution leaves them
    "no_key_scale",        # the 1 / sqrt(Dk) on q left out
    "decay_after",         # the decay applied after the correction
    "gate_before_norm",    # rms_norm(o * silu(gate)), not norm then gate
    "state_after_bucket",  # state and conv rows taken after the padding
    "qk_norm_a_head",      # q, k normalised a head, not the whole width
    "norms_swapped",       # the two placements of the norms exchanged
    "bf16_state",          # the state rounded to bf16 a step
)


def _deq(w: Any, at: Tuple[int, ...] = ()) -> jax.Array:
    """Leaf ``w`` of the engine's tree at ``at`` on its leading axes, in
    float32; an int8 leaf (``q``, ``s``) has its scales on the last
    axis, ``[..., in, out]`` has ``[..., out]``."""
    if hasattr(w, "q"):
        return w.q[at].astype(jnp.float32) * w.s[at].astype(
            jnp.float32
        )[..., None, :]
    return w[at].astype(jnp.float32)


def _as_bf16(x):
    """``x`` rounded to what bf16 holds, in float32. Not a cast there and
    back: the TPU's compiler may drop such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rms(x, gain, eps):
    return _unit(x, eps) * gain.astype(jnp.float32)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_mixer(h, lw, at, hf, fault, counts):
    """One gated-delta-rule mixer over ``h [T, d]``: ``(out [T, d], the
    state [H, Dk, Dv] after the last position)``. ``counts [T]`` bool:
    False at a padding position, which a sound program keeps out of the
    state and out of the convolution's window of later positions."""
    H, Dk = hf["linear_num_value_heads"], hf["linear_key_head_dim"]
    Dv, K = hf["linear_value_head_dim"], hf["linear_conv_kernel_dim"]
    T = h.shape[0]
    qkv = jnp.concatenate(
        [h @ _deq(lw[name], at) for name in ("wq", "wk", "wv")], axis=-1
    )
    # the K - 1 positions before t that count: with padding kept out,
    # position t's j-th predecessor is the j-th counting position before
    # it (all of them, where nothing is padding)
    order = jnp.cumsum(counts) - 1
    skip = fault != "state_after_bucket"
    conv_w = lw["conv_w"][at].astype(jnp.float32)           # [K, C]
    conv = jnp.zeros_like(qkv)
    for j in range(K):
        back = K - 1 - j
        if skip:
            rank = order - back
            src = jnp.searchsorted(order, rank, side="left")
            ok = (rank >= 0) & counts
        else:
            src = jnp.arange(T) - back
            ok = src >= 0
        row = jnp.where(ok[:, None], qkv[jnp.clip(src, 0, T - 1)], 0.0)
        conv = conv + row * conv_w[j]
    act = jax.nn.silu(conv)
    q = act[:, :H * Dk].reshape(T, H, Dk)
    k = act[:, H * Dk:2 * H * Dk].reshape(T, H, Dk)
    v = act[:, 2 * H * Dk:].reshape(T, H, Dv)
    if fault != "no_l2norm":
        q, k = _l2(q), _l2(k)
    if fault != "no_key_scale":
        q = q / math.sqrt(Dk)
    beta = jax.nn.sigmoid(h @ lw["wb"][at].astype(jnp.float32))
    if hf.get("linear_allow_neg_eigval") and fault != "beta_not_doubled":
        beta = 2.0 * beta
    g = -jnp.exp(lw["A_log"][at].astype(jnp.float32)) * jax.nn.softplus(
        h @ lw["wa"][at].astype(jnp.float32)
        + lw["dt_bias"][at].astype(jnp.float32)
    )
    if skip:
        g = jnp.where(counts[:, None], g, 0.0)
        beta = jnp.where(counts[:, None], beta, 0.0)

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        a = jnp.exp(g_t)[:, None, None]
        if fault == "decay_after":
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
            S = a * (S + k_t[:, :, None] * u[:, None, :])
        else:
            S = a * S
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
            S = S + k_t[:, :, None] * u[:, None, :]
        if fault == "bf16_state":
            S = _as_bf16(S)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    last, o = jax.lax.scan(
        step, jnp.zeros((H, Dk, Dv), jnp.float32), (q, k, v, g, beta)
    )
    gate = jax.nn.silu((h @ _deq(lw["wg"], at)).reshape(T, H, Dv))
    eps = float(hf["rms_norm_eps"])
    if fault == "gate_before_norm":
        y = _rms(o * gate, lw["o_norm"][at], eps)
    else:
        y = _rms(o, lw["o_norm"][at], eps) * gate
    return y.reshape(T, H * Dv) @ _deq(lw["wo"], at), last


def attention(h, lw, at, hf, visible, fault, rotary=False):
    """Full causal attention over ``h [T, d]``; ``visible [T]`` bool: a
    padding position is no key for the positions after the padding."""
    T = h.shape[0]
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // Hq
    eps = float(hf["rms_norm_eps"])
    q, k = h @ _deq(lw["wq"], at), h @ _deq(lw["wk"], at)
    w_q = lw["q_norm"][at].astype(jnp.float32)
    w_k = lw["k_norm"][at].astype(jnp.float32)
    if fault == "qk_norm_a_head":
        q = _unit(q.reshape(T, Hq, hd), eps).reshape(T, Hq * hd) * w_q
        k = _unit(k.reshape(T, Hkv, hd), eps).reshape(T, Hkv * hd) * w_k
    else:
        q, k = _rms(q, w_q, eps), _rms(k, w_k, eps)
    q = q.reshape(T, Hkv, Hq // Hkv, hd)
    k = k.reshape(T, Hkv, hd)
    v = (h @ _deq(lw["wv"], at)).reshape(T, Hkv, hd)
    if rotary:
        half = hd // 2
        theta = float((hf.get("rope_parameters") or {}).get("rope_theta")
                      or 10000.0)
        ang = jnp.arange(T)[:, None] * (
            1.0 / theta ** (jnp.arange(half) / half)
        )

        def rot(a):
            a1, a2 = a[..., :half], a[..., half:]
            c = jnp.cos(ang).reshape(T, *([1] * (a.ndim - 2)), half)
            s = jnp.sin(ang).reshape(T, *([1] * (a.ndim - 2)), half)
            return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)

        q, k = rot(q), rot(k)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    t_, s_ = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = (s_ <= t_) & (visible[None, :] | ~visible[:, None])
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, Hq * hd)
    return o @ _deq(lw["wo"], at)


def _layer(x, every, mixer_w, at_all, at_kind, kind, hf, fault, counts,
           norm_after, rotary):
    """One layer, mixer then MLP: ``(x, a linear layer's last state or
    None)``."""
    eps = float(hf["rms_norm_eps"])
    after = kind in norm_after
    if fault == "norms_swapped":
        after = not after
    n1, n2 = every["attn_norm"][at_all], every["mlp_norm"][at_all]
    h = x if after else _rms(x, n1, eps)
    state = None
    if kind == "linear_attention":
        y, state = delta_mixer(h, mixer_w, at_kind, hf, fault, counts)
    else:
        y = attention(h, mixer_w, at_kind, hf, counts, fault, rotary)
    x = x + (_rms(y, n1, eps) if after else y)
    h = x if after else _rms(x, n2, eps)
    y = (
        jax.nn.silu(h @ _deq(every["w_gate"], at_all))
        * (h @ _deq(every["w_up"], at_all))
    ) @ _deq(every["w_down"], at_all)
    return x + (_rms(y, n2, eps) if after else y), state


_layer_jit = jax.jit(
    _layer, static_argnames=("kind", "hf", "fault", "norm_after", "rotary")
)

_STACK = {"linear_attention": "delta_layers", "full_attention": "attn_layers"}


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    fault: str = "",
    pads: Optional[Tuple[int, int]] = None,
    norm_after: Tuple[str, ...] = ("full_attention",),
    rotary: bool = False,
    states=None,
) -> Tuple[jax.Array, Dict[str, float]]:
    """``(logits [len(want), vocab] float32 at the positions ``want`` of
    the one sequence ``tokens``, readings)``. ``states`` ``[L_lin, H, Dk,
    Dv]``: a program's recurrent state after the last of ``tokens``, a
    head at a time. ``readings["state_err"]``: a head's error is
    ``|theirs - ours| / |ours|`` (Frobenius over ``[Dk, Dv]``), the
    largest of any layer and head (0.0 without ``states``).
    ``readings["state_narrow"]``: the largest share, of any layer, of the
    state's numbers that bf16 holds exactly, which is what a state
    **kept** in bf16 reads 1.0 in and a float32 one about 2^-16: the
    program's state, or under the fault ``bf16_state`` this file's own,
    which stands for such a program's.

    ``pads = (n, count)``: ``count`` padding tokens (id 0) stand after
    the first ``n`` tokens, as in the program's padded prefill; ``want``
    still counts positions without them. They pass through every layer
    as rows, are no keys for what follows them, and a sound linear layer
    skips them (then the result is the one without ``pads``, which only
    the padding fault tells apart)."""
    assert fault == "" or fault in FAULTS, fault
    ids = jnp.asarray(tokens, jnp.int32)
    T = ids.shape[0]
    counts = jnp.ones((T,), bool)
    where = jnp.arange(T)
    if pads is not None and pads[1] > 0:
        n, count = pads
        ids = jnp.concatenate(
            [ids[:n], jnp.zeros((count,), jnp.int32), ids[n:]]
        )
        counts = jnp.concatenate([
            jnp.ones((n,), bool), jnp.zeros((count,), bool),
            jnp.ones((T - n,), bool),
        ])
        where = jnp.where(where < n, where, where + count)
    frozen = _Frozen(hf)
    state_err = state_narrow = 0.0
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        if hasattr(embed, "q"):
            x = embed.q[ids].astype(jnp.float32) * embed.s[ids].astype(
                jnp.float32
            )[:, None]
        else:
            x = embed[ids].astype(jnp.float32)
        index = {"linear_attention": 0, "full_attention": 0}
        for layer, kind in enumerate(hf["layer_types"]):
            i = index[kind]
            index[kind] += 1
            x, last = _layer_jit(
                x, tree["layers"], tree[_STACK[kind]], (layer,), (i,),
                kind=kind, hf=frozen, fault=fault, counts=counts,
                norm_after=tuple(norm_after), rotary=rotary,
            )
            if kind == "linear_attention" and states is not None:
                theirs = states[i].astype(jnp.float32)
                # a head at a time: a slowly forgetting head is where a
                # state kept in fewer bits drifts, and a layer's norm
                # hides one head among thirty
                state_err = max(state_err, float(jnp.max(
                    jnp.linalg.norm(theirs - last, axis=(1, 2))
                    / jnp.linalg.norm(last, axis=(1, 2))
                )))
                kept = last if fault == "bf16_state" else theirs
                state_narrow = max(
                    state_narrow, float(jnp.mean(_as_bf16(kept) == kept))
                )
        x = _rms(x[where[jnp.asarray(want, jnp.int32)]], tree["final_norm"],
                 float(hf["rms_norm_eps"]))
        logits = x @ _deq(tree["lm_head"])
    return logits, {"state_err": state_err, "state_narrow": state_narrow}


class _Frozen(dict):
    """The configuration as a hashable, so that it is static under
    ``jit``."""

    def __hash__(self):
        return hash(_freeze(self))

    def __eq__(self, other):
        return _freeze(self) == _freeze(other)


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return x
