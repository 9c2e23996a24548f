"""Solar-Open2 (``solar_open2``) forward pass, plain: the reference the
engine's programs are compared with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No cache, no kernel, no
batching, no chunked form, and nothing imported from
``gpustack_tpu/models``, ``gpustack_tpu/ops`` or another reference: the
layer equations are written out here from the published descriptions
(KDA: Kimi Linear, arXiv:2510.26692, the gated delta rule of
arXiv:2412.06464 with one decay a key channel; negative eigenvalues,
arXiv:2411.12537; the attention's output gate, arXiv:2505.06708; the
router, DeepSeek-V3's), so a fault in the engine's model code is not
shared. One sequence, one layer at a time, an expert at a time (a
layer's weights are dequantised when the layer is computed, so the whole
fits a chip beside the engine's own tree).

What it takes from the engine is the **weights** (the engine's own
parameter tree, int8 leaves dequantised here, ``q * s``, so that the
quantisation is part of what is compared) and the hub's ``config.json``
as a dict.

Every layer is ``x += Mixer(rms_norm(x))``, then ``x +=
Experts(rms_norm(x))``; the layers ``gqa_layers`` lists are attention,
the others KDA:

- **KDA** (``H`` heads of ``D`` = ``linear_attn_config.head_dim``, keys
  and values alike): ``q~ = h Wq``, ``k~ = h Wk``, ``v~ = h Wv``; each
  through its own causal depthwise convolution of ``K`` taps, no bias,
  then ``silu``. A head at a time ``q = l2norm(q') / sqrt(D)``, ``k =
  l2norm(k')`` (eps 1e-6), ``v = v'``. ``beta = 2 sigmoid(h Wb)`` (the 2
  is ``kda_allow_neg_eigval``), one a head. ``g = -exp(A_log[head])
  softplus((h Wf_a) Wf_b + dt_bias)``, one a head **and key channel**.
  The state ``S [D, D]`` a head, zeros before position 0, **one position
  at a time** (``lax.scan`` over time): ``S_t = diag(exp(g_t)) S_{t-1}``,
  ``S_t += k_t (outer) beta_t (v_t - S_t^T k_t)``, ``o_t = S_t^T q_t``.
  Then ``y = rms_norm(o_t; w_o) sigmoid((h Wg_a) Wg_b)`` a head (the norm
  first, then the gate), ``out = y Wo``.
- **attention**: ``q, k, v = h Wq, h Wk, h Wv`` (``Hq`` / ``Hkv`` /
  ``Hkv`` heads), no rotary embedding, no norm on q and k, causal softmax
  a head at ``1 / sqrt(head_dim)``; ``out = (o sigmoid(h W_gate)) Wo``,
  the gate elementwise over all ``Hq * head_dim`` channels (a ``W_gate``
  only ``Hq`` wide is one gate a head: the width of the tree's leaf
  says which).
- **experts**: ``s = sigmoid(h Wr)`` over all ``E`` published experts;
  the ``k`` largest of ``s + b``; weights ``s_e / sum of the chosen s``
  times ``routed_scaling_factor``; ``y = sum_e w_e down_e(silu(gate_e h)
  up_e h)`` over the experts **held** (``held = (first id, how many)``,
  from ``experts_held`` and ``n_routed_experts`` where not given: an
  absent expert adds nothing) plus the shared expert, once.

What the hub file does not settle is an argument of :func:`forward` or
follows the tree's shapes, so that the other reading is one call away
(``deployment.json`` lists each under ``assumed``): ``rotary`` (assumed
False, ``use_rope``), ``scoring`` (assumed ``"sigmoid"`` with the
correction bias; ``"softmax"``), ``held``; the bottleneck's rank, the
shared expert's width and the gate's width are the leaves'.

``fault`` computes one thing wrongly, on purpose, to measure that the
comparison's limits catch it (``perfbench/check_noise/``). One is a
fault of a **padded prefill**: ``pads=(n, count)`` says that the program
ran ``count`` padding tokens (id 0) after the ``n`` of the prompt, and
under ``state_after_bucket`` the KDA layers take them in where a sound
program skips them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "bf16_state",          # the state rounded to bf16 a step
    "decay_a_head",        # one decay a head: the mean of its channels'
    "beta_not_doubled",    # beta = sigmoid(.), eigenvalues in (0, 1)
    "gate_before_norm",    # rms_norm(o * gate), not norm then gate
    "silu_gate",           # the KDA gate under silu, not a sigmoid
    "attn_gate_left_out",  # attention's output without its gate
    "rotary",              # rotary embedding applied to q and k
    "softmax_scores",      # softmax router scores, not sigmoid
    "no_l2norm",           # q, k as the convolution leaves them
    "state_after_bucket",  # state and conv rows taken after the padding
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


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * gain.astype(jnp.float32)


def _l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_mixer(h, lw, at, hf, fault, counts):
    """One KDA mixer over ``h [T, d]``: ``(out [T, d], the state [H, D,
    D] after the last position)``. ``counts [T]`` bool: False at a
    padding position, which a sound program keeps out of the state and
    out of the convolution's window of later positions."""
    linear = hf["linear_attn_config"]
    H, D = int(linear["num_heads"]), int(linear["head_dim"])
    K = int(linear.get("short_conv_kernel_size") or 4)
    T = h.shape[0]
    qkv = jnp.concatenate(
        [h @ _deq(lw[name], at) for name in ("wq", "wk", "wv")], axis=-1
    )
    # the K - 1 positions before t that count: with padding kept out,
    # position t's j-th predecessor is the j-th counting position before
    # it (all of them, where nothing is padding)
    order = jnp.cumsum(counts) - 1
    skip = fault != "state_after_bucket"
    conv_w = lw["conv_w"][at].astype(jnp.float32)           # [K, 3 H D]
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
    q, k, v = (
        act[:, i * H * D:(i + 1) * H * D].reshape(T, H, D) for i in range(3)
    )
    if fault != "no_l2norm":
        q, k = _l2(q), _l2(k)
    q = q / math.sqrt(D)
    beta = jax.nn.sigmoid(h @ _deq(lw["wb"], at))
    if hf.get("kda_allow_neg_eigval") and fault != "beta_not_doubled":
        beta = 2.0 * beta
    # one decay a head and key channel: [T, H, D]
    g = -jnp.exp(lw["A_log"][at].astype(jnp.float32))[:, None] * (
        jax.nn.softplus(
            (h @ _deq(lw["wf_a"], at)) @ _deq(lw["wf_b"], at)
            + lw["dt_bias"][at].astype(jnp.float32)
        ).reshape(T, H, D)
    )
    if fault == "decay_a_head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    if skip:
        g = jnp.where(counts[:, None, None], g, 0.0)
        beta = jnp.where(counts[:, None], beta, 0.0)

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        if fault == "bf16_state":
            S = _as_bf16(S)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    last, o = jax.lax.scan(
        step, jnp.zeros((H, D, D), jnp.float32), (q, k, v, g, beta)
    )
    gate = ((h @ _deq(lw["wg_a"], at)) @ _deq(lw["wg_b"], at)).reshape(T, H, D)
    gate = jax.nn.silu(gate) if fault == "silu_gate" else jax.nn.sigmoid(gate)
    eps = float(hf["rms_norm_eps"])
    if fault == "gate_before_norm":
        y = _rms(o * gate, lw["o_norm"][at], eps)
    else:
        y = _rms(o, lw["o_norm"][at], eps) * gate
    return y.reshape(T, H * D) @ _deq(lw["wo"], at), last


def attention(h, lw, at, hf, visible, fault, rotary):
    """Gated causal attention over ``h [T, d]``; ``visible [T]`` bool: a
    padding position is no key for the positions after the padding."""
    T = h.shape[0]
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // Hq
    q = (h @ _deq(lw["wq"], at)).reshape(T, Hkv, Hq // Hkv, hd)
    k = (h @ _deq(lw["wk"], at)).reshape(T, Hkv, hd)
    v = (h @ _deq(lw["wv"], at)).reshape(T, Hkv, hd)
    if rotary or fault == "rotary":
        half = hd // 2
        ang = jnp.arange(T)[:, None] * (
            1.0 / float(hf.get("rope_theta") or 10000.0)
            ** (jnp.arange(half) / half)
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
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, Hq, hd)
    if hf.get("use_gqa_gate") and fault != "attn_gate_left_out":
        # [T, Hq * hd] elementwise, or [T, Hq] one gate a head
        gate = jax.nn.sigmoid(h @ _deq(lw["wg"], at))
        o = o * gate.reshape(T, Hq, -1)
    return o.reshape(T, Hq * hd) @ _deq(lw["wo"], at)


def experts(h, lw, at, hf, fault, held, theirs=None, scoring="sigmoid"):
    """``(y [T, d], largest difference between the program's router
    scores and this function's own, 0 without ``theirs``)``: the held
    experts' part of the routed sum and the shared expert, once."""
    first, count = held
    k = hf["num_experts_per_tok"]
    logits = h @ lw["router"][at].astype(jnp.float32)
    if fault == "softmax_scores":
        scoring = "softmax"
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        pick = scores + lw["router_bias"][at].astype(jnp.float32)
    else:
        scores = pick = jax.nn.softmax(logits, axis=-1)
    if theirs is None:
        _, chosen = jax.lax.top_k(pick, k)
        score_err = jnp.float32(0.0)
    else:
        chosen, their_logits = theirs
        score_err = jnp.max(jnp.abs(
            jax.nn.sigmoid(their_logits.astype(jnp.float32)) - scores
        ))
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def gated(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * gated(
            h, *(_deq(lw[n], at + (e,)) for n in ("we_gate", "we_up", "we_down"))
        )

    y = jax.lax.fori_loop(0, count, add_expert, jnp.zeros_like(h))
    y = float(hf.get("routed_scaling_factor") or 1.0) * y
    if hf.get("n_shared_experts"):
        y = y + gated(
            h, *(_deq(lw[n], at) for n in ("ws_gate", "ws_up", "ws_down"))
        )
    return y, score_err


def _layer(x, every, mixer_w, at_all, at_kind, kind, hf, fault, counts,
           held, theirs, rotary, scoring):
    """One layer, mixer then experts: ``(x, a KDA layer's last state or
    None, the router's score difference)``."""
    eps = float(hf["rms_norm_eps"])
    h = _rms(x, every["attn_norm"][at_all], eps)
    state = None
    if kind == "kda":
        y, state = kda_mixer(h, mixer_w, at_kind, hf, fault, counts)
    else:
        y = attention(h, mixer_w, at_kind, hf, counts, fault, rotary)
    x = x + y
    y, score_err = experts(
        _rms(x, every["mlp_norm"][at_all], eps), every, at_all, hf, fault,
        held, theirs, scoring,
    )
    return x + y, state, score_err


_layer_jit = jax.jit(
    _layer,
    static_argnames=("kind", "hf", "fault", "held", "rotary", "scoring"),
)

_STACK = {"kda": "delta_layers", "attention": "attn_layers"}


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    routing=None,
    fault: str = "",
    pads: Optional[Tuple[int, int]] = None,
    held: Optional[Tuple[int, int]] = None,
    rotary: Optional[bool] = None,
    scoring: str = "sigmoid",
    states=None,
    hidden: bool = False,
) -> Tuple[jax.Array, Dict[str, float]]:
    """``(logits [len(want), vocab] float32 at the positions ``want`` of
    the one sequence ``tokens``, readings)``; with ``hidden`` the stream
    before the final norm instead of logits (the share test adds up
    layers' outputs).

    ``held = (first id, how many)``: the experts whose weights ``tree``
    holds, the ``how many`` leaves of ``we_*`` standing for ids ``first
    ..``; None: as ``hf`` says (``experts_held.first``,
    ``n_routed_experts``). ``routing``: ``(chosen [L, T, k], router
    logits [L, T, E])``, the program's own for these tokens;
    ``readings["score_err"]`` is the largest difference between its
    scores and this file's own (0.0 without). ``states`` ``[L_kda, H, D,
    D]``: a program's recurrent state after the last of ``tokens``, a
    head at a time. ``readings["state_err"]``: a head's error is
    ``|theirs - ours| / |ours|`` (Frobenius over ``[D, D]``), the largest
    of any layer and head (0.0 without ``states``).
    ``readings["state_narrow"]``: the largest share, of any layer, of the
    state's numbers that bf16 holds exactly, which is what a state
    **kept** in bf16 reads 1.0 in and a float32 one about 2^-16: the
    program's state, or under the fault ``bf16_state`` this file's own,
    which stands for such a program's.

    ``pads = (n, count)``: ``count`` padding tokens (id 0) stand after
    the first ``n`` tokens, as in the program's padded prefill; ``want``
    and ``routing`` still count positions without them. They pass
    through every layer as rows, are no keys for what follows them, and
    a sound KDA layer skips them (then the result is the one without
    ``pads``, which only the padding fault tells apart)."""
    assert fault == "" or fault in FAULTS, fault
    if held is None:
        held = (
            int((hf.get("experts_held") or {}).get("first", 0)),
            int(hf["n_routed_experts"]),
        )
    if rotary is None:
        rotary = bool(hf.get("use_rope"))
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
    full = set(int(i) for i in hf.get("gqa_layers") or ())
    score_err = state_err = state_narrow = 0.0
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        if hasattr(embed, "q"):
            x = embed.q[ids].astype(jnp.float32) * embed.s[ids].astype(
                jnp.float32
            )[:, None]
        else:
            x = embed[ids].astype(jnp.float32)
        index = {"kda": 0, "attention": 0}
        for layer in range(int(hf["num_hidden_layers"])):
            kind = "attention" if layer in full else "kda"
            i = index[kind]
            index[kind] += 1
            theirs = None
            if routing is not None:
                # the program's choice at the real positions; a padding
                # row (whose result nothing reads) routes for itself
                theirs = tuple(
                    jnp.zeros((ids.shape[0],) + r.shape[2:], r.dtype)
                    .at[where].set(r[layer]) for r in routing
                )
            x, last, more = _layer_jit(
                x, tree["layers"], tree[_STACK[kind]], (layer,), (i,),
                kind=kind, hf=frozen, fault=fault, counts=counts,
                held=tuple(held), theirs=theirs, rotary=rotary,
                scoring=scoring,
            )
            if theirs is not None and pads is None:
                # (a padding row's scores are not the program's)
                score_err = max(score_err, float(more))
            if kind == "kda" and states is not None:
                theirs_s = states[i].astype(jnp.float32)
                # a head at a time: a slowly forgetting head is where a
                # state kept in fewer bits drifts, and a layer's norm
                # hides one head among 64
                state_err = max(state_err, float(jnp.max(
                    jnp.linalg.norm(theirs_s - last, axis=(1, 2))
                    / jnp.linalg.norm(last, axis=(1, 2))
                )))
                kept = last if fault == "bf16_state" else theirs_s
                state_narrow = max(
                    state_narrow, float(jnp.mean(_as_bf16(kept) == kept))
                )
        x = x[where[jnp.asarray(want, jnp.int32)]]
        if not hidden:
            x = _rms(x, tree["final_norm"], float(hf["rms_norm_eps"]))
            x = x @ _deq(tree["lm_head"])
    return x, {
        "score_err": score_err, "state_err": state_err,
        "state_narrow": state_narrow,
    }


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
