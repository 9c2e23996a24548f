"""Nemotron-H (Nemotron-3-Nano-30B-A3B) forward pass, plain: the
reference the engine's programs are compared with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No cache, no kernel, no
batching, no chunked scan, and nothing imported from
``gpustack_tpu/models``: the layer equations are written out here from
the published description, so a fault in the engine's model code is not
shared. One sequence, one layer at a time (a layer's weights are
dequantised when the layer is computed, so the whole fits a chip).

What it takes from the engine is the **weights** (the engine's own
parameter tree, int8 leaves dequantised here, ``q * s``, so that the
quantisation is part of what is compared) and the hub's ``config.json``
as a dict, with the benchmark's cut: ``experts_held`` (``{"of": E,
"first": id}`` beside ``n_routed_experts`` = how many are held): the
router scores all ``E``, an expert that is not held adds nothing, the
shared expert is added once.

52 layers, each ``x <- x + mixer(rms(x))`` by
``hybrid_override_pattern``, then a final norm and the head:

- ``M``, Mamba-2: ``[z | xBC | dt] = h W_in``; ``xBC <- silu(conv_K(xBC)
  + b)``, the convolution causal and depthwise over the last ``K``
  positions; ``xBC -> x [H, P], B [G, N], C [G, N]`` (head ``h`` uses
  group ``h // (H / G)``); ``delta = softplus(dt + dt_bias)``; ``A =
  -exp(A_log)``; **one position at a time** (``lax.scan`` over time, not
  the chunked form the engine runs): ``S_t = exp(delta_t A) S_{t-1} +
  delta_t x_t (outer) B_t``, ``y_t = S_t C_t + D x_t``; ``y <- rms(y *
  silu(z))`` over groups of ``H P / G`` with one gain over the whole
  width; ``out = y W_out``.
- ``E``: ``y = f * sum_{e in top-k} w_e W_down,e relu(W_up,e h)^2 +
  W_down,s relu(W_up,s h)^2``; ``s = sigmoid(h W_r)`` in float32, the
  ``k`` highest of ``s + bias`` chosen (ties to the lower index), ``w``
  the raw ``s`` of the chosen normalised to sum 1, ``f =
  routed_scaling_factor``.
- ``*``: GQA, ``softmax(q k^T / sqrt(hd)) v`` causal, no bias.

Departures from the published ``config.json``, each an argument so that
it is one line to change (``deployment.json`` lists them under
``assumed``): (a) the router: the file gives the DeepSeek-V3 router's
keys and no rule; sigmoid scores, selection over score + correction
bias (``scoring``); (b) attention takes **no rotary embedding**
(``rotary=False``), as the family's report and public port have it,
though the file carries ``rope_theta``; (c) the state is float32;
(d) ``time_step_limit`` is (0, inf): ``delta`` is not clipped.

``forward(..., routing=...)`` goes behind a program's own routing (each
token to the experts the program sent it to, the weights this file's
own scores at those experts), as ``reference/axk1.py`` does and for its
reason; it reports how far the program's scores are from its own.

``fault`` computes one thing wrongly, on purpose, to measure that the
comparison's limits catch it (``perfbench/check_noise/``). Two of them
are faults of a **padded prefill**, which a reference without padding
cannot make by itself: ``pads=(n, count)`` says that the program ran
``count`` padding tokens (id 0) after the ``n`` of the prompt, and under
those faults the state-space layers take them in where a sound program
skips them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "bf16_state",         # the recurrent state rounded to bf16 a step
    "state_after_bucket",  # state and conv rows taken after the padding
    "conv_from_padding",  # the kept conv rows are the padding's
    "no_d",               # D x left out
    "whole_gate_norm",    # the gated norm over all 4,096, not groups
    "gated_silu",         # experts as silu(up) * up: a gated form
    "no_scaling",         # routed_scaling_factor left out
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
    back: the TPU's compiler may drop such a pair as excess precision
    (the fault ``bf16_state`` written so read exactly the sound
    program's numbers, my chip run, PR 46)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * gain.astype(jnp.float32)


def _eps(hf) -> float:
    return float(hf.get("layer_norm_epsilon") or hf.get("norm_eps", 1e-5))


def mamba(h, lw, at, hf, fault, counts):
    """One Mamba-2 mixer over ``h [T, d]``: ``(out [T, d], the state [H,
    P, N] after the last position)``. ``counts [T]`` bool: False at
    a padding position, which a sound program keeps out of the state and
    out of the convolution's window of later positions."""
    H, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N, K = hf["n_groups"], hf["ssm_state_size"], hf["conv_kernel"]
    inner, T = H * P, h.shape[0]
    zxd = h @ _deq(lw["w_in"], at)
    z, xbc, dt = (
        zxd[:, :inner], zxd[:, inner:inner + inner + 2 * G * N],
        zxd[:, inner + inner + 2 * G * N:],
    )
    # the K - 1 positions before t that count: with padding kept out,
    # position t's j-th predecessor is the j-th counting position before
    # it (all of them, where nothing is padding)
    order = jnp.cumsum(counts) - 1              # rank among the counting
    skip = fault not in ("state_after_bucket", "conv_from_padding")
    conv_w = lw["conv_w"][at].astype(jnp.float32)          # [K, C]
    conv = jnp.zeros_like(xbc) + lw["conv_b"][at].astype(jnp.float32)
    for j in range(K):
        back = K - 1 - j                         # positions before t
        if skip:
            # the row of rank (rank(t) - back) among the counting rows
            rank = order - back
            src = jnp.searchsorted(order, rank, side="left")
            ok = (rank >= 0) & counts
        else:
            src = jnp.arange(T) - back
            ok = src >= 0
        row = jnp.where(ok[:, None], xbc[jnp.clip(src, 0, T - 1)], 0.0)
        conv = conv + row * conv_w[j]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(T, H, P)
    Bm = jnp.repeat(xbc[:, inner:inner + G * N].reshape(T, G, N), H // G, 1)
    Cm = jnp.repeat(xbc[:, inner + G * N:].reshape(T, G, N), H // G, 1)
    delta = jax.nn.softplus(dt + lw["dt_bias"][at].astype(jnp.float32))
    if fault != "state_after_bucket":
        delta = jnp.where(counts[:, None], delta, 0.0)
    A = -jnp.exp(lw["A_log"][at].astype(jnp.float32))

    def step(S, t):
        x_t, B_t, C_t, d_t = t
        S = (
            jnp.exp(d_t * A)[:, None, None] * S
            + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        )
        if fault == "bf16_state":
            S = _as_bf16(S)
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    last, y = jax.lax.scan(
        step, jnp.zeros((H, P, N), jnp.float32), (x, Bm, Cm, delta)
    )
    if fault != "no_d":
        y = y + lw["D"][at].astype(jnp.float32)[:, None] * x
    y = y.reshape(T, inner) * jax.nn.silu(z)
    groups = 1 if fault == "whole_gate_norm" else G
    y = y.reshape(T, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + _eps(hf))
    y = y.reshape(T, inner) * lw["gate_norm"][at].astype(jnp.float32)
    return y @ _deq(lw["w_out"], at), last


def _relu2(x, up, down, fault=""):
    u = x @ up
    if fault == "gated_silu":
        return (jax.nn.silu(u) * u) @ down
    return jnp.square(jax.nn.relu(u)) @ down


def experts(h, lw, at, hf, fault, theirs=None, scoring="sigmoid"):
    """``(y [T, d], largest difference between the program's router
    scores and this function's own, 0 without ``theirs``)``."""
    assert scoring == "sigmoid", scoring
    share = hf.get("experts_held") or {}
    held, first = hf["n_routed_experts"], int(share.get("first", 0))
    k = hf["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ lw["router"][at].astype(jnp.float32))
    if theirs is None:
        _, chosen = jax.lax.top_k(
            scores + lw["router_bias"][at].astype(jnp.float32), k
        )
        score_err = jnp.float32(0.0)
    else:
        chosen, logits = theirs
        score_err = jnp.max(jnp.abs(
            jax.nn.sigmoid(logits.astype(jnp.float32)) - scores
        ))
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        y_e = _relu2(
            h, _deq(lw["we_up"], at + (e,)), _deq(lw["we_down"], at + (e,)),
            fault,
        )
        return y + w_e[:, None] * y_e

    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    f = 1.0 if fault == "no_scaling" else float(hf["routed_scaling_factor"])
    shared = _relu2(h, _deq(lw["ws_up"], at), _deq(lw["ws_down"], at), fault)
    return shared + f * y, score_err


def attention(h, lw, at, hf, visible, rotary=False):
    """GQA over ``h [T, d]``; ``visible [T]`` bool: a padding position
    is no key for the positions after the padding (the program's rows
    there are overwritten before any of them attends)."""
    T = h.shape[0]
    Hq, Hkv, hd = (
        hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    )
    q = (h @ _deq(lw["wq"], at)).reshape(T, Hkv, Hq // Hkv, hd)
    k = (h @ _deq(lw["wk"], at)).reshape(T, Hkv, hd)
    v = (h @ _deq(lw["wv"], at)).reshape(T, Hkv, hd)
    if rotary:
        half = hd // 2
        inv = 1.0 / float(hf["rope_theta"]) ** (jnp.arange(half) / half)
        ang = jnp.arange(T)[:, None] * inv

        def rot(a):
            a1, a2 = a[..., :half], a[..., half:]
            c = jnp.cos(ang).reshape(T, *([1] * (a.ndim - 2)), half)
            s = jnp.sin(ang).reshape(T, *([1] * (a.ndim - 2)), half)
            return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)

        q, k = rot(q), rot(k)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    t_, s_ = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    # a key counts for a query at or after it, unless the key is padding
    # and the query is past the padding
    ok = (s_ <= t_) & (visible[None, :] | ~visible[:, None])
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, Hq * hd)
    return o @ _deq(lw["wo"], at)


def _layer(x, lw, at, kind, hf, fault, counts, theirs, rotary):
    """``(x + mixer(rms(x)), what the kind adds: M its last state, E its
    router-score difference from ``theirs``, * nothing)``."""
    h = _rms(x, lw["norm"][at], _eps(hf))
    more = None
    if kind == "M":
        y, more = mamba(h, lw, at, hf, fault, counts)
    elif kind == "E":
        y, more = experts(h, lw, at, hf, fault, theirs)
    else:
        y = attention(h, lw, at, hf, counts, rotary)
    return x + y, more


_layer_jit = jax.jit(
    _layer, static_argnames=("kind", "hf", "fault", "rotary")
)

_STACK = {"M": "ssm_layers", "E": "moe_layers", "*": "attn_layers"}


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    routing=None,
    fault: str = "",
    pads: Optional[Tuple[int, int]] = None,
    rotary: bool = False,
    states=None,
) -> Tuple[jax.Array, Dict[str, float]]:
    """``(logits [len(want), vocab] float32 at the positions ``want`` of
    the one sequence ``tokens``, readings)``. ``routing``: ``(chosen
    [L_E, T, k], router logits [L_E, T, E])``, the program's own for
    these tokens; ``readings["score_err"]`` is the largest difference
    between its scores and this file's own (0.0 without). ``states``
    ``[L_M, H, P, N]``: a program's recurrent state after the last of
    ``tokens``. ``readings["state_err"]``: a head's error is ``|theirs
    - ours| / |ours|`` (Frobenius over ``[P, N]``), the largest of any
    layer and head (0.0 without). ``readings["state_narrow"]``: the
    largest share, of any layer, of the state's numbers that bf16 holds
    exactly, which is what a state **kept** in bf16 reads 1.0 in and a
    float32 one about 2^-16: the program's state, or under the fault
    ``bf16_state`` this file's own, which stands for such a program's.

    ``pads = (n, count)``: ``count`` padding tokens (id 0) stand after
    the first ``n`` tokens, as in the program's padded prefill; ``want``
    and ``routing`` still count positions without them. They pass
    through every layer as rows, are no keys for what follows them, and
    a sound state-space layer skips them (then the result is the one
    without ``pads``, which only the two padding faults tell apart)."""
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
    score_err = state_err = state_narrow = 0.0
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        if hasattr(embed, "q"):
            x = embed.q[ids].astype(jnp.float32) * embed.s[ids].astype(
                jnp.float32
            )[:, None]
        else:
            x = embed[ids].astype(jnp.float32)
        index = {"M": 0, "E": 0, "*": 0}
        for kind in hf["hybrid_override_pattern"]:
            i = index[kind]
            index[kind] += 1
            theirs = None
            if kind == "E" and routing is not None:
                # the program's choice at the real positions; a padding
                # row (whose result nothing reads) routes for itself
                chosen = jnp.zeros(
                    (ids.shape[0],) + routing[0].shape[2:], jnp.int32
                ).at[where].set(routing[0][i])
                logits = jnp.zeros(
                    (ids.shape[0],) + routing[1].shape[2:], jnp.float32
                ).at[where].set(routing[1][i])
                theirs = (chosen, logits)
            x, more = _layer_jit(
                x, tree[_STACK[kind]], (i,), kind=kind, hf=frozen,
                fault=fault, counts=counts, theirs=theirs, rotary=rotary,
            )
            if theirs is not None and pads is None:
                # (a padding row's scores are not the program's)
                score_err = max(score_err, float(more))
            if kind == "M" and states is not None:
                theirs_s = states[i].astype(jnp.float32)
                # a head at a time: a slowly forgetting head is where a
                # state kept in fewer bits drifts, and a layer's norm
                # hides one head among 64
                state_err = max(state_err, float(jnp.max(
                    jnp.linalg.norm(theirs_s - more, axis=(1, 2))
                    / jnp.linalg.norm(more, axis=(1, 2))
                )))
                kept = more if fault == "bf16_state" else theirs_s
                state_narrow = max(
                    state_narrow, float(jnp.mean(_as_bf16(kept) == kept))
                )
        x = _rms(x[where[jnp.asarray(want, jnp.int32)]], tree["final_norm"],
                 _eps(hf))
        logits = x @ _deq(tree["lm_head"])
    return logits, {
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
