"""Granite 4.0-H (``granitemoehybrid`` with ``num_local_experts: 0``)
forward pass, plain: the reference the engine's programs are compared
with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No cache, no kernel, no
batching, no chunked scan: the recurrence is a **plain scan over the
positions**, one at a time. Nothing is imported from
``gpustack_tpu/models`` nor from the other references: the layer
equations are written out here from the published ``config.json`` and
the family's public port, so a fault in the engine's model code is not
shared. One sequence, one layer at a time (a layer's weights are
dequantised when the layer is computed, so the whole fits a chip).

What it takes from the engine is the **weights** (the engine's own
parameter tree, int8 leaves dequantised here, ``q * s``, so that the
quantisation is part of what is compared) and the hub's ``config.json``
as a dict. ``r`` is ``residual_multiplier``::

    x0      = embed[tokens] * embedding_multiplier
    layer:  h = rms_norm(x, w_in);   m = mamba(h) or attention(h)
            x = x + r * m
            h = rms_norm(x, w_post)
            x = x + r * ((silu(h W_gate) * (h W_up)) W_down)
    logits  = (rms_norm(x, w_f) @ embed.T) / logits_scaling      # tied

    mamba(h):  z | xBC | dt = h W_inproj
               xBC = silu(causal_conv1d(xBC, K taps, bias))
               xs | B | C = xBC          # [H, P] | [G, N] | [G, N]
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t
               y_t = S_t C_t + D xs_t          # head h reads group h // (H / G)
               out = rms_norm(y * silu(z), w_g) over groups of H P / G, @ W_outproj
    attention(h): q, k, v = h Wq, h Wk, h Wv;  no positional embedding
               softmax(q k^T * attention_multiplier) v, causal;  @ Wo

Departures from the family's port, each said so that it is one line to
change (``deployment.json`` lists them under ``assumed``): (a) the
port's gated MLP is one fused ``input_linear`` of ``2 x
shared_intermediate_size`` columns, gate half first; the engine's tree
holds the halves as ``w_gate`` and ``w_up``, which is the same product;
(b) the state is float32; (c) ``time_step_limit`` is (0, inf): ``dt`` is
not clipped; (d) the port's fast path multiplies by the attention mask
before the convolution for batches with padding on the left; one
sequence without padding has none.

``fault`` computes one thing wrongly, on purpose, to measure that the
comparison's limits catch it (``perfbench/check_noise/``). One of them
is a fault of a **padded prefill**, which a reference without padding
cannot make by itself: ``pads=(n, count)`` says that the program ran
``count`` padding tokens (id 0) after the ``n`` of the prompt, and under
``state_after_bucket`` the state-space layers take them in where a sound
program skips them.

``bf16_activations`` is no fault but a **control** (``CONTROLS``): the
same equations with every activation the program holds in bf16 rounded
to what bf16 holds (the residual stream, a norm's output, a product's
output, the convolution's output; the state, ``dt``, the softmax and the
norms' sums stay float32, as in the program). It says how much of a
sound program's distance from this reference is the rounding of its
activations: against it the program's state reads closer than against
the float32 equations, and a float32 program reads as far from it as
the bf16 one from them (``perfbench/check_noise/``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "bf16_state",               # the recurrent state rounded to bf16 a step
    "state_after_bucket",       # state and conv rows taken after the padding
    "no_d",                     # D x left out
    "no_embedding_multiplier",  # each of the four multipliers read as 1 ...
    "no_residual_multiplier",
    "no_logits_scaling",
    "attention_scale_sqrt",     # ... 1 / sqrt(head_dim) for attention_multiplier
    "bc_a_head",                # B and C read as if every head had its own
)
CONTROLS = ("bf16_activations",)


def _deq(w, at=()):
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


def _held(fault):
    """What a value the program holds in its activations' precision is
    rounded by: nothing, or to bf16 under the control."""
    return _as_bf16 if fault == "bf16_activations" else (lambda x: x)


def _rms(x, gain, eps):
    return (
        x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        * gain.astype(jnp.float32)
    )


def mamba(h, lw, at, hf, fault, counts):
    """One Mamba-2 mixer over ``h [T, d]``: ``(out [T, d], the state [H,
    P, N] after the last position)``. ``counts [T]`` bool: False at a
    padding position, which a sound program keeps out of the state and
    out of the convolution's window of later positions."""
    H, P = hf["mamba_n_heads"], hf["mamba_d_head"]
    G, N, K = hf["mamba_n_groups"], hf["mamba_d_state"], hf["mamba_d_conv"]
    inner, T = H * P, h.shape[0]
    width = inner + 2 * G * N
    held = _held(fault)
    zxd = held(h @ _deq(lw["w_in"], at))
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + width], zxd[:, inner + width:]
    # the K - 1 positions before t that count: with padding kept out,
    # position t's j-th predecessor is the j-th counting position before
    # it (all of them, where nothing is padding)
    order = jnp.cumsum(counts) - 1
    skip = fault != "state_after_bucket"
    conv_w = lw["conv_w"][at].astype(jnp.float32)           # [K, C]
    conv = jnp.broadcast_to(lw["conv_b"][at].astype(jnp.float32), xbc.shape)
    for j in range(K):
        back = K - 1 - j
        if skip:
            rank = order - back
            src = jnp.searchsorted(order, rank, side="left")
            ok = (rank >= 0) & counts
        else:
            src = jnp.arange(T) - back
            ok = src >= 0
        row = jnp.where(ok[:, None], xbc[jnp.clip(src, 0, T - 1)], 0.0)
        conv = conv + row * conv_w[j]
    act = held(jax.nn.silu(conv))
    xs = act[:, :inner].reshape(T, H, P)
    Bm = act[:, inner:inner + G * N].reshape(T, G, N)
    Cm = act[:, inner + G * N:].reshape(T, G, N)
    # a head's group's B and C, a head at a time
    Bh = jnp.repeat(Bm, H // G, axis=1)                     # [T, H, N]
    Ch = jnp.repeat(Cm, H // G, axis=1)
    if fault == "bc_a_head":
        # as if the file had a group a head: head h's B and C are not
        # its neighbours' (here: the group's, B turned by h places and C
        # by 2 h; turned alike their product would be the group's)
        turn = (jnp.arange(N)[None, :] + jnp.arange(H)[:, None]) % N
        Bh = jnp.take_along_axis(Bh, turn[None], axis=2)
        Ch = jnp.take_along_axis(Ch, (2 * turn % N)[None], axis=2)
    dt = jax.nn.softplus(dt + lw["dt_bias"][at].astype(jnp.float32))
    if skip:
        dt = jnp.where(counts[:, None], dt, 0.0)
    A = -jnp.exp(lw["A_log"][at].astype(jnp.float32))       # [H]

    def step(S, t):
        x_t, dt_t, b_t, c_t = t
        S = (
            jnp.exp(dt_t * A)[:, None, None] * S
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        if fault == "bf16_state":
            S = _as_bf16(S)
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    last, y = jax.lax.scan(
        step, jnp.zeros((H, P, N), jnp.float32), (xs, dt, Bh, Ch)
    )
    if fault != "no_d":
        y = y + lw["D"][at].astype(jnp.float32)[:, None] * xs
    y = held(y).reshape(T, inner) * jax.nn.silu(z)
    eps = float(hf["rms_norm_eps"])
    yg = y.reshape(T, G, inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    y = held(
        held(yg).reshape(T, inner) * lw["gate_norm"][at].astype(jnp.float32)
    )
    return held(y @ _deq(lw["w_out"], at)), last


def attention(h, lw, at, hf, visible, fault):
    """Causal GQA over ``h [T, d]`` without positional embedding, the
    scores times ``attention_multiplier``; ``visible [T]`` bool: a
    padding position is no key for the positions after the padding."""
    T = h.shape[0]
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // Hq
    held = _held(fault)
    q = held(h @ _deq(lw["wq"], at)).reshape(T, Hkv, Hq // Hkv, hd)
    k = held(h @ _deq(lw["wk"], at)).reshape(T, Hkv, hd)
    v = held(h @ _deq(lw["wv"], at)).reshape(T, Hkv, hd)
    scale = (
        hd ** -0.5 if fault == "attention_scale_sqrt"
        else float(hf["attention_multiplier"])
    )
    s = jnp.einsum("tkgd,skd->kgts", q, k) * scale
    t_, s_ = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = (s_ <= t_) & (visible[None, :] | ~visible[:, None])
    p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
    o = held(jnp.einsum("kgts,skd->tkgd", p, v)).reshape(T, Hq * hd)
    return held(o @ _deq(lw["wo"], at))


def _layer(x, every, mixer_w, at_all, at_kind, kind, hf, fault, counts):
    """One layer, mixer then MLP: ``(x, a Mamba-2 layer's last state or
    None)``."""
    eps = float(hf["rms_norm_eps"])
    r = 1.0 if fault == "no_residual_multiplier" else float(
        hf["residual_multiplier"]
    )
    held = _held(fault)
    h = held(_rms(x, every["attn_norm"][at_all], eps))
    state = None
    if kind == "mamba":
        y, state = mamba(h, mixer_w, at_kind, hf, fault, counts)
    else:
        y = attention(h, mixer_w, at_kind, hf, counts, fault)
    x = held(x + held(r * y))
    h = held(_rms(x, every["mlp_norm"][at_all], eps))
    y = held(held(
        jax.nn.silu(held(h @ _deq(every["w_gate"], at_all)))
        * held(h @ _deq(every["w_up"], at_all))
    ) @ _deq(every["w_down"], at_all))
    return held(x + held(r * y)), state


_layer_jit = jax.jit(_layer, static_argnames=("kind", "hf", "fault"))

_STACK = {"mamba": "ssm_layers", "attention": "attn_layers"}


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    fault: str = "",
    pads: Optional[Tuple[int, int]] = None,
    states=None,
    keep_states: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """``(logits [len(want), vocab] float32 at the positions ``want`` of
    the one sequence ``tokens``, readings)``. ``states`` ``[L_M, H, P,
    N]``: a program's recurrent state after the last of ``tokens``.
    ``readings["state_err"]``: a head's error is ``|theirs - ours| /
    |ours|`` (Frobenius over ``[P, N]``), the largest of any layer and
    head (0.0 without ``states``). ``readings["state_narrow"]``: the
    largest share, of any layer, of the state's numbers that bf16 holds
    exactly, which is what a state **kept** in bf16 reads 1.0 in and a
    float32 one about 2^-16: the program's state, or under the fault
    ``bf16_state`` this file's own, which stands for such a program's.
    ``keep_states``: ``readings["states"]`` is this forward's own state
    after the last position, ``[L_M, H, P, N]`` (to hold the control
    against the float32 equations, with no program between them).

    ``pads = (n, count)``: ``count`` padding tokens (id 0) stand after
    the first ``n`` tokens, as in the program's padded prefill; ``want``
    still counts positions without them. They pass through every layer
    as rows, are no keys for what follows them, and a sound state-space
    layer skips them (then the result is the one without ``pads``, which
    only the padding fault tells apart)."""
    assert fault == "" or fault in FAULTS + CONTROLS, fault
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
    lasts = []
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        if hasattr(embed, "q"):
            table = lambda rows: embed.q[rows].astype(  # noqa: E731
                jnp.float32
            ) * embed.s[rows].astype(jnp.float32)[:, None]
        else:
            table = lambda rows: embed[rows].astype(jnp.float32)  # noqa: E731
        x = table(ids)
        if fault != "no_embedding_multiplier":
            x = _held(fault)(x * float(hf["embedding_multiplier"]))
        index = {"mamba": 0, "attention": 0}
        for layer, kind in enumerate(hf["layer_types"]):
            i = index[kind]
            index[kind] += 1
            x, last = _layer_jit(
                x, tree["layers"], tree[_STACK[kind]], (layer,), (i,),
                kind=kind, hf=frozen, fault=fault, counts=counts,
            )
            if kind == "mamba" and keep_states:
                lasts.append(last)
            if kind == "mamba" and states is not None:
                theirs = states[i].astype(jnp.float32)
                # a head at a time: a slowly forgetting head is where a
                # state kept in fewer bits drifts, and a layer's norm
                # hides one head among sixty-four
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
        # the tied head, a block of the vocabulary at a time
        V = embed.q.shape[0] if hasattr(embed, "q") else embed.shape[0]
        logits = jnp.concatenate([
            x @ table(jnp.arange(v0, min(v0 + 16384, V))).T
            for v0 in range(0, V, 16384)
        ], axis=-1)
        if fault != "no_logits_scaling":
            logits = logits / float(hf["logits_scaling"])
    readings = {"state_err": state_err, "state_narrow": state_narrow}
    if keep_states:
        readings["states"] = jnp.stack(lasts)
    return logits, readings


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
