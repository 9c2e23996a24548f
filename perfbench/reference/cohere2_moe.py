"""Command A+ (``cohere2_moe``) forward pass, plain: the reference the
engine's programs are compared with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. No cache, no kernel, no
batching, no ring, and nothing imported from ``gpustack_tpu/models``: the
layer equations are written out here from the published file and its
description, so a fault in the engine's model code is not shared. One
sequence, one layer at a time (a layer's weights are dequantised when the
layer is computed), attention in blocks of ``ROWS`` query rows so that
8,192 positions of 128 heads fit a chip.

What it takes from the engine is the **weights** (the engine's own
parameter tree, int8 leaves dequantised here, ``q * s``, so that the
quantisation is part of what is compared) and the hub's ``config.json``
as a dict, with the benchmark's cut: ``experts_held`` (``{"of": E,
"first": id}`` beside ``num_experts`` = how many are held): the router
scores all ``E``, an expert that is not held adds nothing, the shared
experts are added once. ``layer_types`` is read as far as
``num_hidden_layers``.

A layer, ``x [T, d]``:

- ``h = LayerNorm(x)``: ``(x - mean) / sqrt(var + eps) * g``, no bias.
- ``q = h Wq [T, 128, 128]``, ``k = h Wk``, ``v = h Wv [T, 8, 128]``. A
  **sliding** layer rotates q and k in interleaved pairs (``rope_gptj``:
  the pair ``(x[2i], x[2i+1])`` by ``t * theta^(-2i/hd)``) and query
  ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``; a **full**
  layer takes no positional embedding and is causal. ``A = softmax(q k^T
  / sqrt(hd)) v Wo``.
- ``s = sigmoid(h Wr)``; the ``k`` largest chosen (ties to the lower
  index), ``w_e = s_e / sum of the chosen``; ``M = sum w_e E_e(h) + (1 /
  n) sum_{s < n} S_s(h)``, every expert ``down(silu(gate(h)) * up(h))``.
  The engine stores the ``n`` shared experts as one MLP ``n`` times as
  wide, whose output is their sum.
- ``x <- x + A + M`` (one norm, both branches from it).

After the last layer the same LayerNorm and ``logits = logit_scale * x
E^T``, the embedding tied.

``forward(..., routing=...)`` goes behind a program's own routing (each
token to the experts the program sent it to, the weights this file's own
scores at those experts), as ``reference/axk1.py`` does and for its
reason: a router that takes 8 of 128 turns on a rounding. It reports how
far the program's scores are from its own (``score_err``).

``rings``: a program's window store of the one slot after the last of
``tokens``, ``[L_sliding, W, Hkv, hd]`` (keys). ``ring_err`` is the
largest ``|theirs - ours|`` over ``max |ours|`` of any sliding layer,
ours being this file's rotated keys laid out as a ring should hold them:
row ``r`` the newest position ``p < T`` with ``p mod W == r``.

``fault`` computes one thing wrongly, on purpose, to measure that the
comparison's limits catch it (``perfbench/check_noise/``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "window_plus_one",    # a query sees sliding_window + 1 keys
    "window_minus_one",   # or one fewer
    "full_rotated",       # the full layers rotated too
    "sliding_unrotated",  # the sliding layers not rotated
    "rotate_halves",      # rotated in halves (rotate_half), not in pairs
    "shared_summed",      # the shared experts summed, not averaged
    "serial_block",       # norm -> attention -> add -> norm -> experts
    "rms_norm",           # RMS for LayerNorm (no mean taken off)
    "ring_at_position",   # a ring written at position, not mod window
    "bf16_stated",        # bf16 where float32 is stated: norm and router
    "fp8_activations",    # every matrix's input rounded to float8 (e4m3)
)
ROWS = 256   # query rows of one block of attention


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
    """``x`` rounded to what bf16 holds, in float32 (not a cast there and
    back, which the TPU's compiler may drop as excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _act(x, fault):
    """A weight matrix's input. The engine keeps it in bf16 against this
    file's float32; the fault ``fp8_activations`` is the next precision
    below the engine's (e4m3: 3 bits of mantissa for bf16's 7)."""
    if fault == "fp8_activations":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _norm(x, gain, eps, fault):
    if fault == "bf16_stated":
        x = _as_bf16(x)
    if fault != "rms_norm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if fault == "bf16_stated":
        y = _as_bf16(y)
    return y * gain.astype(jnp.float32)


def _rotate(a, theta, pairs=True):
    """``a [T, ..., hd]`` rotated by position: in interleaved pairs, or
    (``pairs`` False, a fault) in halves."""
    T, hd = a.shape[0], a.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    shape = (T,) + (1,) * (a.ndim - 2) + (half,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if pairs:
        a1, a2 = a[..., 0::2], a[..., 1::2]
        return jnp.stack(
            [a1 * c - a2 * s, a1 * s + a2 * c], axis=-1
        ).reshape(a.shape)
    a1, a2 = a[..., :half], a[..., half:]
    return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], axis=-1)


def attention(h, lw, at, hf, sliding, fault):
    """``(A [T, d], the layer's keys as a cache would hold them [T, Hkv,
    hd])``, the scores a block of ``ROWS`` query rows at a time."""
    T = h.shape[0]
    Hq, Hkv, hd = (
        hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    )
    h = _act(h, fault)
    q = (h @ _deq(lw["wq"], at)).reshape(T, Hkv, Hq // Hkv, hd)
    k = (h @ _deq(lw["wk"], at)).reshape(T, Hkv, hd)
    v = (h @ _deq(lw["wv"], at)).reshape(T, Hkv, hd)
    rotated = (
        fault != "sliding_unrotated" if sliding else fault == "full_rotated"
    )
    if rotated:
        theta = float(hf["rope_theta"])
        pairs = fault != "rotate_halves"
        q, k = _rotate(q, theta, pairs), _rotate(k, theta, pairs)
    window = int(hf["sliding_window"]) + {
        "window_plus_one": 1, "window_minus_one": -1,
    }.get(fault, 0)
    rows = min(ROWS, T)
    blocks = -(-T // rows)
    qp = jnp.pad(q, ((0, blocks * rows - T), (0, 0), (0, 0), (0, 0)))
    keys = jnp.arange(T)[None, :]

    def block(b):
        i = b * rows + jnp.arange(rows)[:, None]
        seen = keys <= i
        if sliding:
            seen = seen & (i - keys < window)
        s = jnp.einsum(
            "tkgd,skd->kgts",
            jax.lax.dynamic_slice_in_dim(qp, b * rows, rows, 0), k,
        ) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", p, v).reshape(rows, Hq * hd)

    o = jax.lax.map(block, jnp.arange(blocks)).reshape(-1, Hq * hd)[:T]
    return _act(o, fault) @ _deq(lw["wo"], at), k


def experts(h, lw, at, hf, fault, theirs):
    """``(M [T, d], how far the program's router scores are from this
    file's)``: the held routed experts' part and the shared experts'
    mean. ``theirs``: the program's ``(chosen [T, k], router logits [T,
    E])`` or None (this file's own top-k)."""
    share = hf.get("experts_held") or {}
    held, first = int(hf["num_experts"]), int(share.get("first", 0))
    k = int(hf["num_experts_per_tok"])
    logits = h @ lw["router"][at].astype(jnp.float32)
    if fault == "bf16_stated":
        logits = _as_bf16(_as_bf16(h) @ _as_bf16(
            lw["router"][at].astype(jnp.float32)
        ))
    scores = jax.nn.sigmoid(logits)
    if theirs is None:
        _, chosen = jax.lax.top_k(scores, k)
        score_err = jnp.float32(0.0)
    else:
        chosen = theirs[0]
        score_err = jnp.max(jnp.abs(jax.nn.sigmoid(theirs[1]) - scores))
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    x = _act(h, fault)

    def mlp(gate, up, down):
        return _act(jax.nn.silu(x @ gate) * (x @ up), fault) @ down

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * mlp(*(
            _deq(lw[name], at + (e,))
            for name in ("we_gate", "we_up", "we_down")
        ))

    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    n = 1 if fault == "shared_summed" else int(hf["num_shared_experts"])
    shared = mlp(*(
        _deq(lw[name], at) for name in ("ws_gate", "ws_up", "ws_down")
    ))
    return y + shared / n, score_err


def _layer(x, lw, at, sliding, hf, fault, theirs):
    """``(x + A + M, the layer's keys, the router-score difference)``."""
    eps = float(hf.get("layer_norm_eps", 1e-5))
    h = _norm(x, lw["attn_norm"][at], eps, fault)
    a, keys = attention(h, lw, at, hf, sliding, fault)
    if fault == "serial_block":
        h = _norm(x + a, lw["attn_norm"][at], eps, fault)
    m, score_err = experts(h, lw, at, hf, fault, theirs)
    return x + a + m, keys, score_err


_layer_jit = jax.jit(_layer, static_argnames=("sliding", "hf", "fault"))


def ring_of(keys, window: int, fault: str = ""):
    """``keys [T, ...]`` as a ring of ``min(window, T)`` rows holds them
    after position ``T - 1``: row ``r`` the newest position of its
    residue. Under ``ring_at_position`` what a program that wrote at
    ``position`` (clamped to the store, as an in-place write is) would
    hold: the first rows as they came, the last row the newest key."""
    T = keys.shape[0]
    rows = min(window, T)
    r = jnp.arange(rows)
    if fault == "ring_at_position":
        src = jnp.where(r < rows - 1, r, T - 1)
    else:
        src = r + rows * ((T - 1 - r) // rows)
    return keys[src]


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    routing=None,
    fault: str = "",
    rings=None,
) -> Tuple[jax.Array, Dict[str, float]]:
    """``(logits [len(want), vocab] float32 at the positions ``want`` of
    the one sequence ``tokens``, readings)``. ``routing``: ``(chosen [L,
    T, k], router logits [L, T, E])``, the program's own for these
    tokens; ``readings["score_err"]`` is the largest difference between
    its scores and this file's own (0.0 without). ``rings``: the
    program's window store of the slot, keys ``[L_sliding, W, Hkv,
    hd]``, after the last of ``tokens``; ``readings["ring_err"]`` as the
    module says (0.0 without)."""
    assert fault == "" or fault in FAULTS, fault
    ids = jnp.asarray(tokens, jnp.int32)
    n_layers = int(hf["num_hidden_layers"])
    kinds = [t == "sliding_attention" for t in hf["layer_types"][:n_layers]]
    frozen = _Frozen(hf)
    score_err = ring_err = 0.0
    with jax.default_matmul_precision("highest"):
        x = _deq(tree["embed"])[ids]
        lw = tree["layers"]
        sliding_at = 0
        for i, sliding in enumerate(kinds):
            theirs = None
            if routing is not None:
                theirs = (routing[0][i], routing[1][i])
            x, keys, err = _layer_jit(
                x, lw, (i,), sliding=sliding, hf=frozen, fault=fault,
                theirs=theirs,
            )
            score_err = max(score_err, float(err))
            if sliding and rings is not None:
                ours = ring_of(keys, int(hf["sliding_window"]), fault)
                theirs_k = rings[sliding_at][: ours.shape[0]].astype(
                    jnp.float32
                )
                ring_err = max(ring_err, float(
                    jnp.max(jnp.abs(theirs_k - ours))
                    / jnp.max(jnp.abs(ours))
                ))
            sliding_at += sliding
        x = _norm(
            x[jnp.asarray(want, jnp.int32)], tree["final_norm"],
            float(hf.get("layer_norm_eps", 1e-5)), fault,
        )
        logits = float(hf.get("logit_scale", 1.0)) * (
            _act(x, fault) @ _deq(tree["embed"]).T
        )
    return logits, {"score_err": score_err, "ring_err": ring_err}


# ---------------------------------------------------------------------------
# The window's edges, where one key decides
# ---------------------------------------------------------------------------
#
# With 4,096 keys under a query, one key more or fewer moves a logit of
# the model by less than its bf16 rounding: no comparison of logits shows
# a window that is off by one (measured: window_plus_one and
# window_minus_one read as the sound program does, PERF.md section 6, PR
# 48). So the two kernels that know the window are also run on keys made
# for it: a query's score is 30 on the last key it may see, 60 on the
# first it may not, 0 elsewhere, so its output is the one key's value if
# the edge is right and another's, or a mean of thousands, if not.

_PROBE_SCORE = 30.0


def _probe_window(hf, fault):
    return int(hf["sliding_window"]) + {
        "window_plus_one": 1, "window_minus_one": -1,
    }.get(fault, 0)


def band_probe(hf: Dict[str, Any], length: int, fault: str = ""):
    """Operands for the prefill kernel over ``length`` positions and what
    it must give at the probed rows: ``(q [1, T, Hq, hd], k, v [1, T,
    Hkv, hd], rows [n], expected [n, Hq * hd])``, float32 (the caller
    rounds to the program's dtype; the values survive bf16). The probed
    queries lie past the window, on and beside the kernel's block edges
    (multiples of 128 and 512 of the query and of the band's lower
    edge). ``fault``: what a kernel whose band is one key wider or
    narrower would give."""
    import numpy as np

    W = int(hf["sliding_window"])
    Hq, Hkv, hd = (
        hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    )
    offsets = [0, 1, 2, 126, 127, 128, 129, 255, 256, 511, 512, 513, 1000]
    rows = sorted({W + o for o in offsets if W + o < length} | {length - 1})
    rows = [i for i in rows if i >= W][: hd // 2]
    rng = np.random.default_rng(W)
    q = np.zeros((length, hd), np.float32)
    k = np.zeros((length, hd), np.float32)
    v = rng.normal(size=(length, Hkv, hd)).astype(np.float32)
    a = _PROBE_SCORE * math.sqrt(hd)
    for n, i in enumerate(rows):
        q[i, 2 * n], q[i, 2 * n + 1] = a, 2 * a
        k[i - W + 1, 2 * n] += 1.0      # the last key the query may see
        k[i - W, 2 * n + 1] += 1.0      # the first it may not
    window = _probe_window(hf, fault)
    scores = (q[rows] @ k.T) / math.sqrt(hd)            # [n, T]
    i = np.asarray(rows)[:, None]
    j = np.arange(length)[None, :]
    seen = (j <= i) & (i - j < window)
    p = np.exp(np.where(seen, scores, -np.inf)
               - np.max(np.where(seen, scores, -np.inf), -1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = np.einsum("ns,skd->nkd", p, v)                # [n, Hkv, hd]
    expected = np.repeat(out, Hq // Hkv, axis=1).reshape(len(rows), Hq * hd)
    return (
        np.broadcast_to(q[None, :, None, :], (1, length, Hq, hd)).copy(),
        np.broadcast_to(k[None, :, None, :], (1, length, Hkv, hd)).copy(),
        v[None], np.asarray(rows), expected,
    )


def ring_probe(hf: Dict[str, Any], slots: int, fault: str = ""):
    """Operands for the decode kernel over a ring and what it must give:
    ``(q [B, Hq, hd], ring_k, ring_v [1, B, W, Hkv, hd], lengths [B] (a
    slot's positions, before the window clips them), expected [B, Hq *
    hd])``. A slot's query scores 30 on the last row it may walk
    (``min(length, W) - 1``) and 60 on the row after it, where a slot
    shorter than the window still holds its last tenant's key; lengths
    under, at and over the window, and a dead slot."""
    import numpy as np

    W = int(hf["sliding_window"])
    Hq, Hkv, hd = (
        hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    )
    lengths = [W - 1, W, W + 5, 2 * W + 7, 3, 0, W // 2, W + 1]
    lengths = (lengths * (-(-slots // len(lengths))))[:slots]
    rng = np.random.default_rng(W + 1)
    q = np.zeros((slots, hd), np.float32)
    k = np.zeros((slots, W, hd), np.float32)
    v = rng.normal(size=(slots, W, Hkv, hd)).astype(np.float32)
    a = _PROBE_SCORE * math.sqrt(hd)
    window = _probe_window(hf, fault)
    expected = np.zeros((slots, Hkv, hd), np.float32)
    for b, n in enumerate(lengths):
        live = min(n, W)
        if not live:
            continue
        q[b, 0], q[b, 1] = a, 2 * a
        k[b, live - 1, 0] = 1.0
        if live < W:
            k[b, live, 1] = 1.0
        walked = min(n, window, W)
        scores = (k[b, :walked] @ q[b]) / math.sqrt(hd)
        p = np.exp(scores - scores.max())
        expected[b] = np.einsum("s,skd->kd", p / p.sum(), v[b, :walked])
    return (
        np.broadcast_to(q[:, None, :], (slots, Hq, hd)).copy(),
        np.broadcast_to(k[None, :, :, None, :], (1, slots, W, Hkv, hd)).copy(),
        v[None], np.asarray(lengths, np.int32),
        np.repeat(expected, Hq // Hkv, axis=1).reshape(slots, Hq * hd),
    )


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
