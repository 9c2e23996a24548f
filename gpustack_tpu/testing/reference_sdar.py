"""SDAR-MoE (Qwen3-MoE's layers, generated by diffusion over blocks)
forward pass, plain: the reference the engine's programs are compared
with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs as one bf16 pass). No cache, no kernel, no batching, and
nothing imported from ``gpustack_tpu/models``: the layer equations are
written out here from the published description, so a fault in the
engine's model code is not shared. One sequence, ``forward(tree, hf,
ids, block) -> logits``, every position attending under the **block
mask written out as a ``[T, T]`` boolean**.

What it takes from the engine is the **weights**: the engine's own
parameter tree, int8 leaves dequantised here (``q * s``, the scale per
output channel), so that the quantisation is part of what is compared.
And the configuration as the hub's ``config.json`` states it (a dict).

Equations (``x`` the residual stream, ``h = rms(x)``, eps from the file;
``L`` the block length, positions counted from 0):

- the mask: query at position ``q`` attends key at position ``k`` iff
  ``k // L <= q // L``: causal over blocks, both ways inside a block. So
  rows after a block are invisible to it, and a sequence may carry any
  rows behind the ones that are read;
- attention: ``q = h W_q``, ``k = h W_k``, ``v = h W_v`` in heads of
  ``head_dim``; q and k normalised a head (``rms`` with a gain of
  ``head_dim``); both rotated by halves (``rotate_half``) at the
  **absolute** position with ``theta ** -(i / half)``; scores ``q . k /
  sqrt(head_dim)`` in float32; softmax under the mask; ``o = concat_h(P
  v_h) W_o``;
- experts, every layer: ``s = softmax(h W_r)`` over all experts in
  float32; the ``k`` highest are chosen, ties to the lower index; their
  scores renormalised to sum 1 (``norm_topk_prob``); ``y = sum_e w_e
  (silu(h W_gate,e) * (h W_up,e)) W_down,e``; no shared expert, no bias;
- the logits at position ``i`` are for the token **at** ``i`` (no shift):
  an undecided position of the block being generated holds the mask
  token's id and is read where it stands.

A pass of the engine over the cache is then one full forward over
``[decided tokens ..., the block with its mask ids]`` read at the block's
rows (:func:`pass_ids` makes that sequence), and a commit pass is checked
by the next block's pass, which attends the rows it kept.

Departures from a plain transcription, each for the chip's memory or the
comparison, none for speed: a layer's expert weights are dequantised one
expert at a time (``fori_loop``), so that 12 layers of 128 experts fit
beside the engine's own tree; ``routing`` lets each token go to the
experts the program sent it to, so that what is compared after a router
that takes 8 of 128 is rounding and not another choice, and the choice
is held to account apart (``readings["score_err"]``).

``fault`` computes one thing wrongly, on purpose, so that the check's
limits can be set between sound and wrong (``perfbench/check_noise/``):
:data:`FAULTS`. Two of them are faults of what a pass is fed and what it
keeps, not of a layer: they are :func:`pass_ids`' and :func:`forward`'s
``kept_ids``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "causal_in_block",        # the mask causal inside a block too
    "no_commit",              # the kept rows are the last denoise pass's,
                              # made while a position held the mask id
    "tail_undecided",         # the prompt's tail fed as mask ids
    "rotary_from_block",      # generated rows rotated by position % L
    "topk_not_renormalised",  # the chosen scores as the softmax left them
    "bf16_scores",            # attention scores, softmax and router in bf16
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


def block_mask(T: int, block: int, fault: str = "") -> jax.Array:
    """``[T, T]`` bool, row the query and column the key: ``k // L <= q
    // L``."""
    q, k = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    if fault == "causal_in_block":
        return k <= q
    return k // block <= q // block


def pass_ids(
    decided: Sequence[int], block_tokens: Sequence[int],
    block_decided: Sequence[bool], mask_id: int, prompt_len: int,
    fault: str = "",
):
    """The sequence a pass over a block stands for: every token decided
    before the block, then the block with the mask id where undecided.
    ``prompt_len``: the prompt's length (its tail lies in the first
    block generated, decided from the start); under ``tail_undecided``
    the tail's positions hold the mask id in that block's passes."""
    start = len(decided)
    ids = list(decided) + [
        int(t) if d else mask_id
        for t, d in zip(block_tokens, block_decided)
    ]
    if fault == "tail_undecided" and start <= prompt_len:
        for i in range(start, min(prompt_len, len(ids))):
            ids[i] = mask_id
    return ids


def stale_ids(ids: Sequence[int], gen_from: int, upto: int, block: int,
              mask_id: int):
    """``ids`` as the rows a program **without commit passes** keeps of
    them: of every block from ``gen_from`` that is complete below
    ``upto``, the last position still holds the mask id (the last
    denoise pass of a block that decides left to right ran while it
    did)."""
    out = list(ids)
    for start in range(gen_from, upto, block):
        out[start + block - 1] = mask_id
    return out


def attention(h, kept, lw, at, hf, block, gen_from, fault):
    """One layer's attention over ``h [T, d]``: ``(o [T, d], the keys
    [T, Hkv, hd], normed and rotated, as a cache would hold them)``.
    ``kept`` (None, or ``[T, d]``): the stream whose rows a query sees
    of the blocks **before its own** (the rows a program kept of them);
    its own block's it sees of ``h``."""
    T = h.shape[0]
    Hq, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // Hq
    eps = float(hf["rms_norm_eps"])
    half = hd // 2
    where = rotated_at = jnp.arange(T)
    if fault == "rotary_from_block":
        rotated_at = jnp.where(where >= gen_from, where % block, where)
    ang = rotated_at[:, None].astype(jnp.float32) * (
        1.0 / float(hf.get("rope_theta") or 10000.0)
        ** (jnp.arange(half, dtype=jnp.float32) / half)
    )

    def rot(a):
        a1, a2 = a[..., :half], a[..., half:]
        c = jnp.cos(ang).reshape(T, *([1] * (a.ndim - 2)), half)
        s = jnp.sin(ang).reshape(T, *([1] * (a.ndim - 2)), half)
        return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)

    def keys_values(stream):
        k = (stream @ _deq(lw["wk"], at)).reshape(T, Hkv, hd)
        v = (stream @ _deq(lw["wv"], at)).reshape(T, Hkv, hd)
        return rot(_rms(k, lw["k_norm"][at], eps)), v

    q = (h @ _deq(lw["wq"], at)).reshape(T, Hkv, Hq // Hkv, hd)
    q = rot(_rms(q, lw["q_norm"][at], eps))
    k, v = keys_values(h)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    visible = block_mask(T, block, fault)
    if kept is not None:
        own = (where[:, None] // block) == (where[None, :] // block)
        k_kept, v_kept = keys_values(kept)
        s = jnp.where(
            own[None, None], s,
            jnp.einsum("tkgd,skd->kgts", q, k_kept) / math.sqrt(hd),
        )
    if fault == "bf16_scores":
        s = _as_bf16(s)
    p = jax.nn.softmax(jnp.where(visible[None, None], s, -1e30), axis=-1)
    if fault == "bf16_scores":
        p = _as_bf16(p)
    if kept is None:
        o = jnp.einsum("kgts,skd->tkgd", p, v)
    else:
        o = jnp.einsum(
            "kgts,skd->tkgd", jnp.where(own[None, None], p, 0.0), v
        ) + jnp.einsum(
            "kgts,skd->tkgd", jnp.where(own[None, None], 0.0, p), v_kept
        )
    return o.reshape(T, Hq * hd) @ _deq(lw["wo"], at), k


def experts(h, lw, at, hf, fault, theirs=None, valid=None):
    """``(y [T, d], the largest difference between the program's router
    scores and this function's own over the first ``valid`` rows, the
    share of the router's logits that bf16 holds exactly)``; the last
    two 0 without ``theirs``. The logits whose share is read are the
    program's, or under ``bf16_scores`` this function's own, which stand
    for such a program's: a router computed in float32 reads about
    2^-16, one computed in bf16 reads 1."""
    k = hf["num_experts_per_tok"]
    logits = h @ lw["router"][at].astype(jnp.float32)
    if fault == "bf16_scores":
        logits = _as_bf16(logits)
    scores = jax.nn.softmax(logits, axis=-1)
    if fault == "bf16_scores":
        scores = _as_bf16(scores)
    if theirs is None:
        _, chosen = jax.lax.top_k(scores, k)
        score_err = narrow = jnp.float32(0.0)
    else:
        chosen, their_logits = theirs
        their_logits = their_logits.astype(jnp.float32)
        counts = (jnp.arange(h.shape[0]) < valid)[:, None]
        score_err = jnp.max(jnp.where(counts, jnp.abs(
            jax.nn.softmax(their_logits, axis=-1) - scores
        ), 0.0))
        read = logits if fault == "bf16_scores" else their_logits
        narrow = jnp.sum(
            jnp.where(counts, _as_bf16(read) == read, False)
        ) / (valid * read.shape[1])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if hf.get("norm_topk_prob", True) and fault != "topk_not_renormalised":
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def add_expert(e, y):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        gate, up, down = (
            _deq(lw[n], at + (e,)) for n in ("we_gate", "we_up", "we_down")
        )
        return y + w_e[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    y = jax.lax.fori_loop(
        0, int(hf["num_experts"]), add_expert, jnp.zeros_like(h)
    )
    return y, score_err, narrow


def _layer(x, kept, lw, at, hf, block, gen_from, fault, theirs, valid):
    """One layer over the stream ``x`` and, where there is one, over the
    stream of the rows a program kept: ``(x, kept, the keys of the rows
    kept, score difference, share of narrow router logits)``."""
    eps = float(hf["rms_norm_eps"])

    def one(stream, seen, routing):
        h = _rms(stream, lw["attn_norm"][at], eps)
        hk = None if seen is None else _rms(seen, lw["attn_norm"][at], eps)
        o, keys = attention(h, hk, lw, at, hf, block, gen_from, fault)
        mid = stream + o
        y, err, narrow = experts(
            _rms(mid, lw["mlp_norm"][at], eps), lw, at, hf, fault, routing,
            valid,
        )
        return mid + y, keys, err, narrow

    new_x, keys, err, narrow = one(x, kept, theirs)
    if kept is not None:
        # the kept stream sees itself: its rows are what each block's
        # last pass wrote over what the passes before it had kept. Its
        # tokens are not the program's: it routes for itself
        kept, keys, _, _ = one(kept, None, None)
    return new_x, kept, keys, err, narrow


_layer_jit = jax.jit(
    _layer, static_argnames=("hf", "block", "fault"),
)


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    ids: Sequence[int],
    block: int,
    routing=None,
    fault: str = "",
    kept_ids: Optional[Sequence[int]] = None,
    gen_from: Optional[int] = None,
    valid: Optional[int] = None,
    keys: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """``(logits [T, vocab] float32 of the one sequence ``ids``,
    readings)``.

    ``routing``: ``(chosen [L, T, k], router logits [L, T, E])``, the
    program's own for these positions (of each position, the pass that
    wrote the rows the program kept, or the pass in hand; rows from
    ``valid`` on, which nothing reads, hold anything).
    ``readings["score_err"]`` is the largest difference between its
    scores and this file's own over the first ``valid`` rows (0.0
    without), ``readings["score_narrow"]`` the largest share, of any
    layer, of the router's logits that bf16 holds exactly
    (:func:`experts`). With ``keys`` ``readings["keys"]`` is ``[layers,
    T, Hkv, head_dim]``: every layer's keys, normed and rotated, as the
    rows a program keeps hold them (under ``kept_ids``: that stream's).

    ``kept_ids`` (the fault ``no_commit``; :func:`stale_ids`): a second
    sequence whose rows are what a query sees of the blocks before its
    own. ``gen_from``: the position the generated rows begin at, for the
    fault that rotates them by their place in the block."""
    assert fault == "" or fault in FAULTS, fault
    tokens = jnp.asarray(ids, jnp.int32)
    frozen = _Frozen(hf)

    def embed(t):
        e = tree["embed"]
        if hasattr(e, "q"):
            return e.q[t].astype(jnp.float32) * e.s[t].astype(
                jnp.float32
            )[:, None]
        return e[t].astype(jnp.float32)

    score_err = score_narrow = 0.0
    kept_keys = []
    with jax.default_matmul_precision("highest"):
        x = embed(tokens)
        kept = None
        if kept_ids is not None:
            kept = embed(jnp.asarray(kept_ids, jnp.int32))
        for layer in range(int(hf["num_hidden_layers"])):
            theirs = None
            if routing is not None:
                theirs = tuple(r[layer] for r in routing)
            x, kept, layer_keys, more, narrow = _layer_jit(
                x, kept, tree["layers"], (layer,), hf=frozen, block=block,
                gen_from=jnp.int32(
                    tokens.shape[0] if gen_from is None else gen_from
                ),
                fault=fault, theirs=theirs,
                valid=jnp.int32(tokens.shape[0] if valid is None else valid),
            )
            if theirs is not None:
                score_err = max(score_err, float(more))
                score_narrow = max(score_narrow, float(narrow))
            if keys:
                kept_keys.append(layer_keys)
        x = _rms(x, tree["final_norm"], float(hf["rms_norm_eps"]))
        logits = x @ _deq(tree["lm_head"])
    readings = {"score_err": score_err, "score_narrow": score_narrow}
    if keys:
        readings["keys"] = jnp.stack(kept_keys)
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
