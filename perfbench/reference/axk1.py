"""A.X-K1 (DeepSeek-V3 family) forward pass, plain: the reference the
engine's programs are compared with.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs as one bf16 pass). No cache, no kernel, no batching, and
nothing imported from ``gpustack_tpu/models``: the layer equations are
written out here from the published description, so a fault in the
engine's model code is not shared. One sequence; every position attends
over all earlier ones with K and V **decompressed** per head (the engine
decodes in the absorbed form, over a latent cache); the group-limited
selection is written out step by step.

What it takes from the engine is the **weights**: the engine's own
parameter tree, int8 leaves dequantised here (``q * s``, the scale per
output channel), so that the quantisation is part of what is compared
and not a difference between two sets of weights. And the configuration
as the hub's ``config.json`` states it (a dict), with the benchmark's
cut: ``experts_held`` (``{"published": E, "first": id}`` beside
``n_routed_experts`` = how many are held). The router scores all ``E``;
an expert that is not held adds nothing, in the engine and here alike,
and the shared expert is added once.

Layer equations (``x`` the residual stream, ``h = rms(x)``, eps from the
file):

- attention: ``c_q = rms(h W_qa)``; ``q = c_q W_qb`` -> heads of
  ``nope + rope``; ``[c_kv | k_r] = h W_kva``; ``c_kv <- rms(c_kv)``;
  ``q_rope`` and ``k_r`` (one for all heads) rotate by interleaved pairs
  with YaRN's frequencies; ``k_nope = c_kv W_uk``, ``v = c_kv W_uv`` per
  head; scores ``(q_nope . k_nope + q_rope . k_r) * (nope + rope)^-1/2 *
  m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax;
  ``o = concat_h(P v_h) W_o``.
- the first ``first_k_dense_replace`` layers: dense SwiGLU. The others:
  ``y = shared(h) + routed_scaling_factor * sum_{e in top-k} w_e
  expert_e(h)``, ``s = sigmoid(h W_r)`` in float32, selection on ``s +
  bias``: a group scores the sum of its two highest, the ``topk_group``
  highest groups stay (the others' entries set to 0, as the family's
  public port does), the ``k`` highest entries are chosen, ties to the
  lower index; ``w`` the raw ``s`` of the chosen, normalised to sum 1.

Sized for the chip as well as for the tests: a layer's weights are
dequantised when the layer is computed (one expert at a time), the
attention goes in blocks of query rows, and an expert computes only the
rows routed to it, up to ``capacity`` rows (``forward`` says whether
that sufficed; the caller runs it again with all rows if not).

``forward_following`` is ``forward`` behind a program's own routing:
each token goes to the experts the program sent it to, so that what is
compared after the router is rounding and not another choice, and the
choice is held to account apart (``route_following``).

``fault`` computes one thing wrongly, on purpose: it exists to measure
that the comparison's limits catch each of them (the benchmark's
``perfbench/check_noise/`` table; PERF.md section 6, PR 35). A
reference is ``fault=""``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

FAULTS = (
    "no_mscale",        # the softmax scale without YaRN's m^2
    "plain_topk",       # top-k over all experts, no groups
    "shared_twice",     # the shared expert added twice
    "kv_b_unscaled",    # kv_b_proj's int8 values without their scales
    "bf16_softmax",     # scores rounded to bf16 before the softmax
    "fp8_activations",  # every matrix's input rounded to float8 (e4m3)
)


def _deq(w: Any, at: Tuple[int, ...] = ()) -> jax.Array:
    """Leaf ``w`` of the engine's tree, indexed by ``at`` on its leading
    axes, in float32. An int8 leaf is anything with ``q`` and ``s``; its
    scales span the axes that are not contracted, which for every matrix
    here are the last ones: ``[..., in, out]`` has ``[..., out]``."""
    if hasattr(w, "q"):
        q, s = w.q[at], w.s[at]
        return q.astype(jnp.float32) * s.astype(jnp.float32)[..., None, :]
    return w[at].astype(jnp.float32)


def _act(x: jax.Array, fault: str) -> jax.Array:
    """A weight matrix's input. The engine keeps it in bf16 against
    this file's float32; the fault ``fp8_activations`` is the next
    precision below the engine's (e4m3: 3 bits of mantissa for bf16's
    7)."""
    if fault == "fp8_activations":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _rms(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * gain.astype(jnp.float32)


def yarn_frequencies(hf: Dict[str, Any]) -> Tuple[jax.Array, float]:
    """``(inv_freq [rope / 2], m)``: YaRN's blend of interpolated and
    extrapolated frequencies over the linear ramp between the two
    correction dimensions, and the magnitude ``m`` whose square scales
    the scores. (``mscale / mscale_all_dim``, which would scale sin and
    cos, is 1 in the published file and asserted so.)"""
    dim, theta = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    rs = hf["rope_scaling"]
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    assert rs["mscale"] == rs["mscale_all_dim"]

    def correction_dim(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    freq = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv = (1.0 / (factor * freq)) * ramp + (1.0 / freq) * (1.0 - ramp)
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(factor) + 1.0
    return inv, m


def _rotate(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Interleaved pairs ``(x[2i], x[2i+1])`` turned by ``angles[...,
    i]``; ``x`` is ``[T, ..., rope]``, ``angles`` ``[T, rope / 2]``."""
    angles = angles.reshape(angles.shape[:1] + (1,) * (x.ndim - 2) + (-1,))
    a, b = x[..., 0::2], x[..., 1::2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape
    )


def attention(
    h: jax.Array, lw: Dict[str, Any], at: Tuple[int, ...],
    hf: Dict[str, Any], block: int, fault: str,
) -> jax.Array:
    """``h`` [T, d] -> [T, d]; the layer's weights are ``lw[name][at]``."""
    T = h.shape[0]
    H = hf["num_attention_heads"]
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    rank, vd, eps = hf["kv_lora_rank"], hf["v_head_dim"], hf["rms_norm_eps"]
    inv, m = yarn_frequencies(hf)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]

    h = _act(h, fault)
    c_q = _rms(h @ _deq(lw["wq_a"], at), lw["q_a_norm"][at], eps)
    q = (_act(c_q, fault) @ _deq(lw["wq_b"], at)).reshape(T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], angles)
    kv_a = h @ _deq(lw["wkv_a"], at)
    c_kv = _rms(kv_a[:, :rank], lw["kv_a_norm"][at], eps)
    k_r = _rotate(kv_a[:, rank:], angles)                       # [T, rope]
    w_uk, w_uv = lw["wk_b"], lw["wv_b"]
    if fault == "kv_b_unscaled":
        w_uk, w_uv = (w.q[at].astype(jnp.float32) for w in (w_uk, w_uv))
    else:
        w_uk, w_uv = _deq(w_uk, at), _deq(w_uv, at)
    k_nope = (_act(c_kv, fault) @ w_uk).reshape(T, H, nope)
    v = (_act(c_kv, fault) @ w_uv).reshape(T, H, vd)
    scale = (nope + rope) ** -0.5 * (1.0 if fault == "no_mscale" else m * m)

    def rows(start):
        """The attention of query rows ``start .. start + block - 1``."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block, 0)
        s = (
            jnp.einsum("thn,shn->hts", qn, k_nope)
            + jnp.einsum("the,se->hts", qr, k_r)
        ) * scale
        if fault == "bf16_softmax":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        seen = (
            jnp.arange(T)[None, :] <= (start + jnp.arange(block))[:, None]
        )
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        if fault == "bf16_softmax":
            p = p.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum("hts,shv->thv", p, v).reshape(block, H * vd)

    assert T % block == 0, (T, block)
    o = jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, H * vd)
    return _act(o, fault) @ _deq(lw["wo"], at)


def select(
    scores: jax.Array, bias: jax.Array, hf: Dict[str, Any], fault: str = "",
) -> jax.Array:
    """``chosen [T, k] int32``: the group-limited selection over the
    router's scores ``[T, E]``, written out."""
    k, groups, kept = (
        hf["num_experts_per_tok"], hf["n_group"], hf["topk_group"]
    )
    E = scores.shape[-1]
    choice = scores + bias.astype(jnp.float32)
    if fault != "plain_topk":
        by_group = choice.reshape(-1, groups, E // groups)
        # a group scores the sum of its two highest
        group_score = jnp.sum(
            -jnp.sort(-by_group, axis=-1)[..., :2], axis=-1
        )
        # the `kept` highest groups, ties to the lower index
        order = jnp.argsort(-group_score, axis=-1, stable=True)
        rank_of_group = jnp.argsort(order, axis=-1, stable=True)
        stay = rank_of_group < kept                               # [T, G]
        choice = jnp.where(stay[..., None], by_group, 0.0).reshape(-1, E)
    # the k highest entries, ties to the lower index
    return jnp.argsort(-choice, axis=-1, stable=True)[:, :k].astype(jnp.int32)


def _weights(scores, chosen, hf):
    """The raw scores of the chosen, normalised to sum 1."""
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def route(
    h: jax.Array, router: jax.Array, bias: jax.Array, hf: Dict[str, Any],
    fault: str = "",
) -> Tuple[jax.Array, jax.Array]:
    """``(chosen [T, k] int32, weights [T, k] float32)`` over all the
    router's experts."""
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))      # [T, E]
    chosen = select(scores, bias, hf, fault)
    return chosen, _weights(scores, chosen, hf)


def route_following(
    h: jax.Array, router: jax.Array, bias: jax.Array, hf: Dict[str, Any],
    theirs: Tuple[jax.Array, jax.Array], fault: str = "",
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """:func:`route` behind a program's own routing of these tokens,
    ``theirs = (chosen [T, k], router logits [T, E])``.

    A router that takes 8 of 192 turns on a rounding, and one token sent
    elsewhere moves its logits by more than all the roundings together:
    what comes after the router can be compared only if both sides send
    each token to the same experts. So the program's choice is taken
    (the weights stay this function's own: its scores at those experts),
    and the choice is held to account apart, in the third result:

    - ``differs``: in how many tokens :func:`select`, made over the
      **program's** scores, gives another set than the program chose
      (the same arithmetic on the same numbers: 0 unless the program
      selects by another rule);
    - ``score_err``: the largest difference between the program's
      scores and this function's own (what came before the router);
    - ``turned [T]``: the tokens whose **held** experts this function,
      left to its own scores, would have chosen otherwise: the turns on
      a rounding, which bind nothing and explain what a comparison
      without ``theirs`` reads at those tokens."""
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))      # [T, E]
    chosen, logits = theirs
    their_scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    again = select(their_scores, bias, hf, fault)
    first = int((hf.get("experts_held") or {}).get("first", 0))

    def held(ids):
        """Of a token's experts the held ones, as a set: [T, held]."""
        return jnp.any(
            ids[..., None] == first + jnp.arange(hf["n_routed_experts"]),
            axis=-2,
        )

    return chosen, _weights(scores, chosen, hf), {
        "differs": jnp.sum(jnp.any(
            jnp.sort(again, axis=-1) != jnp.sort(chosen, axis=-1), axis=-1
        ), dtype=jnp.int32),
        "score_err": jnp.max(jnp.abs(their_scores - scores)),
        "turned": jnp.any(
            held(select(scores, bias, hf)) != held(chosen), axis=-1
        ),
    }


def _swiglu(x, gate, up, down, fault=""):
    x = _act(x, fault)
    return _act(jax.nn.silu(x @ gate) * (x @ up), fault) @ down


def moe(
    h: jax.Array, lw: Dict[str, Any], at: Tuple[int, ...],
    hf: Dict[str, Any], capacity: int, fault: str, theirs=None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """``(y [T, d], most rows any held expert was sent, agreement)``: the
    shared expert once, and of the routed part what the held experts
    give. ``theirs``, ``agreement``: :func:`route_following`."""
    T = h.shape[0]
    share = hf.get("experts_held") or {}
    held, first = hf["n_routed_experts"], int(share.get("first", 0))
    router, bias = lw["router"][at], lw["router_bias"][at]
    if theirs is None:
        chosen, w = route(h, router, bias, hf, fault)
        agreement = {}
    else:
        chosen, w, agreement = route_following(
            h, router, bias, hf, theirs, fault
        )

    def add_expert(e, carry):
        """``y`` with held expert ``e``'s part added, for the rows sent to
        it (the first ``capacity`` of them)."""
        y, most = carry
        mine = chosen == first + e                                # [T, k]
        w_e = jnp.sum(jnp.where(mine, w, 0.0), axis=-1)           # [T]
        routed = jnp.any(mine, axis=-1)
        most = jnp.maximum(most, jnp.sum(routed, dtype=jnp.int32))
        (row,) = jnp.nonzero(routed, size=capacity, fill_value=T)
        x_e = jnp.take(h, row, axis=0, mode="fill", fill_value=0.0)
        y_e = _swiglu(
            x_e, _deq(lw["we_gate"], at + (e,)), _deq(lw["we_up"], at + (e,)),
            _deq(lw["we_down"], at + (e,)), fault,
        )
        w_row = jnp.take(w_e, row, mode="fill", fill_value=0.0)
        return y.at[row].add(y_e * w_row[:, None], mode="drop"), most

    y, most = jax.lax.fori_loop(
        0, held, add_expert, (jnp.zeros_like(h), jnp.int32(0))
    )
    shared = _swiglu(
        h, _deq(lw["ws_gate"], at), _deq(lw["ws_up"], at),
        _deq(lw["ws_down"], at), fault,
    )
    if fault == "shared_twice":
        shared = 2.0 * shared
    return shared + float(hf["routed_scaling_factor"]) * y, most, agreement


def layer(
    x: jax.Array, lw: Dict[str, Any], at: Tuple[int, ...],
    hf: Dict[str, Any], dense: bool, block: int, capacity: int, fault: str,
    theirs=None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    eps = hf["rms_norm_eps"]
    x = x + attention(
        _rms(x, lw["attn_norm"][at], eps), lw, at, hf, block, fault
    )
    h = _rms(x, lw["mlp_norm"][at], eps)
    if dense:
        y, most, agreement = _swiglu(
            h, _deq(lw["w_gate"], at), _deq(lw["w_up"], at),
            _deq(lw["w_down"], at), fault,
        ), jnp.int32(0), {}
    else:
        y, most, agreement = moe(h, lw, at, hf, capacity, fault, theirs)
    return x + y, most, agreement


_layer = jax.jit(
    layer, static_argnames=("hf", "dense", "block", "capacity", "fault")
)


def forward_following(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    routing=None,
    block: int = 0,
    capacity: int = 0,
    fault: str = "",
) -> Tuple[jax.Array, Dict[str, Any]]:
    """:func:`forward` behind a program's routing: ``routing`` is
    ``(chosen [L_moe, T, k], router logits [L_moe, T, E])``, the
    program's own for these tokens in each layer with experts
    (:func:`route_following` says why). Returns the logits and, a layer
    with experts, that function's ``differs`` and ``score_err``, and of
    ``turned`` how many tokens (``turned``) and, a wanted position, in
    how many layers it is among them (``turned_at_want``)."""
    assert fault == "" or fault in FAULTS, fault
    T = len(tokens)
    block = block or T
    first_dense = int(hf.get("first_k_dense_replace", 0))
    agreed = {"differs": [], "score_err": [], "turned": []}
    at_want = jnp.zeros(len(want), jnp.int32)
    with jax.default_matmul_precision("highest"):
        embed = tree["embed"]
        ids = jnp.asarray(tokens, jnp.int32)
        if hasattr(embed, "q"):          # int8: a scale per vocabulary row
            x = embed.q[ids].astype(jnp.float32) * embed.s[ids].astype(
                jnp.float32
            )[:, None]
        else:
            x = embed[ids].astype(jnp.float32)
        for i in range(hf["num_hidden_layers"]):
            dense = i < first_dense
            lw = tree["dense_layers"] if dense else tree["layers"]
            at = (i if dense else i - first_dense,)
            theirs = None
            if routing is not None and not dense:
                theirs = (routing[0][at[0]], routing[1][at[0]])
            run = lambda cap: _layer(   # noqa: E731
                x, lw, at, hf=_Frozen(hf), dense=dense, block=block,
                capacity=cap, fault=fault, theirs=theirs,
            )
            y, most, agreement = run(capacity or T)
            if capacity and int(most) > capacity:
                y, most, agreement = run(T)
            x = y
            if theirs is not None:
                turned = agreement["turned"]
                agreed["differs"].append(int(agreement["differs"]))
                agreed["score_err"].append(float(agreement["score_err"]))
                agreed["turned"].append(int(jnp.sum(turned)))
                at_want = at_want + turned[jnp.asarray(want)]
        x = _rms(
            x[jnp.asarray(want, jnp.int32)], tree["final_norm"],
            hf["rms_norm_eps"],
        )
        logits = _act(x, fault) @ _deq(tree["lm_head"])
    return logits, {**agreed, "turned_at_want": at_want.tolist()}


def forward(
    tree: Dict[str, Any],
    hf: Dict[str, Any],
    tokens: Sequence[int],
    want: Sequence[int],
    block: int = 0,
    capacity: int = 0,
    fault: str = "",
) -> jax.Array:
    """Logits ``[len(want), vocab]`` (float32) at the positions ``want``
    of the one sequence ``tokens``, every position attending over all
    earlier ones. ``block`` query rows at a time in the attention (0:
    all at once; it must divide the length); an expert computes up to
    ``capacity`` of the rows routed to it (0: room for all), and a layer
    in which that did not suffice is computed again with room for all.
    """
    return forward_following(
        tree, hf, tokens, want, None, block, capacity, fault
    )[0]


class _Frozen(dict):
    """The configuration as a hashable, so that it is static under
    ``jit`` (its values are numbers, strings and one flat dict)."""

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
