"""The gated delta rule of a linear-attention mixer, three forms.

A head keeps a matrix state ``S [Dk, Dv]`` (float32) and moves it one
position at a time (Yang et al., "Gated Delta Networks",
arXiv:2412.06464)::

    S_t = a_t S_{t-1} + k_t (outer) beta_t (v_t - (a_t S_{t-1})^T k_t)
    o_t = S_t^T q_t

``a_t = exp(g_t)`` in (0, 1] is the decay, of one of two shapes, and every
form here takes either: **one number a head and position** (``g [B, T,
H]``, the gated delta rule as published, Olmo-Hybrid's) or **one number a
head, position and key channel** (``g [B, T, H, Dk]``: ``S_t = diag(a_t)
S_{t-1} + ...``, each of the state's ``Dk`` rows forgetting at its own
rate; KDA, Kimi Linear, arXiv:2510.26692, Solar-Open2's). ``beta_t`` is
one write strength a head and position (in (0, 2) where the model allows
negative eigenvalues, Grazzi et al., arXiv:2411.12537: along ``k_t`` the
transition's eigenvalue is ``a_t (1 - beta_t)``), ``k_t`` of unit length.
Where ``ops/ssm.py``'s rule *adds* an outer product, this one *corrects*:
the state is read into its own update. A position with ``g_t = 0`` and
``beta_t = 0`` leaves the state as it was: that is how a caller keeps
padding out of it.

- :func:`delta_chunk_scan`, for ``T > 1``: the published chunked form
  (arXiv:2406.06484). With ``u_t = beta_t (v_t - (a_t S_{t-1})^T k_t)``
  the rule is ``S_t = a_t S_{t-1} + k_t (outer) u_t``, and within a chunk
  of ``C`` positions the ``u`` solve ``(I + A) U = beta (V - gamma K
  S_0)``, ``A`` strictly lower triangular: ``A[t, s] = beta_t (gamma_t /
  gamma_s) (k_t . k_s)``, ``gamma`` the decay from the chunk's start.
  With a decay a channel the same entry is ``beta_t sum_c k_t[c] k_s[c]
  exp(G_t[c] - G_s[c])``, ``G`` the running sum of ``g`` within the
  chunk, which is no scalar times ``K K^T`` any more
  (:func:`_decayed_products` says how it is formed without overflow).
  ``(I + A)^-1`` is taken once a chunk for all heads and chunks
  together, by substitution a block at a time (:func:`unit_lower_inverse`:
  ``log2 C`` rounds of two products; the doubling product of the
  nilpotent part, ``prod_j (I + (-A)^(2^j))``, is as many products and
  loses every digit in float32 once keys are alike, its powers growing
  to 1e15 before they cancel); the
  boundary states follow from a scan over the ``T / C`` chunks. Plain
  ``jax.numpy`` in float32 at the highest matmul precision, under the
  scope ``delta_chunk_scan`` (``kda_chunk_scan`` with a decay a
  channel, so that a device trace tells the two apart): nothing here is
  a kernel until a trace
  shows it at more than twice what its operations ask (``ops/ssm.py``'s
  rule).
- :func:`delta_state_update`, for a decode step (``T == 1``): a Pallas
  kernel that reads and writes the **stacked** state in place (aliased,
  the layer's index prefetched, one slot a grid point, **live slots
  only**: ``ops/ssm.py ssm_state_update``'s way). The state is laid out
  ``[L, B, Dk, H * Dv]``: the key width on the sublanes, every head's
  values side by side on the lanes. At 30 heads of ``[96, 192]`` that is
  12 sublane tiles by 45 lane tiles with nothing padded, where ``[...,
  96, 192]`` a head stores a third more (192 as 256) and reads and
  writes it every step; and the whole update is elementwise on it, in
  float32 on the vector unit, with two reductions over the sublanes.
  One decay a head rides the lanes beside ``beta`` and ``v`` (a head's
  lanes alike); a decay a channel comes ``[Dk, H]`` like ``q`` and ``k``,
  a head's decays a column that broadcasts over that head's lanes, so
  each sublane row of each head is multiplied by its own number and
  nothing of the state's size is made in HBM. The call is named
  ``delta_state_update`` for the first and ``kda_state_update`` for the
  second.
- :func:`delta_step_xla`: the same step as XLA operations, for any other
  platform and for the tests.

``tests/ops/test_delta_rule.py`` holds all three to the recurrence taken
one position at a time (:func:`delta_recurrence`).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = lax.Precision.HIGHEST
_LANES = 128
CHUNK = 64
# rows of a chunk whose decays a channel are taken relative to one row
# (:func:`_decayed_products`)
SUB_BLOCK = 16


def state_layout(state: jax.Array) -> jax.Array:
    """``[..., H, Dk, Dv]`` as the cache stores it, ``[..., Dk, H * Dv]``."""
    *lead, H, Dk, Dv = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, Dk, H * Dv)


def state_heads(stored: jax.Array, heads: int) -> jax.Array:
    """:func:`state_layout`'s inverse: ``[..., Dk, H * Dv]`` a head at a
    time, ``[..., H, Dk, Dv]``."""
    *lead, Dk, width = stored.shape
    return jnp.moveaxis(
        stored.reshape(*lead, Dk, heads, width // heads), -2, -3
    )


def delta_recurrence(q, k, v, g, beta, h0):
    """The rule one position at a time, float32: ``(o [B, T, H, Dv],
    state [B, H, Dk, Dv] after the last position)``. ``g`` is ``[B, T,
    H]`` (one decay a head) or ``[B, T, H, Dk]`` (one a key channel).
    What the other forms are held to; no served program runs it."""
    f32 = jnp.float32
    channel = g.ndim == 4

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t                 # [B, H, ...]
        a_t = jnp.exp(g_t)
        S = (a_t[..., None] if channel else a_t[..., None, None]) * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", S, k_t, precision=_HIGHEST
        ))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    last, o = lax.scan(
        step, h0.astype(f32),
        tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1), last


def unit_lower_inverse(A: jax.Array) -> jax.Array:
    """``(I + A)^-1`` for ``A [..., C, C]`` strictly lower triangular,
    ``C`` a power of two: forward substitution a block at a time. The
    inverse of ``[[L11, 0], [L21, L22]]`` is ``[[L11^-1, 0], [-L22^-1 L21
    L11^-1, L22^-1]]``; starting from the diagonal's ones, each round
    joins neighbouring diagonal blocks into one of twice the size with
    two batched products, ``log2 C`` rounds. What it forms is entries of
    the inverse itself, which the rule keeps moderate (its transition
    never expands)."""
    C = A.shape[-1]
    if C & (C - 1):
        raise ValueError(f"a chunk of {C} positions is no power of two")
    lead = A.shape[:-2]
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    blocks = jnp.ones(lead + (C, 1, 1), A.dtype)        # [..., n, b, b]
    b = 1
    while b < C:
        n = C // (2 * b)
        # the diagonal blocks of 2b: [..., n, 2, b, 2, b]
        pairs = jnp.moveaxis(
            jnp.diagonal(
                A.reshape(lead + (n, 2 * b, n, 2 * b)), axis1=-4, axis2=-2
            ), -1, -3,
        ).reshape(lead + (n, 2, b, 2, b))
        upper, lower = blocks[..., 0::2, :, :], blocks[..., 1::2, :, :]
        corner = -mm(
            "...ij,...jk,...kl->...il", lower, pairs[..., 1, :, 0, :], upper
        )
        blocks = jnp.concatenate(
            [
                jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
                jnp.concatenate([corner, lower], axis=-1),
            ],
            axis=-2,
        )
        b *= 2
    return blocks[..., 0, :, :]


def delta_chunk_scan(
    q: jax.Array,      # [B, T, H, Dk], scaled as the caller wants it
    k: jax.Array,      # [B, T, H, Dk], unit length a head
    v: jax.Array,      # [B, T, H, Dv]
    g: jax.Array,      # float32 [B, T, H] or [B, T, H, Dk], log decay
                       # <= 0 (a head's, or a key channel's); 0 = skip
    beta: jax.Array,   # float32 [B, T, H]; 0 = skip
    h0: jax.Array,     # float32 [B, H, Dk, Dv]: the state before position 0
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """``(o float32 [B, T, H, Dv], state float32 [B, H, Dk, Dv] after the
    last position)``. ``T`` need be no multiple of ``chunk``: the tail is
    padded with positions of ``g = 0, beta = 0``. ``chunk`` is a power
    of two (and a multiple of :data:`SUB_BLOCK` for a decay a channel)."""
    if g.ndim == 4:
        with jax.named_scope("kda_chunk_scan"):
            return _chunk_scan(q, k, v, g, beta, h0, chunk)
    with jax.named_scope("delta_chunk_scan"):
        return _chunk_scan(q, k, v, g, beta, h0, chunk)


def _decayed_products(a, b, cum, sub: int):
    """``M[t, s] = sum_c a_t[c] b_s[c] exp(cum_t[c] - cum_s[c])`` for ``s
    <= t`` and 0 above the diagonal: ``a, b, cum [..., C, Dk]``, ``cum``
    the running sum of a log decay a channel (never rising), ``M [..., C,
    C]``.

    The factored form ``(a * exp(cum)) (b * exp(-cum))^T`` is one matmul
    and overflows float32: ``-cum`` passes 88 inside a chunk of 64 at the
    decays the mixer is initialised with (``A_log`` up to log 16). So the
    chunk is cut into sub-blocks of ``sub`` rows, the published KDA
    kernels' rule (Kimi Linear, arXiv:2510.26692, section on the chunked
    algorithm; its public code's ``intra`` kernels). **Below the
    diagonal blocks** the decay is taken relative to the first row ``r``
    of ``t``'s sub-block: ``exp(cum_t - cum_r) * exp(cum_r - cum_s)``,
    both exponents <= 0 because ``s < r <= t``, so each factor is in (0,
    1] and the product is one matmul a sub-block row of blocks. **On
    the diagonal blocks** (``s`` and ``t`` in one sub-block) there is no
    such row between them, and the entry is summed from the explicit
    ``[sub, sub, Dk]`` differences, the mask on before the ``exp``."""
    *lead, C, Dk = a.shape
    n = C // sub
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)

    def blocks(x):
        return x.reshape(*lead, n, sub, Dk)

    a_b, b_b, cum_b = blocks(a), blocks(b), blocks(cum)
    rows = jnp.arange(sub)
    # the diagonal sub-blocks, from the differences themselves
    diff = cum_b[..., :, None, :] - cum_b[..., None, :, :]
    diff = jnp.where(
        (rows[:, None] >= rows[None, :])[..., None], diff, -jnp.inf
    )
    diag = jnp.sum(
        a_b[..., :, None, :] * b_b[..., None, :, :] * jnp.exp(diff), axis=-1
    )                                                   # [..., n, sub, sub]
    # below them: every row against every earlier sub-block's rows, the
    # decay through the first row of its own sub-block
    first = cum_b[..., :1, :]                           # [..., n, 1, Dk]
    a_rel = a_b * jnp.exp(cum_b - first)
    # b_s decayed up to each sub-block's first row; a row at or after it
    # (min: exponent 0) is masked below
    b_rel = b[..., None, :, :] * jnp.exp(
        jnp.minimum(first - cum[..., None, :, :], 0.0)
    )                                                   # [..., n, C, Dk]
    below = mm("...itc,...isc->...its", a_rel, b_rel)   # [..., n, sub, C]
    block_of = jnp.arange(C) // sub
    below = jnp.where(
        block_of[None, None, :] < jnp.arange(n)[:, None, None], below, 0.0
    ).reshape(*lead, C, C)
    on = jnp.where(
        jnp.eye(n, dtype=bool)[:, None, :, None],
        diag[..., :, :, None, :], 0.0,
    )                                                   # [..., n, sub, n, sub]
    return below + on.reshape(*lead, C, C)


def _chunk_scan(q, k, v, g, beta, h0, C):
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    f32 = jnp.float32
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    nc = (T + pad) // C

    def chunks(a):
        """``[B, nc * C, H, ...] -> [B, nc, H, C, ...]``"""
        return jnp.moveaxis(
            a.astype(f32).reshape(B, nc, C, *a.shape[2:]), 3, 2
        )

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    channel = g.ndim == 5
    if channel:
        # a decay a key channel: g, cum [B, nc, H, C, Dk]
        cum = jnp.cumsum(g, axis=-2)
        rows = jnp.arange(C)
        A = jnp.where(
            rows[:, None] > rows[None, :],
            beta[..., None] * _decayed_products(k, k, cum, SUB_BLOCK), 0.0,
        )
        inv = unit_lower_inverse(A)
        gamma = jnp.exp(cum)
        w_v = mm("bnhts,bnhsv->bnhtv", inv, beta[..., None] * v)
        w_k = mm("bnhts,bnhsk->bnhtk", inv, beta[..., None] * gamma * k)
        qk = _decayed_products(q, k, cum, SUB_BLOCK)    # s <= t
        # a chunk's keys decayed to its end, and the whole chunk's decay
        k_end = jnp.exp(cum[..., -1:, :] - cum) * k
        whole = gamma[..., -1, :]                       # [B, nc, H, Dk]
        q_in = gamma * q
    else:
        # log decay from the chunk's start to each position, inclusive
        cum = jnp.cumsum(g, axis=-1)                    # [B, nc, H, C]
        # position t against s, decayed over (s, t]; the mask goes on
        # before the exp (above the diagonal the difference is positive)
        seg = cum[..., :, None] - cum[..., None, :]
        rows = jnp.arange(C)
        decay = jnp.exp(
            jnp.where(rows[:, None] >= rows[None, :], seg, -jnp.inf)
        )
        kk = mm("bnhtk,bnhsk->bnhts", k, k)
        A = jnp.where(
            rows[:, None] > rows[None, :], beta[..., None] * decay * kk, 0.0
        )
        inv = unit_lower_inverse(A)
        gamma = jnp.exp(cum)
        w_v = mm("bnhts,bnhsv->bnhtv", inv, beta[..., None] * v)
        w_k = mm("bnhts,bnhsk->bnhtk", inv, (beta * gamma)[..., None] * k)
        qk = decay * mm("bnhtk,bnhsk->bnhts", q, k)     # s <= t
        # a chunk's keys decayed to its end, and the whole chunk's decay
        k_end = jnp.exp(cum[..., -1:] - cum)[..., None] * k
        whole = gamma[..., -1]
        q_in = gamma[..., None] * q
    # a head's decay over all of its state, a channel's over its row
    over_state = (..., None) if channel else (..., None, None)

    def boundary(S, c):
        w_v_c, w_k_c, qk_c, k_end_c, whole_c, q_in_c = c
        u = w_v_c - mm("bhtk,bhkv->bhtv", w_k_c, S)
        o = mm("bhtk,bhkv->bhtv", q_in_c, S) + mm("bhts,bhsv->bhtv", qk_c, u)
        S = whole_c[over_state] * S + mm("bhtk,bhtv->bhkv", k_end_c, u)
        return S, o

    last, o = lax.scan(
        boundary, h0.astype(f32),
        tuple(
            jnp.moveaxis(a, 1, 0)
            for a in (w_v, w_k, qk, k_end, whole, q_in)
        ),
    )
    # [nc, B, H, C, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, nc * C, H, Dv)
    return o[:, :T], last


def delta_step_xla(
    state: jax.Array,   # float32 [L, B, Dk, H * Dv], every layer's
    layer: jax.Array,   # int32 scalar: which of the L
    q: jax.Array,       # [B, H, Dk]
    k: jax.Array,       # [B, H, Dk]
    v: jax.Array,       # [B, H, Dv]
    g: jax.Array,       # float32 [B, H], or [B, H, Dk] a key channel
    beta: jax.Array,    # float32 [B, H]
) -> Tuple[jax.Array, jax.Array]:
    """One position for every slot, as XLA operations: ``(o float32
    [B, H, Dv], state)`` with layer ``layer`` of the state moved on."""
    H = q.shape[1]
    f32 = jnp.float32
    S = state_heads(
        lax.dynamic_index_in_dim(state, layer, 0, keepdims=False), H
    ).astype(f32)
    q, k, v = (a.astype(f32) for a in (q, k, v))
    a = jnp.exp(g.astype(f32))
    S = (a[..., None] if g.ndim == 3 else a[..., None, None]) * S
    u = beta.astype(f32)[..., None] * (
        v - jnp.sum(S * k[..., None], axis=-2)
    )
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.sum(S * q[..., None], axis=-2)
    return o, lax.dynamic_update_index_in_dim(
        state, state_layout(S).astype(state.dtype), layer, 0
    )


def heads_a_group(heads: int, value_width: int) -> int:
    """How many heads the kernel takes at a time: the fewest whose values
    side by side are whole lane tiles (two of 192), so that every slice
    of the state's lanes starts on a tile; all of them where no number
    of heads that divides ``heads`` does (the tests' widths)."""
    group = _LANES // math.gcd(value_width, _LANES)
    return group if heads % group == 0 else heads


def _update_kernel(
    live_ref, name_ref, layer_ref, q_ref, k_ref, *rest,
    heads: int, width: int, group: int, channel: bool,
):
    """Grid point = one slot: its state ``[Dk, heads * width]``. ``q``
    and ``k`` come ``[Dk, heads]``, a head's values a column, which
    broadcasts over the lanes as it is. With one decay a head ``row`` is
    ``[3, heads * width]``: the decay, ``beta`` and ``v``, a head's
    ``width`` lanes alike for the first two. With a decay a key channel
    (``channel``) the decay comes as ``q`` and ``k`` do, ``a [Dk,
    heads]``, a head's a column: sublane row ``c`` of head ``j``'s lanes
    is multiplied by ``a[c, j]``; ``row`` is then ``[2, heads * width]``,
    ``beta`` and ``v``."""
    del name_ref, layer_ref
    if channel:
        a_ref, row_ref, s_ref, s_out_ref, o_ref = rest
    else:
        row_ref, s_ref, s_out_ref, o_ref = rest
    at = 0 if channel else 1        # where ``beta`` lies in ``row``
    b = pl.program_id(0)
    span = group * width

    @pl.when(live_ref[b] > 0)
    def _slot():
        head_of_lane = lax.broadcasted_iota(
            jnp.int32, (s_ref.shape[0], span), 1
        ) // width

        def column(ref, first):
            """Heads ``first .. first + group`` of ``ref``, each over its
            own ``width`` lanes: ``[Dk, span]``."""
            out = ref[:, first:first + 1]
            for j in range(1, group):
                out = jnp.where(
                    head_of_lane >= j, ref[:, first + j:first + j + 1], out
                )
            return out

        for first in range(0, heads, group):
            lanes = slice(first * width, first * width + span)
            k = column(k_ref, first)
            decay = column(a_ref, first) if channel else row_ref[0:1, lanes]
            S = decay * s_ref[:, lanes].astype(jnp.float32)
            u = row_ref[at:at + 1, lanes] * (
                row_ref[at + 1:at + 2, lanes]
                - jnp.sum(S * k, axis=0, keepdims=True)
            )
            S = S + k * u
            s_out_ref[:, lanes] = S.astype(s_out_ref.dtype)
            o_ref[:, lanes] = jnp.sum(
                S * column(q_ref, first), axis=0, keepdims=True
            )

    @pl.when(live_ref[b] == 0)
    def _nobody():
        o_ref[...] = jnp.zeros_like(o_ref)


def delta_state_update(
    state: jax.Array,   # [L, B, Dk, H * Dv], every layer's, as stored
    layer: jax.Array,   # int32 scalar: which of the L
    q: jax.Array,       # [B, H, Dk]
    k: jax.Array,       # [B, H, Dk]
    v: jax.Array,       # [B, H, Dv]
    g: jax.Array,       # float32 [B, H], or [B, H, Dk] a key channel
    beta: jax.Array,    # float32 [B, H]
    live: jax.Array,    # bool [B]: the slots somebody holds
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`delta_step_xla` for the live slots, the stacked state read
    and written where it lies (donated and aliased: the result is the
    same buffer): ``(o float32 [B, H, Dv], state)``. A slot that is not
    live keeps its state, unread, and gives zeros. The decay's shape
    chooses the call: one a head rides the lanes of ``row``
    (``delta_state_update``), one a key channel goes in a column a head
    beside ``q`` and ``k`` (``kda_state_update``)."""
    L, B, Dk, width = state.shape
    H, Dv = v.shape[1:]
    f32 = jnp.float32
    channel = g.ndim == 3
    # a head's values a column: [B, Dk, H]
    q, k = (jnp.swapaxes(a.astype(f32), 1, 2) for a in (q, k))
    decay = jnp.exp(g.astype(f32))
    # the decay: a column a head beside q and k, or a head's lanes alike
    columns = (q, k) + ((jnp.swapaxes(decay, 1, 2),) if channel else ())
    row = jnp.stack(
        ([] if channel else [jnp.repeat(decay, Dv, axis=1)]) + [
            jnp.repeat(beta.astype(f32), Dv, axis=1),
            v.astype(f32).reshape(B, width),
        ],
        axis=1,
    )                                           # [B, 3 or 2, H * Dv]
    # a slot nobody holds names the nearest live slot before it (before
    # the first live one, that one), whose block is resident already
    slots = jnp.arange(B, dtype=jnp.int32)
    before = lax.cummax(jnp.where(live, slots, -1))
    name = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))

    def small(b, *_):
        return (b, 0, 0)

    def block(b, live_ref, name_ref, layer_ref):
        return (layer_ref[0], name_ref[b], 0, 0)

    state_spec = pl.BlockSpec((None, None, Dk, width), block)
    state, o = pl.pallas_call(
        functools.partial(
            _update_kernel, heads=H, width=Dv, group=heads_a_group(H, Dv),
            channel=channel,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, 1, width), f32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                *(pl.BlockSpec((None, Dk, H), small) for _ in columns),
                pl.BlockSpec((None, row.shape[1], width), small),
                state_spec,
            ],
            out_specs=[state_spec, pl.BlockSpec((None, 1, width), small)],
        ),
        # the last operand (after the three prefetched, the columns and
        # the row) is the state: result 0
        input_output_aliases={4 + len(columns): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 2**20,
        ),
        name="kda_state_update" if channel else "delta_state_update",
        interpret=interpret,
    )(
        live.astype(jnp.int32), name,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        *columns, row, state,
    )
    return o.reshape(B, H, Dv), state
