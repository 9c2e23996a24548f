"""The gated delta rule of a linear-attention mixer, three forms.

A head keeps a matrix state ``S [Dk, Dv]`` (float32) and moves it one
position at a time (Yang et al., "Gated Delta Networks",
arXiv:2412.06464)::

    S_t = a_t S_{t-1} + k_t (outer) beta_t (v_t - (a_t S_{t-1})^T k_t)
    o_t = S_t^T q_t

``a_t = exp(g_t)`` in (0, 1] is one decay a head and position, ``beta_t``
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
  ``(I + A)^-1`` is taken once a chunk for all heads and chunks
  together, by substitution a block at a time (:func:`unit_lower_inverse`:
  ``log2 C`` rounds of two products; the doubling product of the
  nilpotent part, ``prod_j (I + (-A)^(2^j))``, is as many products and
  loses every digit in float32 once keys are alike, its powers growing
  to 1e15 before they cancel); the
  boundary states follow from a scan over the ``T / C`` chunks. Plain
  ``jax.numpy`` in float32 at the highest matmul precision, under the
  scope ``delta_chunk_scan``: nothing here is a kernel until a trace
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
    state [B, H, Dk, Dv] after the last position)``. What the other
    forms are held to; no served program runs it."""
    f32 = jnp.float32

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t                 # [B, H, ...]
        S = jnp.exp(g_t)[..., None, None] * S
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
    g: jax.Array,      # float32 [B, T, H], log decay <= 0; 0 = skip
    beta: jax.Array,   # float32 [B, T, H]; 0 = skip
    h0: jax.Array,     # float32 [B, H, Dk, Dv]: the state before position 0
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """``(o float32 [B, T, H, Dv], state float32 [B, H, Dk, Dv] after the
    last position)``. ``T`` need be no multiple of ``chunk``: the tail is
    padded with positions of ``g = 0, beta = 0``. ``chunk`` is a power
    of two."""
    with jax.named_scope("delta_chunk_scan"):
        return _chunk_scan(q, k, v, g, beta, h0, chunk)


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
    # log decay from the chunk's start to each position, inclusive
    cum = jnp.cumsum(g, axis=-1)                        # [B, nc, H, C]
    # position t against s, decayed over (s, t]; the mask goes on before
    # the exp (above the diagonal the difference is positive)
    seg = cum[..., :, None] - cum[..., None, :]
    rows = jnp.arange(C)
    decay = jnp.exp(jnp.where(rows[:, None] >= rows[None, :], seg, -jnp.inf))
    kk = mm("bnhtk,bnhsk->bnhts", k, k)
    A = jnp.where(
        rows[:, None] > rows[None, :], beta[..., None] * decay * kk, 0.0
    )
    inv = unit_lower_inverse(A)
    gamma = jnp.exp(cum)
    w_v = mm("bnhts,bnhsv->bnhtv", inv, beta[..., None] * v)
    w_k = mm("bnhts,bnhsk->bnhtk", inv, (beta * gamma)[..., None] * k)
    qk = decay * mm("bnhtk,bnhsk->bnhts", q, k)         # s <= t
    # a chunk's keys decayed to its end, and the whole chunk's decay
    k_end = jnp.exp(cum[..., -1:] - cum)[..., None] * k
    whole = gamma[..., -1]
    q_in = gamma[..., None] * q

    def boundary(S, c):
        w_v_c, w_k_c, qk_c, k_end_c, whole_c, q_in_c = c
        u = w_v_c - mm("bhtk,bhkv->bhtv", w_k_c, S)
        o = mm("bhtk,bhkv->bhtv", q_in_c, S) + mm("bhts,bhsv->bhtv", qk_c, u)
        S = whole_c[..., None, None] * S + mm("bhtk,bhtv->bhkv", k_end_c, u)
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
    g: jax.Array,       # float32 [B, H]
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
    S = jnp.exp(g.astype(f32))[..., None, None] * S
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
    live_ref, name_ref, layer_ref, q_ref, k_ref, row_ref, s_ref,
    s_out_ref, o_ref, *, heads: int, width: int, group: int,
):
    """Grid point = one slot: its state ``[Dk, heads * width]``. ``q``
    and ``k`` come ``[Dk, heads]``, a head's values a column, which
    broadcasts over the lanes as it is; ``row`` is ``[3, heads * width]``:
    the decay, ``beta`` and ``v``, a head's ``width`` lanes alike for
    the first two."""
    del name_ref, layer_ref
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
            S = row_ref[0:1, lanes] * s_ref[:, lanes].astype(jnp.float32)
            u = row_ref[1:2, lanes] * (
                row_ref[2:3, lanes] - jnp.sum(S * k, axis=0, keepdims=True)
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
    g: jax.Array,       # float32 [B, H]
    beta: jax.Array,    # float32 [B, H]
    live: jax.Array,    # bool [B]: the slots somebody holds
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`delta_step_xla` for the live slots, the stacked state read
    and written where it lies (donated and aliased: the result is the
    same buffer): ``(o float32 [B, H, Dv], state)``. A slot that is not
    live keeps its state, unread, and gives zeros."""
    L, B, Dk, width = state.shape
    H, Dv = v.shape[1:]
    f32 = jnp.float32
    # a head's values a column: [B, Dk, H]
    q, k = (jnp.swapaxes(a.astype(f32), 1, 2) for a in (q, k))
    row = jnp.stack(
        [
            jnp.repeat(jnp.exp(g.astype(f32)), Dv, axis=1),
            jnp.repeat(beta.astype(f32), Dv, axis=1),
            v.astype(f32).reshape(B, width),
        ],
        axis=1,
    )                                                   # [B, 3, H * Dv]
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
            _update_kernel, heads=H, width=Dv, group=heads_a_group(H, Dv)
        ),
        out_shape=(
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, 1, width), f32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, Dk, H), small),
                pl.BlockSpec((None, Dk, H), small),
                pl.BlockSpec((None, 3, width), small),
                state_spec,
            ],
            out_specs=[state_spec, pl.BlockSpec((None, 1, width), small)],
        ),
        # operand 6 (after the three prefetched) is the state: result 0
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 2**20,
        ),
        name="delta_state_update",
        interpret=interpret,
    )(
        live.astype(jnp.int32), name,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q, k, row, state,
    )
    return o.reshape(B, H, Dv), state
