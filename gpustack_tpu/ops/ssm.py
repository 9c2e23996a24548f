"""The selective state-space scan of a Mamba-2 mixer (SSD), two forms.

A head ``h`` of width ``P`` keeps a state ``S [P, N]`` and moves it one
position at a time::

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (outer) B_t
    y_t = S_t C_t                       (D * x_t is the caller's to add)

``A`` is one negative number a head, ``dt_t > 0`` one a head and
position, ``B_t`` and ``C_t`` (``N`` wide) are shared by the heads of a
group. A position with ``dt_t = 0`` leaves the state as it was and adds
nothing: that is how a caller keeps padding out of it.

- :func:`ssm_chunk_scan`, for ``T > 1``: the published chunked
  algorithm. Within a chunk of ``Q`` positions the outputs are one
  decay-masked ``(C B^T)`` product against the inputs, no state formed;
  each chunk's own contribution to the state is one product more; the
  states at the chunk boundaries follow from a scan over the ``T / Q``
  chunks, and each position adds what the state at its chunk's start
  gives it. Plain einsums in float32: nothing here is a kernel until a
  trace shows these at more than twice what their operations ask.
- :func:`ssm_state_update`, for a decode step (``T == 1``): a Pallas
  kernel that reads and writes the **stacked** state ``[L, B, H, P, N]``
  in place, the layer's index prefetched (a custom call cannot take the
  scan's slice in place; a sliced operand is copied whole before every
  call), one slot a grid point and **live slots only**: a slot nobody
  holds names the block already resident, so nothing of it is read or
  written (``ops/decode_attention.py slot_walk``'s way). The update is
  elementwise in float32 on the vector unit; the state never passes
  through a matmul's rounding. :func:`ssm_step_xla` is the same step as
  XLA operations, for any other platform and for the tests.

  Which unit does what in the kernel's body. The state lies ``[P, N]``
  a head with the contracted width ``N`` on the lanes, so a register of
  state (8 rows of ``P``) needs its rows' ``dt x`` on every lane, and
  its rows' sums over the lanes for ``y``: two crossings of the lanes a
  register, and on a v5e the three cross-lane units' time for them,
  not the 2 MB a slot moves, was what a call took (PERF.md section 6,
  PR 55). So: the *vector unit* does the arithmetic (two products and a
  sum a register for the state, one more product for the readout), with
  the decay one scalar a head from SMEM; the *matrix unit* does no
  arithmetic, only the spreading of ``dt x`` over the lanes (a head's
  three bfloat16 parts against ones: three exact products and their
  exact sum, :func:`_update_kernel`); the *cross-lane units* are left
  the readout's one reduction a register, which is what they are then
  busy with for four fifths of the body. ``ops/delta_rule.py``'s
  kernel has the same outside and needs none of this: its state lies
  with the contracted axis on the **sublanes**, where a reduction is
  additions of whole registers.

``tests/ops/test_ssm.py`` holds all three to the recurrence taken one
position at a time.

One of two kinds of state a slot can keep in ``KVCache.ssm``
(``ModelConfig.state_shapes`` is the one place that says either's
shape): this rule *adds* an outer product to a decayed state;
``ops/delta_rule.py``'s *corrects* the state through itself, in the same
two forms and with the same way of keeping dead slots and padding out.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ssm_chunk_scan(
    x: jax.Array,     # [B, T, H, P]
    dt: jax.Array,    # float32 [B, T, H], after its softplus; 0 = skip
    A: jax.Array,     # float32 [H], negative
    Bm: jax.Array,    # [B, T, G, N]
    Cm: jax.Array,    # [B, T, G, N]
    h0: jax.Array,    # float32 [B, H, P, N]: the state before position 0
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """``(y float32 [B, T, H, P], state float32 [B, H, P, N] after the
    last position)``. ``T`` need be no multiple of ``chunk``: the tail is
    padded with positions of ``dt = 0``."""
    Bt, T, H, P = x.shape
    G, N = Bm.shape[2:]
    r, Q = H // G, chunk
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, Bm, Cm)
        )
    nc = (T + pad) // Q
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(Bt, nc, Q, G, r)
    xdt = x.astype(f32).reshape(Bt, nc, Q, G, r, P) * dt[..., None]
    Bm = Bm.astype(f32).reshape(Bt, nc, Q, G, N)
    Cm = Cm.astype(f32).reshape(Bt, nc, Q, G, N)
    # log-decay from the chunk's start to each position, inclusive
    cum = jnp.cumsum(dt * A.astype(f32).reshape(G, r), axis=2)
    # within a chunk: position t takes position s <= t, decayed over
    # (s, t]; the mask goes on before the exp (above the diagonal the
    # difference is positive and may overflow)
    seg = cum[:, :, :, None] - cum[:, :, None, :]       # [B, nc, t, s, G, r]
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bctsg", Cm, Bm)
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", cb[..., None] * decay, xdt)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    added = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bm, xdt * to_end[..., None])
    whole = jnp.exp(cum[:, :, -1])                      # [B, nc, G, r]

    def boundary(h, chunk_c):
        added_c, whole_c = chunk_c
        return whole_c[..., None, None] * h + added_c, h

    last, starts = lax.scan(
        boundary, h0.astype(f32).reshape(Bt, G, r, P, N),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)),
    )
    starts = jnp.moveaxis(starts, 0, 1)                 # [B, nc, G, r, P, N]
    y = y + jnp.einsum(
        "bctgn,bcgrpn->bctgrp", Cm, starts
    ) * jnp.exp(cum)[..., None]
    return (
        y.reshape(Bt, nc * Q, H, P)[:, :T],
        last.reshape(Bt, H, P, N),
    )


def ssm_step_xla(
    state: jax.Array,   # float32 [L, B, H, P, N], every layer's
    layer: jax.Array,   # int32 scalar: which of the L
    x: jax.Array,       # [B, H, P]
    dt: jax.Array,      # float32 [B, H]
    A: jax.Array,       # float32 [H]
    Bm: jax.Array,      # [B, G, N]
    Cm: jax.Array,      # [B, G, N]
) -> Tuple[jax.Array, jax.Array]:
    """One position for every slot, as XLA operations: ``(y float32
    [B, H, P], state)`` with layer ``layer`` of the state moved on."""
    H, G = x.shape[1], Bm.shape[1]
    f32 = jnp.float32
    h = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    Bh = jnp.repeat(Bm.astype(f32), H // G, axis=1)     # [B, H, N]
    Ch = jnp.repeat(Cm.astype(f32), H // G, axis=1)
    dt = dt.astype(f32)
    new = (
        jnp.exp(dt * A.astype(f32))[..., None, None] * h.astype(f32)
        + (dt[..., None] * x.astype(f32))[..., None] * Bh[:, :, None, :]
    )
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1)
    return y, lax.dynamic_update_index_in_dim(
        state, new.astype(state.dtype), layer, 0
    )


# how many heads' ``dt x`` one tile of 128 lanes holds as three parts
_HEADS_A_TILE = 128 // 3


def _bf16_parts(v: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``v`` (float32) as three float32 arrays, each exactly a bfloat16
    (the top 8 bits of what the ones before it left) and summing to ``v``
    exactly, in any order."""
    def top(a):
        bits = lax.bitcast_convert_type(a, jnp.uint32)
        return lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32
        )

    first = top(v)
    rest = v - first
    second = top(rest)
    return first, second, rest - second


def _update_kernel(
    live_ref, name_ref, layer_ref, da_ref, xdt_ref, b_ref, c_ref, h_ref,
    h_out_ref, y_ref, *, heads: int, per_group: int,
):
    """Grid point = one slot: its ``heads`` states ``[P, N]``, ``N`` on
    the lanes, a head at a time. ``B`` and ``C`` come ``[G, N]``, a
    group's a row, which broadcasts over the sublanes; the decay is one
    scalar a head, read from SMEM. ``dt x`` is one number a row of the
    state and has to lie on every lane of that row: ``xdt_ref`` holds it
    ``[P, tiles * 128]``, a tile the three bfloat16 parts
    (:func:`_bf16_parts`) of up to ``_HEADS_A_TILE`` heads, and the
    matrix unit spreads it: a head's three lanes, the others masked,
    against a matrix of ones is each row's ``dt x`` on every lane,
    exactly. The cross-lane units are left the readout's reduction, one
    a register."""
    del name_ref, layer_ref
    b = pl.program_id(0)

    @pl.when(live_ref[b] > 0)
    def _slot():
        lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        ones = jnp.ones((128, h_ref.shape[-1]), jnp.bfloat16)
        for j in range(heads):
            g = j // per_group
            tile, at = divmod(j, _HEADS_A_TILE)
            w = min(_HEADS_A_TILE, heads - tile * _HEADS_A_TILE)
            mine = (lane == at) | (lane == w + at) | (lane == 2 * w + at)
            xdt = jnp.dot(
                jnp.where(
                    mine, xdt_ref[:, tile * 128:(tile + 1) * 128], 0.0
                ).astype(jnp.bfloat16),
                ones, preferred_element_type=jnp.float32,
            )
            new = (
                da_ref[b, j] * h_ref[j].astype(jnp.float32)
                + xdt * b_ref[g:g + 1, :]
            )
            h_out_ref[j] = new.astype(h_out_ref.dtype)
            y_ref[:, j:j + 1] = jnp.sum(
                new * c_ref[g:g + 1, :], axis=1, keepdims=True
            )

    @pl.when(live_ref[b] == 0)
    def _nobody():
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_state_update(
    state: jax.Array,   # [L, B, H, P, N], every layer's, as stored
    layer: jax.Array,   # int32 scalar: which of the L
    x: jax.Array,       # [B, H, P]
    dt: jax.Array,      # float32 [B, H]
    A: jax.Array,       # float32 [H]
    Bm: jax.Array,      # [B, G, N]
    Cm: jax.Array,      # [B, G, N]
    live: jax.Array,    # bool [B]: the slots somebody holds
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`ssm_step_xla` for the live slots, the stacked state read
    and written where it lies (donated and aliased: the result is the
    same buffer): ``(y float32 [B, H, P], state)``. A slot that is not
    live keeps its state, unread, and gives zeros."""
    L, B, H, P, N = state.shape
    G = Bm.shape[1]
    f32 = jnp.float32
    dt = dt.astype(f32)
    # [B, P, tiles * 128]: a row of the state a sublane; a tile its
    # heads' first parts, then their second, then their third
    parts = _bf16_parts(jnp.swapaxes(dt[..., None] * x.astype(f32), 1, 2))
    xdt = jnp.concatenate([
        jnp.pad(
            jnp.concatenate(
                [p[..., first:first + _HEADS_A_TILE] for p in parts], axis=-1
            ),
            ((0, 0), (0, 0), (0, 128 - 3 * min(_HEADS_A_TILE, H - first))),
        )
        for first in range(0, H, _HEADS_A_TILE)
    ], axis=-1)
    # a slot nobody holds names the nearest live slot before it (before
    # the first live one, that one), whose block is resident already
    slots = jnp.arange(B, dtype=jnp.int32)
    before = lax.cummax(jnp.where(live, slots, -1))
    name = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))

    def small(b, *_):
        return (b, 0, 0)

    def block(b, live_ref, name_ref, layer_ref, da_ref):
        return (layer_ref[0], name_ref[b], 0, 0, 0)

    state_spec = pl.BlockSpec((None, None, H, P, N), block)
    state, y = pl.pallas_call(
        functools.partial(_update_kernel, heads=H, per_group=H // G),
        out_shape=(
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B, P, H), f32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, P, xdt.shape[-1]), small),
                pl.BlockSpec((None, G, N), small),
                pl.BlockSpec((None, G, N), small),
                state_spec,
            ],
            out_specs=[state_spec, pl.BlockSpec((None, P, H), small)],
        ),
        # operand 7 (after the four prefetched) is the state: result 0
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 2**20,
        ),
        name="ssm_state_update",
        interpret=interpret,
    )(
        live.astype(jnp.int32), name,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.exp(dt * A.astype(f32)),
        xdt, Bm.astype(f32), Cm.astype(f32), state,
    )
    return jnp.swapaxes(y, 1, 2), state
