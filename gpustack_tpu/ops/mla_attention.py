"""Pallas TPU kernels for latent attention: a decode step's attention
over the latent cache and the write of its latent rows and of its rope
keys, and the decompressed prefill's attention over the step's own rows.

Latent attention (MLA, DeepSeek-V2/V3 family) caches, for each position
of a layer, the shared latent ``c_kv`` (``kv_lora_rank`` wide) and the
shared rope key (``qk_rope_head_dim`` wide). In the absorbed form every
query head attends over those as over one key/value head: the score of a
head is ``q_lat . c_kv + q_pe . k_r`` (``q_lat`` the head's ``nope`` part
through ``W_uk``) and its result the softmax-weighted sum of the
``c_kv``, which ``W_uv`` then takes to the head's value width
(``models/transformer.py``, ``mla_attention``).

One grid point holds all ``H`` query heads of one slot against one block
of that slot's cached positions: the block is fetched once for all heads
(at 64 heads about 128 operations a byte, near a v5e's ridge), the
running max / sum / accumulator persist in VMEM across the sweep over
the blocks (online softmax, float32), and the ``[H, S]`` score matrix
never exists in HBM. A slot's length (how many positions it attends, a
scalar-prefetch operand; 0 for a slot nobody holds) bounds its work two
ways: a block at or above it is never fetched (the index map names the
block already resident instead, and such a point copies nothing:
``ops/decode_attention.py slot_walk``) and never computed. A slot
of length 0 reads nothing and gives zeros.

Both caches are passed with all their layers, as they are stored, and the
layer's index prefetched: a custom call cannot read the scan's slice in
place as a fusion can, and a sliced operand would be copied whole before
every call (134 MB a layer at 16 slots of 8,192: PERF.md section 6,
PR 34 met the same with the experts' weights). "As they are stored" is
the TPU's choice, not the logical shape's: the latent, 512 wide, lies
with the positions next to the width; the rope keys, 64 wide, lie with
the **positions on the lanes**, so the view ``[L, B, rope, S]`` taken
here is free and the score product
over them a plain matmul (``tests/ops/test_chip_compile.py`` holds the
decode program to having no copy of either cache).

That layout is also why the step's rope keys are written here
(:func:`mla_write_rope_keys`) and not by ``transformer._write_rows``'
scatter: one position of a slot is one **lane** of 64 sublanes, which
no XLA form writes in place. An aliased call on the same view does: a
grid point a slot fetches the tile of 128 positions that holds the
slot's position, puts the key on its lane and writes the tile back,
16 KB each way, where the pass it replaces read and wrote the layer and
had the array copied whole in and out of the scan (1.8 of A.X-K1's
9.3 ms step: PERF.md section 6, PR 54).

The latent's rows lie as a scatter wants them, and a scatter writes them
in place; but the TPU runs a scatter of ``B`` windows as a loop of one
small update a slot, four operations each, one after another (0.86 of
A.X-K1's 7.43 ms step at 12 layers of 16 slots: PERF.md section 6,
PR 57). :func:`mla_write_latent_rows` is the same aliased call turned
round: a grid point a slot fetches the tile of 16 positions that holds
the slot's position, puts the row on its sublane and writes the tile
back, the slots' tiles in flight behind one another.

A **prefill from position 0** attends over its own rows decompressed
(``k_nope`` and ``v`` a head from ``c_kv``, compute-bound), and until
PR 62 through ``ops/flash_attention.py``'s call, which wants ``[B, H, T,
d]`` operands of one key width: the keys were built out to 64 heads of
192 (the one rope key copied 64 times), q and k relaid head-major, the
query rotated and sliced in copies of their own, the result relaid back:
4.2 ms a layer round a call of 15.2 in A.X-K1's 8,192 program (PERF.md
sections 5 and 6, PR 62's traces). :func:`mla_prefill_attention`
is that kernel's body at a group of one (the same tiles, by import; the
same sums in the same order inside a head) with the latent's operands: the
query, ``k_nope`` and ``v`` **token-major, as their projections make
them**, a head's block the lane tiles of its columns; the rope key one
``[B, T, rope]`` array whose block's index has no head; the score in its
two parts, ``q_nope . k_nope + q_pe . k_pe``; the query's rope part
rotated inside, once a q-block, to the bit as the rotation outside gave
it; the result written token-major, as ``wo`` reads it. Separate from the flash kernel and not an option of it: five
operands against three, a key shared over heads, a rotation, blocks by
columns; its body is what every other model traces at every start.

``tests/ops/test_mla_attention.py`` holds the decode attention to the XLA
formulation, both writes to ``_write_rows``' scatter and pass, bit for
bit, and the prefill's call to ``_attend`` over the keys built out, in
interpret mode; ``tests/ops/test_chip_compile.py`` compiles all four for
a described v5e at A.X-K1's widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gpustack_tpu.ops.decode_attention import (
    _LANES,
    _NEG,
    _across,
    Walk,
    block_positions,
    cached_block,
    slot_walk,
)
from gpustack_tpu.ops.flash_attention import (
    _VMEM_BUDGET,
    SUB_K,
    Tiles,
    _vmem_bytes,
    choose_tiles,
    tiles_of,
)

# positions to a stored tile whose rows are positions: a bf16 tile's 16
# sublanes (two float32 tiles of 8)
_SUBLANES = 16


def _kernel(
    len_ref, slot_ref, block_ref, layer_ref, ql_ref, qp_ref, c_ref, r_ref,
    o_ref, m_ref, l_ref, acc_ref, *, scale: float, block_s: int,
):
    """Grid point = (slot, block of cached positions): the slot's ``H``
    heads against ``block_s`` positions of its latent rows."""
    del slot_ref, block_ref, layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    rank = ql_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(j * block_s < length)
    def _block():
        c = c_ref[...].astype(jnp.float32)                # [block_s, rank]
        s = lax.dot_general(
            ql_ref[...].astype(jnp.float32), c,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + jnp.dot(
            qp_ref[...].astype(jnp.float32),
            r_ref[...].astype(jnp.float32),               # [rope, block_s]
            preferred_element_type=jnp.float32,
        )                                                 # [H, block_s]
        k_idx = j * block_s + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_idx < length, s * scale, _NEG)
        # the running max and sum stay broadcast over 128 lanes (as in
        # ops/flash_attention.py: a [rows, 1] column costs a register
        # for every eight rows)
        m_prev, l_prev = m_ref[...], l_ref[...]           # [H, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across(m_new, block_s))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _across(corr, rank) + jnp.dot(
            p, c, preferred_element_type=jnp.float32
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / _across(jnp.maximum(l_ref[...], 1e-30), rank)
        ).astype(o_ref.dtype)


def mla_walk(lengths: jax.Array, max_len: int) -> Walk:
    """:func:`slot_walk` over a latent cache of ``max_len`` positions, in
    the largest block that divides it: 1,024 x 576 values are 1.2 MB in
    bf16, twice for the two buffers and 2.4 MB more as float32 operands,
    well inside the 16 MiB a kernel is given."""
    block_s = block_positions(max_len)
    if block_s is None:
        raise ValueError(f"no block divides a cache of {max_len}")
    return slot_walk(lengths, max_len, block_s)


def mla_decode_attention(
    q_lat: jax.Array,     # [B, H, rank]: q_nope through W_uk
    q_pe: jax.Array,      # [B, H, rope], rotated
    c_cache: jax.Array,   # [L, B, S, rank]: KVCache.k (MLA), without
    r_cache: jax.Array,   # [L, B, S, rope]: KVCache.v      its one head
    layer: jax.Array,     # int32 scalar: which of the L
    lengths,              # int32 [B]: positions each slot attends, 0..S,
                          # or their Walk (mla_walk), made once a step
    scale: float,
    *,
    interpret: bool = False,
) -> jax.Array:
    """``softmax((q_lat . c + q_pe . k_r) * scale) @ c`` over positions
    ``0 .. lengths[b] - 1`` of slot ``b``'s rows in layer ``layer``:
    ``[B, H, rank]`` in ``q_lat``'s dtype, to go through ``W_uv``; zeros
    for a slot of length 0, whose rows are not read."""
    B, H, rank = q_lat.shape
    L, _, S, rope = r_cache.shape
    walk = lengths if isinstance(lengths, Walk) else mla_walk(lengths, S)
    block_s = walk.block_s
    n_blocks = S // block_s

    def q_block(b, j, *_):
        return (b, 0, 0)

    def c_block(b, j, *prefetched):
        layer, slot, block = cached_block(b, j, *prefetched)
        return (layer, slot, block, 0)

    def r_block(b, j, *prefetched):
        layer, slot, block = cached_block(b, j, *prefetched)
        return (layer, slot, 0, block)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_s=block_s),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_blocks),
            in_specs=[
                pl.BlockSpec((None, H, rank), q_block),
                pl.BlockSpec((None, H, rope), q_block),
                pl.BlockSpec((None, None, block_s, rank), c_block),
                pl.BlockSpec((None, None, rope, block_s), r_block),
            ],
            out_specs=pl.BlockSpec((None, H, rank), q_block),
            scratch_shapes=[
                pltpu.VMEM((H, _LANES), jnp.float32),     # running max
                pltpu.VMEM((H, _LANES), jnp.float32),     # running sum
                pltpu.VMEM((H, rank), jnp.float32),       # accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="mla_decode_attention",
        interpret=interpret,
    )(
        walk.lengths, walk.slot, walk.block,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q_lat, q_pe, c_cache, jnp.transpose(r_cache, (0, 1, 3, 2)),
    )


def _write_kernel(layer_ref, start_ref, new_ref, old_ref, o_ref, *, axis):
    """Grid point = slot: the step's row onto its place along ``axis``
    of the tile, a sublane (0) or a lane (1)."""
    del layer_ref
    place = start_ref[pl.program_id(0)] % old_ref.shape[axis]
    at = lax.broadcasted_iota(jnp.int32, old_ref.shape, axis)
    o_ref[...] = jnp.where(at == place, new_ref[...], old_ref[...])


def _write_a_tile_a_slot(stored, new, layer, start, *, axis, name, interpret):
    """``stored [L, B, ., .]`` (a cache as the TPU stores it, its
    positions on ``2 + axis``) with ``new[b]`` at position ``start[b]``,
    clamped into ``[0, S - 1]``, of ``[layer, b]``, in place: a grid
    point a slot fetches the one tile that holds the position (16
    sublanes or 128 lanes of positions by the whole of the other axis;
    the whole slot where the tile does not divide it), puts the row in
    and writes the tile back into the aliased array."""
    S = stored.shape[2 + axis]
    start = jnp.clip(start, 0, S - 1)
    tile = (_SUBLANES, _LANES)[axis]
    tile = tile if S % tile == 0 else S
    block = list(stored.shape[2:])
    block[axis] = tile

    def stored_tile(b, layer, start):
        at = [0, 0]
        at[axis] = start[b] // tile
        return (layer[0], b, *at)

    spec = pl.BlockSpec((None, None, *block), stored_tile)
    return pl.pallas_call(
        functools.partial(_write_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct(stored.shape, stored.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(stored.shape[1],),
            in_specs=[
                pl.BlockSpec((None, *new.shape[1:]), lambda b, *_: (b, 0, 0)),
                spec,
            ],
            out_specs=spec,
        ),
        # operand 3 (after the two prefetched scalars and the rows)
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        name=name,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), start, new, stored)


def mla_write_rope_keys(
    r_cache: jax.Array,   # [L, B, S, rope]: KVCache.v, without its one head
    k_pe: jax.Array,      # [B, rope]: the step's rope key a slot, rotated
    layer: jax.Array,     # int32 scalar: which of the L
    start: jax.Array,     # int32 [B]: each slot's position
    *,
    interpret: bool = False,
) -> jax.Array:
    """``r_cache`` with ``k_pe[b]`` at ``[layer, b, start[b]]``, a start
    clamped into ``[0, S - 1]`` (``transformer._write_rows``' contract at
    one row a slot; every slot's row is written, held or not).

    In place, on the array as the TPU stores it, positions on the lanes
    (the view :func:`mla_decode_attention` reads): of each slot the one
    tile of 128 positions that holds its row is fetched, the key put on
    its lane, and the tile written back into the aliased cache, 16 KB
    each way a slot. No XLA form writes a column of such an array in
    place: a scatter relays the whole array out and back, the pass over
    the layer's positions reads and writes the layer."""
    view = _write_a_tile_a_slot(
        jnp.transpose(r_cache, (0, 1, 3, 2)), k_pe[:, :, None], layer, start,
        axis=1, name="mla_write_rope_keys", interpret=interpret,
    )
    return jnp.transpose(view, (0, 1, 3, 2))


def mla_write_latent_rows(
    c_cache: jax.Array,   # [L, B, S, rank]: KVCache.k, without its one head
    c_kv: jax.Array,      # [B, rank]: the step's latent a slot, normed
    layer: jax.Array,     # int32 scalar: which of the L
    start: jax.Array,     # int32 [B]: each slot's position
    *,
    interpret: bool = False,
) -> jax.Array:
    """``c_cache`` with ``c_kv[b]`` at ``[layer, b, start[b]]``, a start
    clamped into ``[0, S - 1]``: :func:`mla_write_rope_keys`' contract,
    on the array whose rows the TPU stores as rows.

    In place: of each slot the one tile of 16 positions that holds its
    row is fetched (16 KB at a rank of 512 in bf16), the row put on its
    sublane, and the tile written back into the aliased cache. The
    scatter this stands for writes in place too, but as a loop of one
    update a slot whose four small operations wait on one another."""
    return _write_a_tile_a_slot(
        c_cache, c_kv[:, None, :], layer, start,
        axis=0, name="mla_write_latent_rows", interpret=interpret,
    )


def _prefill_kernel(
    q_ref, cos_ref, sin_ref, kn_ref, kp_ref, v_ref, o_ref,
    qn_ref, qp_ref, m_ref, l_ref, acc_ref,
    *, scale: float, seq_k: int, tiles: Tiles,
):
    """Grid point = (batch row, head, q-block, k-block): the head's
    ``block_q`` query rows against ``block_k`` keys, causal from position
    0. ``ops/flash_attention.py``'s body at a group of one, without an
    offset or a band, the score in its two parts."""
    block_q, _, block_k, unroll = tiles
    n_sub_k = block_k // SUB_K
    h, qb, kb = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nope, rope, dv = kn_ref.shape[1], kp_ref.shape[1], v_ref.shape[1]
    per = q_ref.shape[1] // (nope + rope)     # heads of the fetched q block

    def take_query(at):
        """The head's query out of the fetched columns (static places),
        once a q-block: scaled, as float32, into scratch; the rope part
        rotated on the way, pairs of neighbouring lanes, and rounded as
        the rotation outside a kernel rounds it."""
        qn_ref[...] = q_ref[:, at:at + nope].astype(jnp.float32) * scale
        x = q_ref[:, at + nope:at + nope + rope].astype(jnp.float32)
        if rope < _LANES:       # a lane tile, so that the lanes can roll
            x = jnp.concatenate(
                [x, jnp.zeros((block_q, _LANES - rope), jnp.float32)], axis=1
            )
        even = lax.broadcasted_iota(jnp.int32, x.shape, 1) % 2 == 0
        partner = jnp.where(
            even, pltpu.roll(x, _LANES - 1, 1), pltpu.roll(x, 1, 1)
        )
        x = (
            x * cos_ref[...].astype(jnp.float32)
            + partner * sin_ref[...].astype(jnp.float32)
        ).astype(q_ref.dtype)
        qp_ref[...] = x[:, :rope].astype(jnp.float32) * scale

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for i in range(per):
            pl.when(h % per == i)(
                functools.partial(take_query, i * (nope + rope))
            )

    q_first = qb * block_q          # position of q row 0
    first_sub_k = kb * n_sub_k      # this block's first sub-block, of all

    def update(qn, qp, j, masked):
        """One online-softmax update against keys 128 j .. 128 j + 127 of
        the fetched block; the running max and sum stay broadcast over
        128 lanes all through."""
        k_rows = pl.ds(pl.multiple_of(j * SUB_K, SUB_K), SUB_K)
        nt = (((1,), (1,)), ((), ()))
        s = lax.dot_general(
            qn, kn_ref[k_rows, :].astype(jnp.float32), nt,
            preferred_element_type=jnp.float32,
        ) + lax.dot_general(
            qp, kp_ref[k_rows, :].astype(jnp.float32), nt,
            preferred_element_type=jnp.float32,
        )                                                 # [block_q, 128]
        if masked:
            q_idx = q_first + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_idx = (first_sub_k + j) * SUB_K + lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where((k_idx <= q_idx) & (k_idx < seq_k), s, _NEG)

        m_prev, l_prev = m_ref[...], l_ref[...]           # [block_q, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(s <= _NEG / 2, 0.0, p)
        corr = jnp.where(m_prev <= _NEG / 2, 0.0, jnp.exp(m_prev - m_new))
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _across(corr, dv) + jnp.dot(
            p, v_ref[k_rows, :].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    def sweep(lo, hi, masked, unroll):
        """Sub-blocks lo .. hi - 1 in ascending order, ``unroll`` of them
        to a basic block and then the rest one by one."""
        def some(first, n):
            qn, qp = qn_ref[...], qp_ref[...]
            for t in range(n):
                update(qn, qp, first + t, masked)

        def group(i, carry):
            some(lo + i * unroll, unroll)
            return carry

        def single(j, carry):
            some(j, 1)
            return carry

        if unroll > 1:
            groups = (hi - lo) // unroll
            lax.fori_loop(0, groups, group, 0)
            lo = lo + groups * unroll
        lax.fori_loop(lo, hi, single, 0)

    # sub-blocks j (keys 128 j .. 128 j + 127), counted from key 0: those
    # below n_seen hold a key some row sees; those below n_clear are seen
    # whole by every row and lie inside seq_k, and need no mask
    n_seen = (q_first + block_q - 1) // SUB_K + 1
    n_clear = jnp.minimum(q_first // SUB_K, seq_k // SUB_K)
    clear = jnp.clip(n_clear - first_sub_k, 0, n_sub_k)
    seen = jnp.clip(n_seen - first_sub_k, 0, n_sub_k)
    sweep(0, clear, masked=False, unroll=unroll)
    sweep(clear, seen, masked=True, unroll=1)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / _across(jnp.maximum(l_ref[...], 1e-30), dv)
        ).astype(o_ref.dtype)


def mla_prefill_takes(heads: int, nope: int, rope: int, vd: int) -> bool:
    """Whether :func:`mla_prefill_attention` takes a latent of these
    widths: a head's no-position part and its value are whole lane tiles
    of the projections' columns, and the queries of a whole number of
    heads, ``128 // rope`` of them, are whole lane tiles too."""
    return (
        nope % _LANES == 0 and vd % _LANES == 0
        and rope > 0 and _LANES % rope == 0 and rope % 2 == 0
        and heads % (_LANES // rope) == 0
    )


def mla_prefill_attention(
    q: jax.Array,         # [B, T, H * (nope + rope)]: a head's two parts
                          # side by side, the rope part **not** rotated
    k_nope: jax.Array,    # [B, T, H * nope]: c_kv through W_uk
    k_pe: jax.Array,      # [B, T, rope]: the one rope key, rotated
    v: jax.Array,         # [B, T, H * vd]: c_kv through W_uv
    sin: jax.Array,       # [B, T, rope // 2] float32: the positions'
    cos: jax.Array,       #   rotation (``transformer.rope_sin_cos``)
    scale: float,
    *,
    interpret: bool = False,
    _blocks: tuple[int, int] | None = None,
) -> jax.Array:
    """Causal attention of a latent's decompressed prefill from position
    0, the step's own rows every key there is: ``softmax((q_nope . k_nope
    + rotated(q_pe) . k_pe) * scale) @ v`` a head, ``[B, T, H * vd]``.

    Every operand is token-major, **as the projections make it**, and so
    is the result: a head's block is the lane tiles of its columns
    (:func:`mla_prefill_takes`), so nothing is transposed, built out,
    sliced or padded round the call (``T`` a multiple of 128, as every
    bucket is; any other is padded here). The query comes as ``wq_b``
    makes it, a head's ``nope + rope`` columns side by side: a grid point
    fetches the columns of the ``128 // rope`` heads that make whole lane
    tiles and, once a q-block, takes its head's two parts out of them and
    rotates the rope part in place (the interleaved convention,
    ``transformer.apply_rope_interleaved``: pairs of neighbouring lanes,
    two lane rolls and a select; products in float32, rounded to the
    operands' dtype as the rotation outside a kernel is: to the bit what
    the parent's call was handed). The rope key is one for all heads: its
    block's index has no head, and no key of ``nope + rope`` a head is
    ever made. ``_blocks`` is for the tests and the timer, which go
    through every tile.

    Not taken: the rotation folded into the contraction (the rope part
    twice side by side under its rows' cosines and sines, against the key
    beside its pair-swapped copy, 128 deep). It moves no lane and read
    15.54 ms a call where this reads 15.70 (PERF.md section 6, PR 62),
    and it rounds the two products each where this rounds their sum:
    ``check.reference_logit_err`` moved by 0.0035 and 0.0076 at two
    seeds."""
    B, T, rope = k_pe.shape
    H = (q.shape[2] - k_nope.shape[2]) // rope
    nope, vd = k_nope.shape[2] // H, v.shape[2] // H
    if not mla_prefill_takes(H, nope, rope, vd):
        raise ValueError(
            f"{H} heads of {nope} + {rope} / {vd}: no whole lane tiles"
        )
    per = _LANES // rope
    # the rotation a lane of the rope part: cos, and the sine its partner
    # of the pair is multiplied by (-sin for the even lane, sin for the
    # odd), in the operands' dtype as the rotation outside has them;
    # zeros on the lanes past the rope part
    lanes = ((0, 0), (0, 0), (0, _LANES - rope))
    cos_lanes = jnp.pad(jnp.repeat(cos, 2, axis=2).astype(q.dtype), lanes)
    sin_lanes = jnp.pad(
        jnp.stack([-sin, sin], axis=3).reshape(B, T, rope).astype(q.dtype),
        lanes,
    )
    T_pad = -(-T // SUB_K) * SUB_K
    if T_pad != T:
        q, cos_lanes, sin_lanes, k_nope, k_pe, v = (
            jnp.pad(x, ((0, 0), (0, T_pad - T), (0, 0)))
            for x in (q, cos_lanes, sin_lanes, k_nope, k_pe, v)
        )
    itemsize = q.dtype.itemsize
    if _blocks is None:
        tiles = choose_tiles(T_pad, T_pad, 1, nope + rope, itemsize)
    else:
        tiles = tiles_of(*_blocks, 1)
    block_q, sub_q, block_k, _ = tiles
    if T_pad % block_q or T_pad % block_k or sub_q != block_q:
        raise ValueError(f"{tiles} does not suit {T_pad} rows a head")
    # a tile past the budget asks for its VMEM, as the flash call does;
    # beyond what that counts: the other heads of the fetched query, the
    # rotation's two blocks, and the query in scratch
    need = _vmem_bytes(tiles, 1, nope + rope, itemsize) + block_q * (
        2 * itemsize * ((per - 1) * (nope + rope) + 2 * _LANES)
        + 4 * (nope + _LANES)
    )
    vmem = {"vmem_limit_bytes": need * 8 // 5} if need > _VMEM_BUDGET else {}

    def last_seen(qb, kb):
        # the last block that holds a key the q-block's last row sees: a
        # point past it names that block again and nothing is copied
        return jnp.minimum(kb, ((qb + 1) * block_q - 1) // block_k)

    def q_rows(b, h, qb, kb):
        return (b, qb, 0)

    def k_cols(b, h, qb, kb):
        return (b, last_seen(qb, kb), h)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, scale=scale, seq_k=T, tiles=tiles
        ),
        out_shape=jax.ShapeDtypeStruct((B, T_pad, H * vd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(B, H, T_pad // block_q, T_pad // block_k),
            in_specs=[
                pl.BlockSpec(
                    (None, block_q, per * (nope + rope)),
                    lambda b, h, qb, kb: (b, qb, h // per),
                ),
                pl.BlockSpec((None, block_q, _LANES), q_rows),
                pl.BlockSpec((None, block_q, _LANES), q_rows),
                pl.BlockSpec((None, block_k, nope), k_cols),
                pl.BlockSpec(
                    (None, block_k, rope),
                    lambda b, h, qb, kb: (b, last_seen(qb, kb), 0),
                ),
                pl.BlockSpec((None, block_k, vd), k_cols),
            ],
            out_specs=pl.BlockSpec(
                (None, block_q, vd), lambda b, h, qb, kb: (b, qb, h)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, nope), jnp.float32),     # q_nope * scale
                pltpu.VMEM((block_q, rope), jnp.float32),     # q_pe, rotated
                pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
                pltpu.VMEM((block_q, vd), jnp.float32),       # accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
            **vmem,
        ),
        name="mla_prefill_attention",
        interpret=interpret,
    )(q, cos_lanes, sin_lanes, k_nope, k_pe, v)
    return out if T_pad == T else out[:, :T]
