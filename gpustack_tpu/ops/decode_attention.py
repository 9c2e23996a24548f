"""Pallas TPU kernel for a decode step's attention over a GQA cache.

A decode step has one query row a slot and reads every cached row the
slot attends: it is bound by the memory's rate, and what it must read is
the **live** rows, not the allocation. The XLA form (``_attend`` over a
layer's slab, ``models/transformer.py``) slices the slab out of the
stacked cache, relays it out and scores all ``slots x max_len``
positions before it masks them (7.0 of a 23.9 ms program at Qwen3-8B's
widths, 12 slots of 2,048: PERF.md section 6, PR 41). Here:

- both caches are passed **whole, as they are stored**, and the layer's
  index prefetched, as ``ops/mla_attention.py`` does and for its reason:
  a custom call cannot read the scan's slice in place, and a sliced
  operand is copied before every call;
- a grid point is (slot, block of cached positions). Each slot's
  ``length`` (how many positions it attends; 0 for a slot nobody holds)
  is a scalar-prefetch operand and bounds the work two ways: a block at
  or above it is never computed, and never fetched, because the index
  map names the block already resident instead (a point that names the
  resident block copies nothing). A slot of length 0 names the block its
  predecessor left, so it reads nothing of the cache at all, and its
  result is zeros (:func:`slot_walk`);
- the stored layout decides the view. The TPU stores ``[S, Hkv, hd]``
  with ``Hkv`` on the sublanes of a tile, unpadded (``T(8,128)(2,1)`` at
  8 kv heads, ``T(4,128)(2,1)`` at 4), so positions and heads merge for
  free into ``[S * Hkv, hd]`` rows, and a per-head view would be a
  strided load of packed rows. So one product scores all ``Hq`` query
  heads against a block's ``block_s * Hkv`` rows, and the columns of the
  other kv heads get ``-1e30`` before the softmax: exact (their weight
  is 0), and the ``Hkv``-fold surplus costs the MXU nothing it was not
  idle for, since its time is pushing the block through as weights
  whatever the few query rows;
- online softmax in float32, running max and sum broadcast over the
  lanes (``ops/flash_attention.py`` says what a ``[rows, 1]`` column
  costs). The products take the operands as they are stored, in bf16 on
  the served path, with float32 accumulation, and the weights go to the
  values' dtype for ``P @ V``, as ``_attend``'s do.

``tests/ops/test_decode_attention.py`` holds it to ``_attend`` in
interpret mode; ``tests/ops/test_chip_compile.py`` compiles the decode
programs of the benchmark's two Qwen3 deployments for a described v5e
and holds them to no copy, slice or transpose of the cache or a slab.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG = -1e30
# cached positions a grid point, largest first
_BLOCKS = (1024, 512, 256, 128)
# the most a GQA block takes, in positions and in bytes of K (or of V).
# A slot's last block is fetched whole, so a large block reads past the
# length, and a small one makes grid points, at 0.2-0.35 us each
# whatever they do: on the chip (hack/decode_attn_bench.py, PERF.md
# section 6, PR 41) 512 beat 1,024 by 10-30 % wherever slots were part
# full (8 of 12 slots at 1,450: 84 us a layer against 104; 5 at 350: 31
# against 45), beat 256 where most slots were dead (8 of 32: 60 against
# 77) and drew level with both where every slot was full. The bytes keep
# a point (K, V, two buffers each, float32 scores beside them) inside
# the 16 MiB of VMEM a kernel is given by default at any head count.
_GQA_POSITIONS = 512
_GQA_BLOCK_BYTES = 2 * 1024 * 1024


def block_positions(max_len: int, most: int = _BLOCKS[0]) -> Optional[int]:
    """The block of cached positions for a cache of ``max_len``: the
    largest of ``_BLOCKS`` of at most ``most`` that divides it; a cache
    shorter than the smallest is one block (the tests'); None where
    nothing divides, and the caller takes the XLA form."""
    for b in _BLOCKS:
        if b <= most and max_len % b == 0:
            return b
    if max_len < _BLOCKS[-1] and max_len % 8 == 0:
        return max_len
    return None


def gqa_block_positions(
    max_len: int, kv_heads: int, head_dim: int, itemsize: int = 2
) -> Optional[int]:
    """:func:`block_positions` for a cache of ``kv_heads`` heads of
    ``head_dim``. None too where ``head_dim`` is no whole number of lane
    tiles (the merged view of positions and heads is then not the stored
    one)."""
    if head_dim % _LANES:
        return None
    fit = _GQA_BLOCK_BYTES // (kv_heads * head_dim * itemsize)
    return block_positions(max_len, min(_GQA_POSITIONS, fit))


class Walk(NamedTuple):
    """What a kernel's walk over (slot, block) needs of the slots'
    lengths, its scalar-prefetch operands: :func:`slot_walk`."""

    lengths: jax.Array    # int32 [B]: positions each slot attends, 0..S
    slot: jax.Array       # int32 [B]  what a slot's grid points name once
    block: jax.Array      # int32 [B]  past its live blocks: (slot, block)
    block_s: int          # positions a block


def slot_walk(lengths: jax.Array, max_len: int, block_s: int) -> Walk:
    """The :class:`Walk` for slots of ``lengths`` (clipped to the cache)
    in blocks of ``block_s``. Past its live blocks a slot's grid points
    name what is resident already, so that nothing is copied: a live
    slot its own last block; a slot of length 0 what the walk over the
    slots left there, the last block of the nearest live slot before it
    or, before the first live slot, that slot's block 0 (fetched early,
    once).

    The same for every layer of a step, and a dozen small operations: a
    caller with a scan over the layers makes it before the scan (inside,
    XLA makes it again every layer) and hands it to the kernel in place
    of the lengths."""
    lengths = jnp.clip(lengths, 0, max_len).astype(jnp.int32)
    slots = jnp.arange(lengths.shape[0], dtype=jnp.int32)
    live = lengths > 0
    before = lax.cummax(jnp.where(live, slots, -1))
    slot = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    last = jnp.maximum(lengths - 1, 0) // block_s
    return Walk(
        lengths, slot, jnp.where(before >= 0, last[slot], 0), block_s
    )


def cached_block(b, j, len_ref, slot_ref, block_ref, layer_ref):
    """The ``(layer, slot, block)`` of the cache that grid point
    ``(b, j)`` names: the slot's own block ``j`` while it has positions
    there, then what :func:`slot_walk` says stays."""
    held = block_ref[b]
    return (
        layer_ref[0], slot_ref[b],
        jnp.where(len_ref[b] > 0, jnp.minimum(j, held), held),
    )


def gqa_walk(lengths: jax.Array, k_cache: jax.Array) -> Walk:
    """:func:`slot_walk` over a GQA cache ``[L, B, S, Hkv, hd]`` (a ring
    of window rows is such a cache, its lengths the live rows')."""
    S, Hkv, hd = k_cache.shape[2:]
    block_s = gqa_block_positions(S, Hkv, hd, k_cache.dtype.itemsize)
    if block_s is None:
        raise ValueError(
            f"no block of {_BLOCKS} suits a cache of {S} x {Hkv} x {hd}"
        )
    return slot_walk(lengths, S, block_s)


def _across(x, n: int):
    """``x`` [rows, 128], every lane of a row alike, as [rows, n]."""
    return jnp.tile(x, (1, -(-n // _LANES)))[:, :n]


def _kernel(
    len_ref, slot_ref, block_ref, layer_ref, q_ref, own_ref, k_ref, v_ref,
    o_ref, m_ref, l_ref, acc_ref, *, scale: float, block_s: int,
    kv_heads: int,
):
    """Grid point = (slot, block of cached positions): the slot's ``Hq``
    query heads against the block's ``block_s * kv_heads`` rows."""
    del slot_ref, block_ref, layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    hd = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ahead = len_ref[b] - j * block_s      # positions still to attend

    @pl.when(ahead > 0)
    def _block():
        s = lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                     # [Hq, block_s * kv_heads]
        # a row is (position, kv head): those of a head's own kv head
        # (``own``: 0 there, -1e30 elsewhere) below the slot's length
        row = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row < ahead * kv_heads, s * scale + own_ref[...], _NEG)
        m_prev, l_prev = m_ref[...], l_ref[...]           # [Hq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across(m_new, s.shape[1]))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _across(corr, hd) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / _across(jnp.maximum(l_ref[...], 1e-30), hd)
        ).astype(o_ref.dtype)


def gqa_decode_attention(
    q: jax.Array,         # [B, Hq, hd]: one query row a slot, rotated
    k_cache: jax.Array,   # [L, B, S, Hkv, hd]: KVCache.k, as stored
    v_cache: jax.Array,   # [L, B, S, Hkv, hd]: KVCache.v
    layer: jax.Array,     # int32 scalar: which of the L
    lengths,              # int32 [B]: positions each slot attends, 0..S,
                          # or their Walk (gqa_walk), made once a step
    scale: float,
    *,
    interpret: bool = False,
    name: str = "gqa_decode_attention",
) -> jax.Array:
    """``softmax(q . k * scale) @ v`` for every query head over positions
    ``0 .. lengths[b] - 1`` of slot ``b``'s rows of its kv head in layer
    ``layer``: ``[B, Hq * hd]`` in ``q``'s dtype. A slot of length 0
    gives zeros and reads none of its rows. ``name``: the call's name in
    the compiled program and the profiler's trace (a sliding layer's
    walk over its ring of window rows goes under one of its own)."""
    B, Hq, hd = q.shape
    L, _, S, Hkv, _ = k_cache.shape
    walk = lengths if isinstance(lengths, Walk) else gqa_walk(lengths, k_cache)
    block_s = walk.block_s
    rows = block_s * Hkv
    # 0 where a row of the merged view is of the query head's kv head: a
    # constant of the program (as operations it is made every layer)
    own = jnp.asarray(np.where(
        np.arange(rows)[None, :] % Hkv
        == np.arange(Hq)[:, None] // (Hq // Hkv),
        0.0, _NEG,
    ), jnp.float32)

    def q_block(b, j, *_):
        return (b, 0, 0)

    def own_block(b, j, *_):
        return (0, 0)

    def kv_block(b, j, *prefetched):
        return (*cached_block(b, j, *prefetched), 0)

    kv_spec = pl.BlockSpec((None, None, rows, hd), kv_block)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_s=block_s, kv_heads=Hkv
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, S // block_s),
            in_specs=[
                pl.BlockSpec((None, Hq, hd), q_block),
                pl.BlockSpec((Hq, rows), own_block),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((None, Hq, hd), q_block),
            scratch_shapes=[
                pltpu.VMEM((Hq, _LANES), jnp.float32),    # running max
                pltpu.VMEM((Hq, _LANES), jnp.float32),    # running sum
                pltpu.VMEM((Hq, hd), jnp.float32),        # accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name=name,
        interpret=interpret,
    )(
        walk.lengths, walk.slot, walk.block,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        q, own,
        k_cache.reshape(L, B, S * Hkv, hd),
        v_cache.reshape(L, B, S * Hkv, hd),
    )
    return out.reshape(B, Hq * hd)
