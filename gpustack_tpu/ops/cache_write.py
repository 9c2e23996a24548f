"""A step's rows into a GQA cache where they lie, as a Pallas TPU call.

``transformer._write_rows`` puts a step's rows into the cache with a
scatter of one block a slot, which XLA compiles for the TPU into a serial
``while`` of one update a slot: a handful of scalar operations an
iteration that wait on one another, slots x layers iterations a step, for
the keys and again for the values (2.5 ms of SDAR's block pass of 11.45 ms
at 32 slots and 12 layers, for 3 MB written; 0.05 ms through the call
here: PERF.md section 6, PR 66).
The call here writes the same bytes as one stored tile a slot, on the
view the decode kernel reads (``ops/decode_attention.py``:
``[L, B, S * heads, width]``, a reshape without a copy), into the aliased
cache.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def a_block_is_whole_tiles(cache: jax.Array, T: int) -> bool:
    """Whether ``T`` rows at a multiple of ``T`` of ``cache [L, B, S,
    heads, width]`` are whole stored tiles of its merged view (16 rows
    of two bytes, 8 of four), so that :func:`gqa_write_block_rows` writes
    them without reading what was there."""
    S, heads = cache.shape[2:4]
    sublanes = 32 // jnp.dtype(cache.dtype).itemsize
    return S % T == 0 and (T * heads) % sublanes == 0


def _block_kernel(
    layer_ref, start_ref, k_ref, v_ref, k_old, v_old, k_out, v_out
):
    """Grid point = slot: the block's rows are the tile."""
    del layer_ref, start_ref, k_old, v_old
    k_out[...] = k_ref[...]
    v_out[...] = v_ref[...]


def gqa_write_block_rows(
    k_cache: jax.Array,   # [L, B, S, heads, width]: KVCache.k
    v_cache: jax.Array,   # the same shape: KVCache.v
    k: jax.Array,         # [B, T, heads, width]: the step's rows as stored
    v: jax.Array,
    layer: jax.Array,     # int32 scalar: which of the L
    start: jax.Array,     # int32 [B]: each slot's first position
    *,
    interpret: bool = False,
):
    """``(k_cache, v_cache)`` with ``k[b], v[b]`` at ``[layer, b,
    start[b]:start[b] + T]``: ``transformer._write_rows``' contract for a
    block of ``T`` rows a slot that starts at a multiple of ``T`` and is
    whole stored tiles (:func:`a_block_is_whole_tiles`). Every slot's
    rows are written, held or not, a start clamped into ``[0, S - T]``;
    one that is no multiple of ``T`` (a slot that is not live and holds
    a stale position) is floored to its block.

    In place, keys and values in one call: a grid point a slot writes the
    block over its tile of each aliased cache, 4 KB at 4 rows of 4 heads
    of 128 in bf16. The tile that was there is not fetched: nothing of it
    stays."""
    L, B, S, heads, width = k_cache.shape
    T = k.shape[1]
    if not a_block_is_whole_tiles(k_cache, T):
        raise ValueError(
            f"{T} rows a slot of a cache {k_cache.shape} of {k_cache.dtype} "
            f"are no whole stored tiles"
        )
    rows = T * heads
    new = pl.BlockSpec((None, rows, width), lambda b, *_: (b, 0, 0))
    kept = pl.BlockSpec(memory_space=pl.ANY)
    # of [L, B, S * heads, width] the rows of positions start[b] ..
    # start[b] + T - 1 of [layer, b]; a part tile's read-modify-write
    # (one row a slot: ROADMAP A5(b)) would index its tile the same way
    tile = pl.BlockSpec(
        (None, None, rows, width),
        lambda b, layer, start: (layer[0], b, start[b] // T, 0),
    )
    merged = (L, B, S * heads, width)
    k_cache, v_cache = pl.pallas_call(
        _block_kernel,
        out_shape=[
            jax.ShapeDtypeStruct(merged, k_cache.dtype),
            jax.ShapeDtypeStruct(merged, v_cache.dtype),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[new, new, kept, kept],
            out_specs=[tile, tile],
        ),
        # operands 4 and 5 (after the two prefetched scalars and the rows)
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        name="gqa_write_block_rows",
        interpret=interpret,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.clip(start, 0, S - T).astype(jnp.int32),
        k.reshape(B, rows, width), v.reshape(B, rows, width),
        k_cache.reshape(merged), v_cache.reshape(merged),
    )
    return (
        k_cache.reshape(L, B, S, heads, width),
        v_cache.reshape(L, B, S, heads, width),
    )
