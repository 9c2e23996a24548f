"""Grouped matmul for routed experts: each row meets one expert's weights.

A prefill of ``R`` rows through a top-``k`` router asks for ``R * k``
(row, expert) products. :func:`group_rows` lays those pairs out sorted by
expert, each expert's rows padded up to whole tiles of ``block_m`` rows, so
a tile belongs to one expert; :func:`grouped_matmul` is the Pallas kernel
that walks the tiles, fetching an expert's weight block when the tile's
expert changes and not again while it stays (consecutive grid points that
name one block copy nothing). Tiles past the last real one are skipped:
the grid is as long as the worst routing needs, the work is what the
routing asked for. Every pair is computed: no capacity, nothing dropped.

The mathematics is ``_mm``'s (``models/transformer.py``): operands in the
activations' dtype, an int8 weight block upcast **in VMEM** (the
dequantized weight never exists in HBM), float32 accumulation over the
whole contraction, the per-output-channel scale multiplied onto the
float32 product, one rounding to the activations' dtype.

A decode step has one row a slot, ``B`` rows in all: they are one tile, so
no row layout is needed. :func:`touched_experts` walks the experts the
step's live rows chose, each read once and multiplied with all ``B`` rows,
and adds each result under the rows' combine weights, zero where a row did
not choose the expert: ``touched / held`` of the dense products' bytes,
never more.

``hack/moe_bench.py`` times both on a chip beside the dense formulation and
the grouped matmuls JAX ships; ``tests/ops/test_chip_compile.py`` compiles
them for a described v5e at the benchmark's widths.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows a tile: the MXU's own height. ``hack/moe_bench.py --sweep`` sets it
# while it traces; nothing else does.
BLOCK_M = 128
# What one grid point may hold in VMEM: two buffers of every block, the
# upcast weight block and the float32 product. v5e has 128 MiB a core;
# the compiler's default allowance (16 MiB) is too small for a whole
# [2048, 768] expert beside its upcast.
_VMEM_BUDGET = 40 * 2**20
_VMEM_LIMIT = 64 * 2**20


class RowGroups(NamedTuple):
    """Where each (row, expert) pair sits among the padded, sorted rows."""

    src: jax.Array         # int32 [M]: the pair in padded row m, P if none
    dest: jax.Array        # int32 [P]: the padded row of pair p
    tile_group: jax.Array  # int32 [M // BLOCK_M]: the expert of tile t
    n_active: jax.Array    # int32 [1]: tiles that hold a pair


def padded_rows(n_pairs: int, num_groups: int) -> int:
    """Rows enough for any routing of ``n_pairs`` pairs: each group that
    holds a pair wastes less than one tile."""
    tiles = (n_pairs + min(num_groups, n_pairs) * (BLOCK_M - 1)) // BLOCK_M
    return max(tiles, 1) * BLOCK_M


def group_rows(
    group_of_pair: jax.Array,   # int32 [P]
    num_groups: int,
) -> RowGroups:
    """Sort the pairs by group (stable: pairs of one group keep their
    order) with each group padded to whole tiles: the padding rows are
    sorted in with the pairs, ``M - P`` fillers dealt to the groups by
    what each lacks to its next whole tile, the rest behind the last
    group. Two sorts of ``M`` and two compare-and-sums against a table of
    ``num_groups``: no scatter, so the same input gives the same layout
    bit for bit, and no gather of one scalar a row (7 ns an element on the
    chip, 1.2 ms a layer at 2,048 rows: PERF.md section 6, PR 34)."""
    P = group_of_pair.shape[0]
    block_m = BLOCK_M
    M = padded_rows(P, num_groups)
    groups = jnp.arange(num_groups, dtype=jnp.int32)
    sizes = jnp.sum(
        group_of_pair[:, None] == groups[None, :], axis=0, dtype=jnp.int32
    )
    tiles = (sizes + block_m - 1) // block_m
    tile_end = jnp.cumsum(tiles)
    lacks_end = jnp.cumsum(tiles * block_m - sizes)

    def group_at(i, ends):   # the group whose run [ends[g-1], ends[g]) holds i
        return jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)

    filler_group = group_at(jnp.arange(M - P, dtype=jnp.int32), lacks_end)
    _, src = lax.sort_key_val(
        jnp.concatenate([2 * group_of_pair, 2 * filler_group + 1]),
        jnp.concatenate([
            jnp.arange(P, dtype=jnp.int32), jnp.full((M - P,), P, jnp.int32)
        ]),
    )
    _, row_of = lax.sort_key_val(src, jnp.arange(M, dtype=jnp.int32))
    tile_group = jnp.minimum(
        group_at(jnp.arange(M // block_m, dtype=jnp.int32), tile_end),
        num_groups - 1,
    )
    return RowGroups(src, row_of[:P], tile_group, tile_end[-1:])


def choose_blocks(
    K: int, N: int, lhs_bytes: int, rhs_bytes: int
) -> tuple[int, int]:
    """``(block_k, block_n)``: the whole contraction and the whole width
    where a grid point's VMEM holds them (then the rows are read once and
    an expert's weights once a tile), else the largest halvings that are
    still multiples of 128."""
    block_m = BLOCK_M

    def vmem(bk, bn):
        return (
            2 * block_m * bk * lhs_bytes        # rows, two buffers
            + 2 * bk * bn * rhs_bytes           # weights, two buffers
            + bk * bn * lhs_bytes               # the upcast block
            + block_m * bn * (4 + 4 + 2 * lhs_bytes)  # product, acc, out
        )

    bk, bn = K, N
    while vmem(bk, bn) > _VMEM_BUDGET:
        if bn >= bk and bn % 256 == 0:
            bn //= 2
        elif bk % 256 == 0:
            bk //= 2
        elif bn % 256 == 0:
            bn //= 2
        else:
            break
    return bk, bn


def _kernel(tile_group_ref, n_active_ref, layer_ref, lhs_ref, rhs_ref, *rest,
            n_k: int, scaled: bool):
    del tile_group_ref, layer_ref
    rest = list(rest)
    scale_ref = rest.pop(0) if scaled else None
    out_ref = rest.pop(0)
    acc_ref = rest.pop(0) if n_k > 1 else None
    k = pl.program_id(2)

    @pl.when(pl.program_id(1) < n_active_ref[0])
    def _tile():
        prod = jnp.dot(
            lhs_ref[...], rhs_ref[...].astype(lhs_ref.dtype),
            preferred_element_type=jnp.float32,
        )
        if n_k > 1:
            @pl.when(k == 0)
            def _first():
                acc_ref[...] = prod

            @pl.when(k > 0)
            def _more():
                acc_ref[...] += prod

        @pl.when(k == n_k - 1)
        def _store():
            total = prod if n_k == 1 else acc_ref[...]
            if scaled:
                total = total * scale_ref[...].astype(jnp.float32)
            out_ref[...] = total.astype(out_ref.dtype)


def grouped_matmul(
    lhs: jax.Array,          # [M, K]: rows as group_rows lays them out
    rhs: jax.Array,          # [E, K, N] or [L, E, K, N], int8 or a float type
    tile_group: jax.Array,   # int32 [M // BLOCK_M]
    n_active: jax.Array,     # int32 [1]
    scale: Optional[jax.Array] = None,   # [(L,) E, N] per-output-channel
    layer: Optional[jax.Array] = None,   # int32 scalar: which of the L
    *,
    interpret: bool = False,
    _blocks: tuple[int, int] | None = None,
) -> jax.Array:
    """``out[m] = lhs[m] @ rhs[tile_group[m // BLOCK_M]] (* scale)`` for
    the rows of the first ``n_active`` tiles, in lhs's dtype; rows of
    later tiles hold whatever was there.

    With ``layer``, ``rhs`` and ``scale`` are the weights of all ``L``
    layers as they are stored and the kernel fetches its blocks from
    layer ``layer``: a custom call cannot read a slice in place as a
    fusion can, so inside a scan over the layers a sliced ``rhs`` is
    copied whole before every call (200 MB a product at the benchmark's
    widths, 1.5 ms a layer: PERF.md section 6, PR 34). ``_blocks`` is for
    the timer and the tests; nothing that serves passes it."""
    if layer is None:
        rhs = rhs[None]
        scale = None if scale is None else scale[None]
        layer = jnp.int32(0)
    M, K = lhs.shape
    L, E, _, N = rhs.shape
    block_m = BLOCK_M
    if M % block_m or tile_group.shape != (M // block_m,):
        raise ValueError(f"{M} rows are not {tile_group.shape} x {block_m}")
    bk, bn = _blocks or choose_blocks(
        K, N, lhs.dtype.itemsize, rhs.dtype.itemsize
    )
    if K % bk or N % bn:
        raise ValueError(f"blocks {bk} x {bn} do not divide {K} x {N}")
    n_k = K // bk

    def tile(m, n_active_ref):
        # a point past the last real tile names that tile again: nothing
        # is copied for it, in or out
        return jnp.minimum(m, n_active_ref[0] - 1)

    def lhs_block(n, m, k, tile_group_ref, n_active_ref, layer_ref):
        return (tile(m, n_active_ref), k)

    def rhs_block(n, m, k, tile_group_ref, n_active_ref, layer_ref):
        return (layer_ref[0], tile_group_ref[tile(m, n_active_ref)], k, n)

    def scale_block(n, m, k, tile_group_ref, n_active_ref, layer_ref):
        return (layer_ref[0], tile_group_ref[tile(m, n_active_ref)], 0, n)

    def out_block(n, m, k, tile_group_ref, n_active_ref, layer_ref):
        return (tile(m, n_active_ref), n)

    in_specs = [
        pl.BlockSpec((block_m, bk), lhs_block),
        pl.BlockSpec((None, None, bk, bn), rhs_block),
    ]
    operands = [lhs, rhs]
    if scale is not None:
        in_specs.append(pl.BlockSpec((None, None, 1, bn), scale_block))
        operands.append(scale.reshape(L, E, 1, N))
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, scaled=scale is not None),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // bn, M // block_m, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, bn), out_block),
            scratch_shapes=(
                [pltpu.VMEM((block_m, bn), jnp.float32)] if n_k > 1 else []
            ),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(tile_group, n_active, jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands)


def choose_block_f(
    B: int, D: int, F: int, lhs_bytes: int, rhs_bytes: int,
    matrices: int = 3,
) -> int:
    """Columns of the intermediate width a grid point of
    :func:`touched_experts` takes: all ``F`` where a point's VMEM holds
    an expert's three matrices whole (two buffers each, and their
    upcasts), else the largest divisor of ``F`` that is a multiple of
    128 and fits (the smallest, where none does). ``matrices``: 3 an
    expert, or 2 for the plain form without a gate."""

    def vmem(bf):
        return (
            matrices * D * bf * (2 * rhs_bytes + lhs_bytes)  # gate, up, down
            + 2 * B * D * lhs_bytes                    # rows, two buffers
            + 3 * B * D * 4                            # product, out twice
            + 3 * B * bf * 4                           # gate, up, their act
        )

    chunks = [bf for bf in range(F, 0, -128) if F % bf == 0]
    if F % 128:
        chunks = [F]
    return next((bf for bf in chunks if vmem(bf) <= _VMEM_BUDGET), chunks[-1])


def _touched_kernel(ids_ref, n_ref, layer_ref, x_ref, combine_ref, *rest,
                    scaled: bool, gated: bool):
    del layer_ref
    # the matrices (gate, up, down; up, down for the plain form), their
    # scales in the same order, the result
    n_w = 3 if gated else 2
    w_refs, scale_refs, out_ref = rest[:n_w], rest[n_w:-1], rest[-1]
    t = pl.program_id(0)

    @pl.when((t == 0) & (pl.program_id(1) == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t < n_ref[0])
    def _expert():
        x = x_ref[...]

        def mm(a, w_ref, at):
            prod = jnp.dot(
                a, w_ref[...].astype(a.dtype),
                preferred_element_type=jnp.float32,
            )
            if scaled:
                prod = prod * scale_refs[at][...].astype(jnp.float32)
            return prod

        # gate and up round to the activations' dtype as _mm's do; the
        # activation is taken in float32 and rounded once
        if gated:
            g = mm(x, w_refs[0], 0).astype(x.dtype).astype(jnp.float32)
            u = mm(x, w_refs[1], 1).astype(x.dtype).astype(jnp.float32)
            h = jax.nn.silu(g) * u
        else:
            u = mm(x, w_refs[0], 0).astype(x.dtype).astype(jnp.float32)
            h = jnp.square(jnp.maximum(u, 0.0))
        y = mm(h.astype(x.dtype), w_refs[-1], n_w - 1)
        # the rows' weights for this expert: its column of [B, E], taken
        # by a compare and a sum over the lanes (one term is not zero)
        lanes = lax.broadcasted_iota(jnp.int32, combine_ref.shape, 1)
        w = jnp.sum(
            jnp.where(lanes == ids_ref[t], combine_ref[...], 0.0),
            axis=1, keepdims=True,
        )
        out_ref[...] += w * y


def touched_experts(
    x: jax.Array,          # [B, D]: a decode step's rows
    combine: jax.Array,    # float32 [B, E]: row b's weight for expert e
    ids: jax.Array,        # int32 [G]: the touched experts, ascending
    n_touched: jax.Array,  # int32 [1]: how many of ``ids`` count
    gate,                  # [(L,) E, D, F] int8 or a float type; None:
                           # the plain form, relu(x @ up)^2 @ down
    up: jax.Array,         # [(L,) E, D, F]
    down: jax.Array,       # [(L,) E, F, D]
    scales: Optional[tuple] = None,   # ([(L,) E, F], [.. F], [.. D])
    layer: Optional[jax.Array] = None,   # int32 scalar: which of the L
    *,
    interpret: bool = False,
    _block_f: int | None = None,
) -> jax.Array:
    """``out[b] = sum over t < n_touched of combine[b, ids[t]] *
    expert_ids[t](x[b])`` in float32 ``[B, D]``, an expert being
    ``(silu(x @ gate) * (x @ up)) @ down`` (without a gate,
    ``relu(x @ up) ** 2 @ down``) with :func:`grouped_matmul`'s
    mathematics: every expert in ``ids`` meets all ``B`` rows, read once,
    and a row that did not choose it weighs its result by zero.

    The grid walks ``len(ids)`` experts by ``F / block_f`` chunks of the
    intermediate width (``out += act(x Wg[:, f]) * (x Wu[:, f]) Wd[f]``:
    no point holds a whole expert where it is too large); a point at or
    past ``n_touched`` names the last block again and copies nothing.
    Experts are added in the order of ``ids`` and chunks in theirs, and
    adding ``0 * y`` changes nothing: a row's bits depend on its own
    experts only. ``layer`` as in :func:`grouped_matmul`; ``_block_f``
    is for the tests."""
    gated = gate is not None
    mats = [gate, up, down] if gated else [up, down]
    if scales is not None:
        scales = [s for s in scales if s is not None]
    if layer is None:
        mats = [m[None] for m in mats]
        scales = None if scales is None else [s[None] for s in scales]
        layer = jnp.int32(0)
    B, D = x.shape
    L, E, _, F = mats[-2].shape     # up's
    bf = _block_f or choose_block_f(
        B, D, F, x.dtype.itemsize, up.dtype.itemsize, len(mats)
    )
    if F % bf:
        raise ValueError(f"chunks of {bf} do not divide {F}")
    n_f = F // bf

    def point(t, f, ids_ref, n_ref):
        # a point past the last touched expert names that expert's last
        # chunk again: nothing is copied for it
        last = jnp.maximum(n_ref[0] - 1, 0)
        return ids_ref[jnp.minimum(t, last)], jnp.where(t < n_ref[0], f, n_f - 1)

    def whole(t, f, ids_ref, n_ref, layer_ref):
        return (0, 0)

    def columns(t, f, ids_ref, n_ref, layer_ref):    # of gate, up, scales
        e, f = point(t, f, ids_ref, n_ref)
        return (layer_ref[0], e, 0, f)

    def rows(t, f, ids_ref, n_ref, layer_ref):       # of down
        e, f = point(t, f, ids_ref, n_ref)
        return (layer_ref[0], e, f, 0)

    def down_scale(t, f, ids_ref, n_ref, layer_ref):
        e, _ = point(t, f, ids_ref, n_ref)
        return (layer_ref[0], e, 0, 0)

    n_cols = len(mats) - 1     # gate and up, or up alone
    in_specs = [
        pl.BlockSpec((B, D), whole),
        pl.BlockSpec((B, E), whole),
        *[pl.BlockSpec((None, None, D, bf), columns)] * n_cols,
        pl.BlockSpec((None, None, bf, D), rows),
    ]
    operands = [x, combine.astype(jnp.float32), *mats]
    if scales is not None:
        in_specs += [
            *[pl.BlockSpec((None, None, 1, bf), columns)] * n_cols,
            pl.BlockSpec((None, None, 1, D), down_scale),
        ]
        operands += [s.reshape(L, E, 1, -1) for s in scales]
    return pl.pallas_call(
        functools.partial(
            _touched_kernel, scaled=scales is not None, gated=gated
        ),
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ids.shape[0], n_f),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((B, D), whole),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        name="moe_touched_experts",
        interpret=interpret,
    )(ids, n_touched, jnp.reshape(layer, (1,)).astype(jnp.int32), *operands)
