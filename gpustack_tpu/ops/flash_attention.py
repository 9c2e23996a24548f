"""Pallas TPU flash-attention kernel for causal prefill.

Blocked online-softmax attention. The grid walks (batch, kv-head,
q-block, k-block) with the k-block axis innermost. One grid point holds
the ``block_q`` query rows of **all** ``G`` query heads of one GQA group
(``G * block_q`` rows) against one ``block_k``-row block of that group's
K and V, so a K/V block is fetched once for the whole group and the grid
has tens of points a layer, not thousands (at 2,048 tokens, 32 / 8
heads: 64-128 against the 8,192 of the one-head, 128 x 128 grid this
replaced; a grid point costs about 0.3 us whatever it does).

That holds for a group of 4, 8 or 16. **A group of one** has a key/value
head a query head (a latent model's decompressed prefill, A.X-K1: 64
heads, keys 192 and values 128 wide; Olmo-Hybrid's 32 stored heads), and
with blocks of 512 rows, the most any group was offered until PR 59, its
point held one head's 512 rows where another's holds 2,048: at 8,192
tokens 16,384 points a call, 7,680 of them above the diagonal, matmuls of
512 rows, and a K/V block fetched for 512 rows: 20.70 ms a call, 33.7 %
of its roofline as counted (40.5 % of what is issued: a key of 192 is
two passes of the 128-deep matrix unit) where the groups' calls read
41.5-44.2 %. So a group of one is offered blocks of 1,024 query rows, one
matmul of them, and of up to 2,048 keys (:func:`candidate_tiles`), and
the call asks for the VMEM they take. Measured on a v5e at ``[1, 64,
8192, 192 / 128]`` (``hack/flash_bench.py --sweep``; PERF.md section 5,
PR 59; ``block_q`` x ``block_k``, points, ms): 512 x 512 16,384 20.70;
1,024 x 1,024 4,096 16.06; **1,024 x 2,048 2,048 15.78** (44.2 %
counted, 53.1 % issued); at 4,096 tokens 5.69 -> 4.45. Halving the
points alone is worth 0.14 us a point there (512 x 512 / 1,024 / 2,048:
20.70 / 19.53 / 18.94), the rest is the rows of a matmul and the K/V
traffic. At 1,024 tokens (Olmo-Hybrid: 32 points or 128) every tile reads
0.214-0.225 ms. **Not offered: a ``block_q`` of 2,048** in two matmuls
of 1,024. It reads 15.29 ms alone and a first token of A.X-K1's cell 5
ms sooner (607.5 against 612.7 ms), and its body holds every sweep twice,
once a q sub-block: the two prefill programs' trace and lowering took
3.1 s more of a start (``start.lower_s`` 26.60 against 23.46 s on one
machine; 23.52 with blocks of 1,024), which the groups of 4 and 8 have
paid since PR 32 (``sub_q`` 256 in 512).

Inside a point the fetched block is walked in 128-row sub-blocks, in
ascending order, and the q rows in sub-blocks of ``sub_q``: the running
max / sum / accumulator (VMEM scratch that persists across the k sweep)
are updated once per 128 keys, exactly as on the old grid, so every
float32 sum keeps its order and the result is the old kernel's element
for element (tests/ops/test_flash_attention.py holds the old kernel as
the oracle; hack/flash_bench.py counts the same on the chip). The
mathematics is as it was: q * scale, QK^T, the softmax and PV written in
float32 at the default precision (at which the chip's MXU takes a
float32 operand in one bf16 pass: explicit bf16 feeds differ nowhere,
PERF.md, PR 32).

Two things about the body decide its time on the chip, both measured
(PERF.md, PR 32: 2.53 ms a layer at 2,048 tokens before, 0.43 after):

- the running max and sum live broadcast over 128 lanes, in scratch and
  in every operation on them. As [rows, 1] columns they cost a vector
  register for every eight rows, as much as the scores themselves, plus
  a relayout each way: the kernel with column statistics took 1.12 ms,
  the same kernel with them broadcast 0.43, which is the two products'
  own time;
- ``unroll`` sub-blocks of an interior stretch sit in one basic block,
  so the scheduler can put one sub-block's products beside another's
  softmax (a loop with run-time bounds gives it one sub-block at a
  time), and one matmul holds about 1,024 rows (``G * sub_q``).

Causal work is bounded three ways:

- a (q sub-block, k sub-block) pair wholly above the diagonal is never
  computed: the sweeps over a block's sub-blocks are loops whose bounds
  come from the run-time offset;
- a k-block wholly above a q-block's diagonal is never fetched: the
  offset is a scalar-prefetch operand, the K/V index maps clamp the
  block index to the last block the q-block needs, and a point that
  names the block already resident issues no copy;
- the iotas, compares and selects of the mask run only on sub-blocks
  that straddle the diagonal or hold the ``seq_k`` tail; on an interior
  sub-block the mask is all true and ``where(mask, s, _NEG)`` the
  identity.

With a **band** (``window``, static; 0: none) query ``i`` sees keys
``i - window < j <= i``, and the same three hold at the band's lower
edge: a pair wholly below it is never computed, a k-block wholly below a
q-block's band is never fetched (the index maps clamp from below as they
do from above), and the mask runs on the sub-blocks that straddle either
edge. A stack that mixes window and full layers calls the kernel with
and without one (``models/transformer.py window_attention``); without
one the program is the kernel's as it was.

With a **block** (``block``, static; 0: none; generation by diffusion
over blocks of that many positions) query ``i`` sees the keys up to the
last position of its own block, ``j <= i - i % block + block - 1``:
causal over blocks, both ways inside one. ``block`` divides the 128 rows
of a sub-block and the offset is a whole number of blocks, so a
sub-block's last row is its block's last and sees what it saw: the three
bounds above stand as they are (a sub-block counted as straddling the
diagonal still does, by up to ``block - 1`` keys more), and the mask's
compare is the one line that differs. Without one the program is the
kernel's as it was.

The [T, S] score matrix never exists in HBM and VMEM use is
O(G * block_q x 128) regardless of sequence length, so a 32k prefill
fits as easily as a 1k one (the XLA path materializes a [B, H, T, S]
fp32 score tensor: 128 GiB at 32k for an 8B model; reference
long-context profile:
gpustack/assets/profiles_config/profiles_config.yaml:29-38).

The block sizes follow the shapes (:func:`choose_tiles`: the group's
size, the padded lengths, the head widths, the item size): no knob.

Engine wiring: ``models/transformer.forward(attn_impl="flash")`` uses this
for prefill steps; the engine enables it per prefill bucket
(engine/runner.py prefill_attention). Verified bit-close against the XLA
reference and bit-equal to the old kernel in interpret mode
(tests/ops/test_flash_attention.py), and compiled for a described v5e at
Qwen3-8B and Qwen3-30B-A3B widths (tests/ops/test_chip_compile.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

# keys per online-softmax update: fixed, it is the order of summation
SUB_K = 128
# lane width: TPU vector registers are (8, 128); the running max/sum are
# kept broadcast across one 128-lane tile
_LANES = 128
_NEG = -1e30
# what a grid point may hold of the 16 MiB of VMEM a kernel is given by
# default on a v5e (blocks double-buffered, scratch, float32 temporaries)
_VMEM_BUDGET = 10 * 1024 * 1024
# and what a point may hold where the call asks for its VMEM
# (``vmem_limit_bytes``, by the same arithmetic): a v5e has 128 MiB
_VMEM_ASKED = 24 * 1024 * 1024
# rows of one matmul inside a grid point (all G heads of ``sub_q`` rows)
# and sub-blocks of an interior stretch in one basic block: the chip's
# sweep (PERF.md, PR 32) has 512 rows 18 % and no unrolling 16 % slower.
# At a group of one (PR 59's sweep, 8,192 tokens) a ``sub_q`` of 1,024 in
# a block of 1,024 reads 16.06 ms against 18.40 for two of 512 (7.4
# bundles a row against 8.9, ``hack/kernel_bundles.py flash``) and, in a
# block of 2,048, two of 1,024 15.29 against 15.71 for one of 2,048; no
# unrolling 18.10 against 16.06
_MATMUL_ROWS = 1024
_UNROLL = 4


class Tiles(NamedTuple):
    block_q: int    # query rows (per head) of a grid point: chosen
    sub_q: int      # query rows (per head) of one matmul inside it: follows
    block_k: int    # K/V rows fetched for a grid point: chosen
    unroll: int     # 128-key sub-blocks of it in one basic block: follows


def tiles_of(block_q: int, block_k: int, G: int) -> Tiles:
    """The tile of a pair of block sizes: the one place a tile is made.
    The block sizes are the choice; the inner shape follows from them, the
    group's size and the two constants above. ``sub_q`` is the largest
    power of two from 128 up that holds at most ``_MATMUL_ROWS`` rows over
    the ``G`` heads and divides ``block_q`` (1,024 at a group of one; 2,048
    only where ``hack/flash_bench.py`` sweeps the constant): the kernel
    walks ``block_q // sub_q`` sub-blocks, so one that does not divide
    would leave a q-block's last rows unwritten (any ``G``: 3, 5, 6, 7
    too)."""
    if block_q % SUB_K or block_k % SUB_K:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must be multiples of "
            f"{SUB_K}"
        )
    most = max(SUB_K, _MATMUL_ROWS // G)
    sub_q = next(
        s for s in (2048, 1024, 512, 256, 128)
        if s <= most and block_q % s == 0
    )
    return Tiles(block_q, sub_q, block_k, min(_UNROLL, block_k // SUB_K))


def _vmem_bytes(tiles: Tiles, G: int, d: int, itemsize: int) -> int:
    """What a grid point holds, roughly: the chip's compiler has the last
    word (tests/ops/test_chip_compile.py at the cells' shapes). ``d`` is
    the wider of the two head widths where keys and values differ."""
    if d > _LANES:
        d = -(-d // _LANES) * _LANES    # a row of 192 lies in 256 lanes
    rows, sub_rows = G * tiles.block_q, G * tiles.sub_q
    blocks = 2 * (2 * rows * d + 2 * tiles.block_k * d) * itemsize
    scratch = rows * (2 * _LANES + d) * 4
    # q, two score-sized tiles, the product, and a sub-block of k and v
    temps = sub_rows * (2 * d + 2 * SUB_K) * 4 + 2 * SUB_K * d * 4
    return blocks + scratch + temps


def _one_head_a_point(G: int) -> bool:
    """Whether a group's 512 rows, the most any group had until PR 59, are
    less than one matmul's: a group of one. Its grid point holds one head
    where another's holds 4, 8 or 16, so it is offered longer blocks, and
    the VMEM they take is asked for (module docstring)."""
    return G * 512 < _MATMUL_ROWS


def candidate_tiles(T_pad: int, S_pad: int, G: int) -> list[Tiles]:
    """The tiles that divide the padded lengths, largest first; the last
    is always 128 x 128. Blocks of at most 512 rows, of queries and of
    keys, but for a group of one: one matmul's rows of queries (a second
    q sub-block would be written out in the body: the module docstring
    has what that costs a start) and up to 2,048 of keys."""
    q_sizes = k_sizes = (512, 256, 128)
    if _one_head_a_point(G):
        q_sizes = (_MATMUL_ROWS // G,) + q_sizes
        k_sizes = (2048, 1024) + k_sizes
    return [
        tiles_of(block_q, block_k, G)
        for block_q in q_sizes if T_pad % block_q == 0
        for block_k in k_sizes if S_pad % block_k == 0
    ]


def choose_tiles(
    T_pad: int, S_pad: int, G: int, d: int, itemsize: int
) -> Tiles:
    """The largest tiles of a short list that divide the padded lengths
    and fit VMEM; 128 x 128 where nothing larger does."""
    candidates = candidate_tiles(T_pad, S_pad, G)
    budget = _VMEM_ASKED if _one_head_a_point(G) else _VMEM_BUDGET
    return next(
        (
            tiles for tiles in candidates
            if _vmem_bytes(tiles, G, d, itemsize) <= budget
        ),
        candidates[-1],
    )


def grid_points(tiles: Tiles, T_pad: int, S_pad: int, groups: int) -> int:
    """The points of one call's grid over ``groups`` key/value heads
    (batch rows times heads): what the runner's log and
    ``hack/flash_bench.py`` say of a call beside its tile."""
    return groups * (T_pad // tiles.block_q) * (S_pad // tiles.block_k)


def _across(x, d: int):
    """``x`` [rows, 128], every lane of a row alike, as [rows, d]."""
    return jnp.tile(x, (1, -(-d // _LANES)))[:, :d]


def _flash_kernel(
    off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, seq_k: int, tiles: Tiles, window: int = 0,
    block: int = 0,
):
    """Grid point = one (batch, kv-head, q-block, k-block) tile: the
    ``block_q`` rows of the group's ``G`` query heads against ``block_k``
    keys.

    ``off_ref`` (scalar prefetch) is the absolute position of q row 0 —
    zero for prefill-from-scratch; the prefix length for chunked-prefill
    continuation steps, whose queries sit at positions offset..offset+T-1
    against a cache of offset+T keys.
    """
    block_q, sub_q, block_k, unroll = tiles
    # d: the width of a query or key head; dv: of a value head, and of
    # the result (latent attention decompresses to 192 / 128)
    G, d, dv = q_ref.shape[1], q_ref.shape[3], v_ref.shape[3]
    rows = G * sub_q
    n_sub_k = block_k // SUB_K
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[0]
    first_sub_k = kb * n_sub_k      # this block's first sub-block, of all

    def update(qs, q_first, q, j, masked):
        """One online-softmax update of q sub-block ``qs`` (row 0 at
        position ``q_first``) against keys 128 j .. 128 j + 127 of the
        fetched block. The running max and sum stay broadcast over 128
        lanes all through (module docstring)."""
        k_rows = pl.ds(pl.multiple_of(j * SUB_K, SUB_K), SUB_K)
        k = k_ref[0, 0, k_rows, :].astype(jnp.float32)    # [128, d]
        v = v_ref[0, 0, k_rows, :].astype(jnp.float32)
        s = lax.dot_general(
            q, k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # [rows, 128]
        if masked:
            q_idx = q_first + lax.rem(
                lax.broadcasted_iota(jnp.int32, s.shape, 0), sub_q
            )
            k_idx = (first_sub_k + j) * SUB_K + lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            if block:    # as far as the last position of the row's block
                q_idx = q_idx - lax.rem(q_idx, block) + (block - 1)
            seen = (k_idx <= q_idx) & (k_idx < seq_k)
            if window:
                seen = seen & (q_idx - k_idx < window)
            s = jnp.where(seen, s, _NEG)

        m_prev, l_prev = m_ref[qs], l_ref[qs]             # [rows, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(s <= _NEG / 2, 0.0, p)
        corr = jnp.where(m_prev <= _NEG / 2, 0.0, jnp.exp(m_prev - m_new))
        l_ref[qs] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[qs] = m_new
        acc_ref[qs] = acc_ref[qs] * _across(corr, dv) + lax.dot_general(
            p, v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def sweep(qs, q_first, lo, hi, masked, unroll):
        """Sub-blocks lo .. hi - 1 in ascending order, ``unroll`` of them
        to a basic block and then the rest one by one."""
        def some(first, n):
            q = q_ref[0, :, pl.ds(qs * sub_q, sub_q), :].reshape(rows, d)
            q = q.astype(jnp.float32) * scale             # [G*sub_q, d]
            for t in range(n):
                update(qs, q_first, q, first + t, masked)

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

    for qs in range(block_q // sub_q):
        q_first = off + qb * block_q + qs * sub_q    # position of row 0
        # sub-blocks j (keys 128 j .. 128 j + 127), counted from key 0:
        # those below n_seen hold a key some row sees; those below
        # n_clear are seen whole by every row and lie inside seq_k, and
        # need no mask
        n_seen = (q_first + sub_q - 1) // SUB_K + 1
        n_clear = jnp.minimum((q_first + 1) // SUB_K, seq_k // SUB_K)
        clear = jnp.clip(n_clear - first_sub_k, 0, n_sub_k)
        seen = jnp.clip(n_seen - first_sub_k, 0, n_sub_k)
        if not window:
            sweep(qs, q_first, 0, clear, masked=False, unroll=unroll)
            sweep(qs, q_first, clear, seen, masked=True, unroll=1)
            continue
        # the band's lower edge: sub-blocks below lo_seen hold no key
        # the first row sees (the other rows see less far back); from
        # lo_clear on the last row sees a sub-block whole
        lo_seen = jnp.maximum(q_first - window + 1, 0) // SUB_K
        lo_clear = -(-jnp.maximum(q_first + sub_q - window, 0) // SUB_K)
        first = jnp.clip(lo_seen - first_sub_k, 0, seen)
        whole = jnp.clip(lo_clear - first_sub_k, first, seen)
        clear = jnp.clip(clear, whole, seen)
        sweep(qs, q_first, first, whole, masked=True, unroll=1)
        sweep(qs, q_first, whole, clear, masked=False, unroll=unroll)
        sweep(qs, q_first, clear, seen, masked=True, unroll=1)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
        for qs in range(block_q // sub_q):
            o = acc_ref[qs] / _across(jnp.maximum(l_ref[qs], 1e-30), dv)
            o_ref[0, :, pl.ds(qs * sub_q, sub_q), :] = o.reshape(
                G, sub_q, dv
            ).astype(o_ref.dtype)


def flash_call(
    qt: jax.Array,      # [B, Hq, T_pad, d], head-major, rows padded to 128
    kt: jax.Array,      # [B, Hkv, S_pad, d]
    vt: jax.Array,      # [B, Hkv, S_pad, dv]
    off: jax.Array,     # int32[1]: the position of q row 0
    *, scale: float, seq_k: int, interpret: bool = False,
    _blocks: tuple[int, int] | None = None, window: int = 0,
    block: int = 0,
) -> jax.Array:
    """The ``pallas_call`` alone, on head-major operands whose rows are
    already padded to multiples of 128: what :func:`flash_attention_prefill`
    runs between its transposes, and what ``hack/flash_bench.py`` times.
    ``_blocks`` (``block_q``, ``block_k``) is for that timer and the
    tests, which go through every tile; nothing that serves passes it,
    and the shapes choose (:func:`choose_tiles`). Returns
    [B, Hq, T_pad, dv] in q's dtype."""
    B, Hq, T_pad, d = qt.shape
    Hkv, S_pad, dv = kt.shape[1], kt.shape[2], vt.shape[3]
    G = Hq // Hkv
    if block and (window or SUB_K % block):
        raise ValueError(
            f"a block of {block} must divide the {SUB_K} rows of a "
            "sub-block, and comes without a window"
        )
    if _blocks is None:
        tiles = choose_tiles(
            T_pad, S_pad, G, max(d, dv), qt.dtype.itemsize
        )
    else:
        tiles = tiles_of(*_blocks, G)
    block_q, sub_q, block_k, _ = tiles
    if T_pad % block_q or S_pad % block_k or block_q % sub_q:
        raise ValueError(f"{tiles} does not divide {T_pad} x {S_pad}")
    n_qs, n_kb = block_q // sub_q, S_pad // block_k
    # a tile past the budget asks for its VMEM by the same arithmetic,
    # with the room the budget leaves in the default 16 MiB
    need = _vmem_bytes(tiles, G, max(d, dv), qt.dtype.itemsize)
    vmem = {"vmem_limit_bytes": need * 8 // 5} if need > _VMEM_BUDGET else {}

    def q_block(b, h, qb, kb, off_ref):
        return (b, h, qb, 0)     # heads h*G .. h*G+G-1: one GQA group

    def kv_block(b, h, qb, kb, off_ref):
        # the last block that holds a key the q-block's last row sees: a
        # point past it names that block again and nothing is copied
        last = (off_ref[0] + (qb + 1) * block_q - 1) // block_k
        at = jnp.minimum(kb, jnp.minimum(last, n_kb - 1))
        if window:
            # nor a block below the band of the q-block's first row: a
            # point before it names the band's first block early
            low = jnp.maximum(
                off_ref[0] + qb * block_q - window + 1, 0
            ) // block_k
            at = jnp.maximum(at, jnp.minimum(low, n_kb - 1))
        return (b, h, at, 0)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, seq_k=seq_k, tiles=tiles,
            **({"window": window} if window else {}),
            **({"block": block} if block else {}),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T_pad, dv), qt.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, T_pad // block_q, n_kb),
            in_specs=[
                pl.BlockSpec((1, G, block_q, d), q_block),
                pl.BlockSpec((1, 1, block_k, d), kv_block),
                pl.BlockSpec((1, 1, block_k, dv), kv_block),
            ],
            out_specs=pl.BlockSpec((1, G, block_q, dv), q_block),
            scratch_shapes=[
                pltpu.VMEM((n_qs, G * sub_q, _LANES), jnp.float32),  # max
                pltpu.VMEM((n_qs, G * sub_q, _LANES), jnp.float32),  # sum
                pltpu.VMEM((n_qs, G * sub_q, dv), jnp.float32),      # acc
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
            **vmem,
        ),
        # a call with a band under a name of its own in the compiled
        # program and the profiler's trace; without one the call is
        # named after the jitted function, as it was
        **({"name": "flash_attention_window"} if window else {}),
        interpret=interpret,
    )(off, qt, kt, vt)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "window", "block")
)
def flash_attention_prefill(
    q: jax.Array,       # [B, T, Hq, d]
    k: jax.Array,       # [B, S, Hkv, d]
    v: jax.Array,       # [B, S, Hkv, dv]
    scale: float,
    interpret: bool = False,
    q_offset=0,
    window: int = 0,
    block: int = 0,
) -> jax.Array:
    """Causal GQA prefill attention (q positions q_offset..q_offset+T-1
    against k positions 0..S-1, with keys at index >= S masked via
    ``seq_k``). ``q_offset`` (traced scalar) supports chunked-prefill
    continuation: every batch row shares the one offset. Returns
    [B, T, Hq*dv]: the values may be narrower than the keys (latent
    attention decompresses to keys of 192 and values of 128), nothing is
    padded to make them alike. ``window`` (static; 0: none) keeps a
    query to the keys ``i - window < j <= i``; ``block`` (static; 0:
    none) lets it see to the end of its block of that many positions
    (``q_offset`` then a whole number of blocks). T and S are padded to
    multiples of 128 internally; any sequence length fits (VMEM use is O(block)); the
    shapes choose the tiles (:func:`choose_tiles`)."""
    B, T, Hq, d = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if Hq % Hkv != 0:
        raise ValueError(
            f"q heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )

    # head-major layout for blocking; pad seq dims to block multiples
    qt = jnp.transpose(q, (0, 2, 1, 3))          # [B, Hq, T, d]
    kt = jnp.transpose(k, (0, 2, 1, 3))          # [B, Hkv, S, d]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    T_pad = -(-T // SUB_K) * SUB_K
    S_pad = -(-S // SUB_K) * SUB_K
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))

    out = flash_call(
        qt, kt, vt, jnp.asarray(q_offset, jnp.int32).reshape(1),
        scale=scale, seq_k=S, interpret=interpret,
        **({"window": window} if window else {}),
        **({"block": block} if block else {}),
    )
    out = jnp.transpose(out[:, :, :T, :], (0, 2, 1, 3))  # [B, T, Hq, dv]
    return out.reshape(B, T, Hq * v.shape[3])


def sharded_flash_attention_prefill(
    mesh: Mesh,
    q: jax.Array,       # [B, T, Hq, d]
    k: jax.Array,       # [B, S, Hkv, d]
    v: jax.Array,
    scale: float,
    interpret: bool = False,
    q_offset=0,
) -> jax.Array:
    """:func:`flash_attention_prefill` on a mesh. The compiler cannot
    partition a Mosaic kernel by itself, so under tensor parallelism the
    kernel runs per shard of heads: q, k and v arrive head-sharded over
    ``tp`` (contiguous shards keep every GQA group on one chip), rows
    are replicated (the engine's prefill paths are B=1)."""
    tp = int(mesh.shape["tp"])
    if tp == 1:
        return flash_attention_prefill(
            q, k, v, scale, interpret=interpret, q_offset=q_offset
        )
    if k.shape[2] % tp:
        raise ValueError(
            f"flash prefill needs kv heads ({k.shape[2]}) divisible by "
            f"tp={tp}"
        )
    heads = P(None, None, "tp", None)
    return shard_map(
        lambda q_, k_, v_, off: flash_attention_prefill(
            q_, k_, v_, scale, interpret=interpret, q_offset=off
        ),
        mesh=mesh,
        in_specs=(heads, heads, heads, P()),
        out_specs=P(None, None, "tp"),
        check_vma=False,
    )(q, k, v, jnp.asarray(q_offset, jnp.int32))
