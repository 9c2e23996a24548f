"""Flash-attention prefill kernel vs reference attention (interpret mode:
hermetic on CPU; real-chip compilation is profiled before engine wiring),
and vs the kernel it replaced in PR 32, kept here as the oracle: one query
head and 128 x 128 keys a grid point. The new tiling must give its output
element for element (``hack/flash_bench.py`` counts the same on the chip)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gpustack_tpu.models.transformer import _attend
from gpustack_tpu.ops.flash_attention import (
    SUB_K,
    Tiles,
    candidate_tiles,
    choose_tiles,
    flash_attention_prefill,
    flash_call,
    tiles_of,
)

_OLD_BLOCK = 128
_NEG = -1e30


def _old_flash_kernel(
    off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, seq_k: int, n_kb: int,
):
    """The kernel as it was until PR 32: grid point = one (batch, q-head,
    q-block, k-block) tile of 128 x 128."""
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[0]
    q_start = qb * _OLD_BLOCK
    k_start = kb * _OLD_BLOCK

    @pl.when(k_start <= off + q_start + _OLD_BLOCK - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        q_idx = off + q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_idx <= q_idx) & (k_idx < seq_k)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...][:, :1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s <= _NEG / 2, 0.0, jnp.exp(s - m_new))
        corr = jnp.where(m_prev <= _NEG / 2, 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = l_ref[...][:, :1]
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)


def old_flash_call(qt, kt, vt, off, *, scale, seq_k, interpret=False):
    """The old ``pallas_call`` on head-major operands padded to 128 rows:
    the same contract as ``flash_attention.flash_call``."""
    B, Hq, T_pad, d = qt.shape
    G = Hq // kt.shape[1]
    n_kb = kt.shape[2] // _OLD_BLOCK
    blk = (1, 1, _OLD_BLOCK, d)
    return pl.pallas_call(
        functools.partial(
            _old_flash_kernel, scale=scale, seq_k=seq_k, n_kb=n_kb
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T_pad, d), qt.dtype),
        grid=(B, Hq, T_pad // _OLD_BLOCK, n_kb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(blk, lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec(blk, lambda b, h, qb, kb: (b, h // G, kb, 0)),
            pl.BlockSpec(blk, lambda b, h, qb, kb: (b, h // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec(blk, lambda b, h, qb, kb: (b, h, qb, 0)),
        scratch_shapes=[
            pltpu.VMEM((_OLD_BLOCK, 128), jnp.float32),
            pltpu.VMEM((_OLD_BLOCK, 128), jnp.float32),
            pltpu.VMEM((_OLD_BLOCK, d), jnp.float32),
        ],
        interpret=interpret,
    )(off, qt, kt, vt)


def _head_major(x):
    """[B, T, H, d] as the kernels take it: [B, H, T_pad, d], rows padded
    to a multiple of 128."""
    pad = -x.shape[1] % SUB_K
    return jnp.pad(
        jnp.transpose(x, (0, 2, 1, 3)), ((0, 0), (0, 0), (0, pad), (0, 0))
    )


def _attend_with(call, q, k, v, off):
    """``call`` (either kernel's ``pallas_call``, interpreted) between the
    transposes and pads that ``flash_attention_prefill`` puts round it."""
    B, T, Hq, d = q.shape
    out = call(
        _head_major(q), _head_major(k), _head_major(v),
        jnp.full((1,), off, jnp.int32),
        scale=d ** -0.5, seq_k=k.shape[1], interpret=True,
    )
    out = jnp.transpose(out[:, :, :T, :], (0, 2, 1, 3)).reshape(B, T, Hq * d)
    assert out.dtype == q.dtype
    return np.asarray(out.astype(jnp.float32))


# (T, S, offset, Hq, Hkv, d, dtype): from scratch, continuations whose
# offset is a multiple of no block, ragged T and S, G of 1, 4 and 8 and
# the groups that are no power of two (3, 5, 6 and Qwen2.5-7B's 7), head
# widths of 64, 128 and 192 (MLA's qk width), both input precisions; a
# group of one long enough for the blocks of 1,024 queries and of 1,024
# and 2,048 keys it alone is offered (PR 59), from scratch and at an odd
# offset with T and S ragged
_EQUAL_CASES = [
    (512, 512, 0, 4, 1, 64, jnp.float32),       # from scratch, G 4
    (1024, 1024, 0, 2, 2, 64, jnp.bfloat16),    # G 1: MHA
    (512, 512, 0, 8, 1, 128, jnp.bfloat16),     # G 8, published width
    (256, 1024, 700, 4, 1, 64, jnp.float32),    # continuation mid-cache
    (300, 700, 393, 8, 2, 64, jnp.bfloat16),    # ragged T and S, odd offset
    (200, 1000, 488, 4, 1, 192, jnp.float32),   # ragged, MLA width
    (512, 2048, 1536, 4, 1, 128, jnp.bfloat16),  # a cell-like chunk
    (100, 100, 0, 1, 1, 64, jnp.float32),       # one block, S < 128
    (512, 512, 0, 3, 1, 64, jnp.float32),       # G 3: 24 / 8 heads
    (512, 512, 0, 5, 1, 64, jnp.bfloat16),      # G 5: 40 / 8 heads
    (300, 700, 393, 6, 1, 64, jnp.float32),     # G 6, ragged, odd offset
    (512, 512, 0, 7, 1, 128, jnp.bfloat16),     # G 7: Qwen2.5-7B's 28 / 4
    (256, 1024, 700, 14, 2, 64, jnp.bfloat16),  # G 7, two groups, mid-cache
    (2048, 2048, 0, 2, 2, 64, jnp.float32),     # G 1, every larger block
    (1000, 4000, 2987, 1, 1, 64, jnp.float32),  # G 1, mid-cache, ragged
]


def _equal_params():
    for case in _EQUAL_CASES:
        T, S, off, Hq, Hkv, d, dtype = case
        T_pad, S_pad = -(-T // SUB_K) * SUB_K, -(-S // SUB_K) * SUB_K
        for tiles in candidate_tiles(T_pad, S_pad, Hq // Hkv):
            name = "-".join(map(str, (
                T, S, off, f"{Hq}x{Hkv}", d, jnp.dtype(dtype).name, *tiles
            )))
            yield pytest.param(*case, tiles, id=name)


@functools.lru_cache(maxsize=None)
def _equal_inputs(T, S, Hq, Hkv, d, dtype):
    ks = jax.random.split(jax.random.key(T * S + Hq), 3)
    return tuple(
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key, shape in zip(
            ks, ((1, T, Hq, d), (1, S, Hkv, d), (1, S, Hkv, d))
        )
    )


@functools.lru_cache(maxsize=None)
def _old_output(T, S, off, Hq, Hkv, d, dtype):
    return _attend_with(
        old_flash_call, *_equal_inputs(T, S, Hq, Hkv, d, dtype), off
    )


@pytest.mark.parametrize("T,S,off,Hq,Hkv,d,dtype,tiles", _equal_params())
def test_every_tiling_equals_the_old_kernel_element_for_element(
    T, S, off, Hq, Hkv, d, dtype, tiles
):
    """A row meets the same 128-key sub-blocks in the same order with the
    same float32 updates whatever the tile, so nothing may differ: not in
    the last bit."""
    out = _attend_with(
        functools.partial(
            flash_call, _blocks=(tiles.block_q, tiles.block_k)
        ),
        *_equal_inputs(T, S, Hq, Hkv, d, dtype), off,
    )
    old = _old_output(T, S, off, Hq, Hkv, d, dtype)
    assert np.isfinite(out).all()
    assert int((out != old).sum()) == 0


@pytest.mark.parametrize("T_pad,S_pad,G,d,itemsize,want", [
    (2048, 2048, 4, 128, 2, Tiles(512, 256, 512, 4)),  # Qwen3-8B, 2048
    (1024, 1024, 8, 128, 2, Tiles(256, 128, 512, 4)),  # Qwen3-30B-A3B, 1024
    (384, 640, 4, 128, 2, Tiles(128, 128, 128, 1)),    # nothing larger divides
    (512, 512, 1, 128, 4, Tiles(512, 512, 512, 4)),    # MHA: rows from sub_q
    (2048, 2048, 7, 128, 2, Tiles(256, 128, 512, 4)),  # Qwen2.5-7B, 2048
    (1024, 1024, 3, 128, 2, Tiles(512, 256, 512, 4)),  # 24 / 8 heads
    (1024, 1024, 5, 128, 2, Tiles(512, 128, 512, 4)),  # 40 / 8 heads
    # a group of one (PR 59's sweep on the chip): A.X-K1's decompressed
    # latent at the long-document cell's two buckets, Olmo-Hybrid's heads
    (8192, 8192, 1, 192, 2, Tiles(1024, 1024, 2048, 4)),
    (4096, 4096, 1, 192, 2, Tiles(1024, 1024, 2048, 4)),
    (1024, 1024, 1, 128, 2, Tiles(1024, 1024, 1024, 4)),
])
def test_tiles_follow_the_shapes(T_pad, S_pad, G, d, itemsize, want):
    got = choose_tiles(T_pad, S_pad, G, d, itemsize)
    assert got == want
    assert T_pad % got.block_q == 0 and S_pad % got.block_k == 0
    assert got.block_q % got.sub_q == 0


@pytest.mark.parametrize("G", range(1, 17))
def test_the_rows_of_a_matmul_divide_the_block_for_any_group(G):
    """The kernel walks ``block_q // sub_q`` sub-blocks of a q-block: a
    ``sub_q`` that did not divide (146 rows at G 7, 341 at G 3) left the
    block's last rows unwritten. Whatever the shapes choose divides."""
    for block_q in (128, 256, 384, 512, 1024, 2048):
        for block_k in (128, 512, 1024, 2048):
            tiles = tiles_of(block_q, block_k, G)
            assert (tiles.block_q, tiles.block_k) == (block_q, block_k)
            assert block_q % tiles.sub_q == 0 and tiles.sub_q % SUB_K == 0
            assert 1 <= tiles.unroll <= block_k // SUB_K
    for T_pad, S_pad, d, itemsize in (
        (2048, 2048, 128, 2), (1024, 1024, 64, 2), (1024, 1024, 256, 2),
        (512, 2048, 192, 4), (384, 640, 128, 2), (8192, 8192, 192, 2),
        (4096, 4096, 128, 2),
    ):
        tiles = choose_tiles(T_pad, S_pad, G, d, itemsize)
        assert T_pad % tiles.block_q == 0 and S_pad % tiles.block_k == 0
        assert tiles.block_q % tiles.sub_q == 0
    with pytest.raises(ValueError):
        tiles_of(192, 512, G)


@pytest.mark.parametrize("B,T,Hq,Hkv,d", [
    (1, 256, 4, 2, 64),
    (2, 128, 2, 2, 64),     # MHA
    (1, 200, 4, 1, 64),     # MQA + non-block-multiple T
    (1, 256, 32, 2, 128),   # Nemotron-3-Nano: 2 kv heads, groups of 16
])
def test_flash_matches_reference(B, T, Hq, Hkv, d):
    ks = jax.random.split(jax.random.key(0), 3)
    G = Hq // Hkv
    q = jax.random.normal(ks[0], (B, T, Hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    mask = positions[:, :, None] >= positions[:, None, :]
    ref = _attend(
        q.reshape(B, T, Hkv, G, d), k, v, mask, scale
    )

    out = flash_attention_prefill(q, k, v, scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_flash_bf16_inputs():
    B, T, Hq, Hkv, d = 1, 128, 2, 2, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, d), jnp.float32).astype(
        jnp.bfloat16
    )
    k = jax.random.normal(ks[1], (B, T, Hkv, d), jnp.float32).astype(
        jnp.bfloat16
    )
    v = jax.random.normal(ks[2], (B, T, Hkv, d), jnp.float32).astype(
        jnp.bfloat16
    )
    out = flash_attention_prefill(q, k, v, d ** -0.5, interpret=True)
    assert out.dtype == jnp.bfloat16
    assert jnp.isfinite(out.astype(jnp.float32)).all()


@pytest.mark.parametrize("B,T,S,off,Hq,Hkv,d", [
    (1, 128, 512, 256, 4, 2, 64),    # mid-cache chunk
    (1, 100, 512, 384, 2, 1, 64),    # non-block T, chunk ends mid-cache
    (1, 128, 128, 0, 2, 2, 64),      # offset 0 == original contract
])
def test_flash_q_offset_matches_reference(B, T, S, off, Hq, Hkv, d):
    """Chunked-prefill continuation: q rows at positions off..off+T-1
    against a cache of S keys (keys above the causal line are garbage
    the mask must hide)."""
    ks = jax.random.split(jax.random.key(2), 3)
    G = Hq // Hkv
    q = jax.random.normal(ks[0], (B, T, Hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    q_pos = off + jnp.arange(T, dtype=jnp.int32)
    mask = jnp.broadcast_to(
        jnp.arange(S)[None, None, :] <= q_pos[None, :, None], (B, T, S)
    )
    ref = _attend(q.reshape(B, T, Hkv, G, d), k, v, mask, scale)

    out = flash_attention_prefill(
        q, k, v, scale, interpret=True, q_offset=off
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_flash_q_offset_is_traced_not_specialized():
    """Different offsets reuse one compiled kernel (offset rides SMEM,
    not the jit cache key)."""
    B, T, S, Hq, Hkv, d = 1, 128, 256, 2, 2, 64
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, d), jnp.float32)
    o1 = flash_attention_prefill(
        q, k, v, 0.125, interpret=True, q_offset=jnp.int32(0)
    )
    o2 = flash_attention_prefill(
        q, k, v, 0.125, interpret=True, q_offset=jnp.int32(128)
    )
    # offset widens the visible key range → outputs must differ
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_flash_under_tensor_parallelism_runs_per_shard_of_heads():
    """The chip's compiler cannot partition a Mosaic kernel by itself
    ("wrap the call in a shard_map" — seen compiling the tp4 prefill for
    a described v5e, PR 23): on a tp mesh the kernel runs per shard of
    heads, with the same answer as on one device, for a continuation
    (q_offset > 0, S > T) as well."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from gpustack_tpu.ops.flash_attention import (
        sharded_flash_attention_prefill,
    )
    from gpustack_tpu.parallel.mesh import MeshPlan, make_mesh

    B, T, S, off, Hq, Hkv, d = 1, 128, 256, 128, 8, 4, 64
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, d), jnp.float32)
    scale = d ** -0.5
    ref = flash_attention_prefill(
        q, k, v, scale, interpret=True, q_offset=off
    )

    mesh = make_mesh(MeshPlan(tp=4), jax.devices()[:4])
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    out = jax.jit(
        lambda q, k, v, off: sharded_flash_attention_prefill(
            mesh, q, k, v, scale, interpret=True, q_offset=off
        )
    )(*(jax.device_put(x, heads) for x in (q, k, v)), jnp.int32(off))
    assert out.sharding.spec == P(None, None, "tp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    # kv heads that do not divide over tp are refused by name
    with pytest.raises(ValueError, match="divisible by tp=4"):
        sharded_flash_attention_prefill(
            mesh, q, k[:, :, :2], v[:, :, :2], scale, interpret=True
        )
