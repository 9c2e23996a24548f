"""Pallas TPU flash-attention kernel for causal prefill.

Blocked online-softmax attention: the grid walks (batch, q-head, q-block,
k-block) with the k-block axis innermost; running max/sum/accumulator live
in VMEM scratch that persists across the k sweep, so the [T, S] score
matrix never exists in HBM and VMEM use is O(BLOCK_Q x BLOCK_K) regardless
of sequence length — a 32k prefill fits as easily as a 1k one (the XLA
path materializes a [B, H, T, S] fp32 score tensor: 128 GiB at 32k for an
8B model; reference long-context profile:
gpustack/assets/profiles_config/profiles_config.yaml:29-38).

Fully-masked k-blocks above the causal diagonal are skipped with
``pl.when`` — the sweep does ~half the work of a dense scan.

Engine wiring: ``models/transformer.forward(attn_impl="flash")`` uses this
for prefill steps; the engine enables it per prefill bucket
(engine/runner.py attn_impl_for). Verified bit-close against the XLA
reference in interpret mode (tests/ops/test_flash_attention.py) and
compiled for a described v5e at Qwen3-8B widths
(tests/ops/test_chip_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

BLOCK_Q = 128
BLOCK_K = 128
# scratch lane width: TPU vector registers are (8, 128); the running
# max/sum are stored broadcast across one 128-lane tile
_LANES = 128
_NEG = -1e30


def _flash_kernel(
    off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, seq_k: int, n_kb: int,
):
    """Grid point = one (batch, q-head, q-block, k-block) tile.

    ``off_ref`` (SMEM scalar) is the absolute position of q row 0 —
    zero for prefill-from-scratch; the prefix length for chunked-prefill
    continuation steps, whose queries sit at positions offset..offset+T-1
    against a cache of offset+T keys.
    """
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = off_ref[0]
    q_start = qb * BLOCK_Q
    k_start = kb * BLOCK_K

    # causal: skip k-blocks entirely above the (offset) diagonal
    @pl.when(k_start <= off + q_start + BLOCK_Q - 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # [BQ, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [BK, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(
            q, k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # [BQ, BK]
        q_idx = off + q_start + lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        )
        k_idx = k_start + lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        mask = (k_idx <= q_idx) & (k_idx < seq_k)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...][:, :1]                    # [BQ, 1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s <= _NEG / 2, 0.0, jnp.exp(s - m_new))
        corr = jnp.where(m_prev <= _NEG / 2, 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p, v,
            (((1,), (0,)), ((), ())),
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


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_attention_prefill(
    q: jax.Array,       # [B, T, Hq, d]
    k: jax.Array,       # [B, S, Hkv, d]
    v: jax.Array,       # [B, S, Hkv, d]
    scale: float,
    interpret: bool = False,
    q_offset=0,
) -> jax.Array:
    """Causal GQA prefill attention (q positions q_offset..q_offset+T-1
    against k positions 0..S-1, with keys at index >= S masked via
    ``seq_k``). ``q_offset`` (traced scalar) supports chunked-prefill
    continuation: every batch row shares the one offset. Returns
    [B, T, Hq*d]. T and S are padded to block multiples internally; any
    sequence length fits (VMEM use is O(block))."""
    B, T, Hq, d = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if Hq % Hkv != 0:
        raise ValueError(
            f"q heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    G = Hq // Hkv

    # head-major layout for blocking; pad seq dims to block multiples
    qt = jnp.transpose(q, (0, 2, 1, 3))          # [B, Hq, T, d]
    kt = jnp.transpose(k, (0, 2, 1, 3))          # [B, Hkv, S, d]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    T_pad = -(-T // BLOCK_Q) * BLOCK_Q
    S_pad = -(-S // BLOCK_K) * BLOCK_K
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))

    n_kb = S_pad // BLOCK_K
    grid = (B, Hq, T_pad // BLOCK_Q, n_kb)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, seq_k=S, n_kb=n_kb
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T_pad, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (1, 1, BLOCK_Q, d), lambda b, h, qb, kb: (b, h, qb, 0)
            ),
            pl.BlockSpec(
                (1, 1, BLOCK_K, d),
                lambda b, h, qb, kb, G=G: (b, h // G, kb, 0),
            ),
            pl.BlockSpec(
                (1, 1, BLOCK_K, d),
                lambda b, h, qb, kb, G=G: (b, h // G, kb, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, BLOCK_Q, d), lambda b, h, qb, kb: (b, h, qb, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((BLOCK_Q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((BLOCK_Q, d), jnp.float32),        # accumulator
        ],
        interpret=interpret,
    )(off, qt, kt, vt)
    out = jnp.transpose(out[:, :, :T, :], (0, 2, 1, 3))  # [B, T, Hq, d]
    return out.reshape(B, T, Hq * d)


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
