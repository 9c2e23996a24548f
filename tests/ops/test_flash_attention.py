"""Flash-attention prefill kernel vs reference attention (interpret mode:
hermetic on CPU; real-chip compilation is profiled before engine wiring)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.models.transformer import _attend
from gpustack_tpu.ops.flash_attention import flash_attention_prefill


@pytest.mark.parametrize("B,T,Hq,Hkv,d", [
    (1, 256, 4, 2, 64),
    (2, 128, 2, 2, 64),     # MHA
    (1, 200, 4, 1, 64),     # MQA + non-block-multiple T
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
