"""The main path's kernels, compiled for a described TPU v5e at real widths.

No chip is attached: the TPU compiler that ships with the installation
compiles for a ``v5e:2x2`` topology that is only described, and raises
what the chip's compiler would raise (a block not aligned to the
tiling, too much fast memory, a program that does not fit 16 GB).
Interpret-mode tests cannot see any of that. Nothing runs, so these say
nothing about results or times.

The topology is described inside module-scoped fixtures only — never at
import time, in ``conftest.py`` or in a child process: one process at a
time may load the TPU's library, and the worker that runs this file is
the one that loads it. Keep these compiles in this one file.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gpustack_tpu.models.config import get_config

QWEN3_8B = get_config("qwen3-8b")


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flash_compiled(one_chip, T: int, S: int):
    from gpustack_tpu.ops.flash_attention import flash_attention_prefill

    cfg = QWEN3_8B
    q = jax.ShapeDtypeStruct(
        (1, T, cfg.num_heads, cfg.head_dim), jnp.bfloat16, sharding=one_chip
    )
    kv = jax.ShapeDtypeStruct(
        (1, S, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16,
        sharding=one_chip,
    )
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def attend(q, k, v, off):
        return flash_attention_prefill(
            q, k, v, scale=cfg.head_dim ** -0.5, q_offset=off
        )

    compiled = jax.jit(attend).lower(q, kv, kv, off).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "T,S",
    [
        (1024, 1024),   # the 1024 bucket, from scratch
        (2048, 2048),   # the 2048 bucket, from scratch
        (512, 2048),    # chunked continuation: q_offset > 0, S > T
    ],
)
def test_flash_prefill_compiles_for_v5e(one_chip, T, S):
    compiled = _flash_compiled(one_chip, T, S)
    mem = compiled.memory_analysis()
    # the [T, S] score matrix never exists in HBM: temporaries stay near
    # the transposed/padded operands, far below Hq*T*S*4 bytes
    assert mem.temp_size_in_bytes < QWEN3_8B.num_heads * T * S * 4 / 4


def test_int8_decode_layer_compiles_for_v5e(one_chip):
    """One int8 decode step over a single Qwen3-8B-wide layer, at the
    smoke deployment's batch (8 slots x 2048)."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward

    cfg = dataclasses.replace(QWEN3_8B, num_layers=1)
    slots, max_len = 8, 2048

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=one_chip
            ),
            tree,
        )

    params = on_chip(jax.eval_shape(
        lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    ))
    cache = on_chip(jax.eval_shape(
        lambda: KVCache.create(cfg, slots, max_len)
    ))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)

    def step(params, tokens, positions, cache):
        return forward(params, cfg, tokens, positions, cache)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache
    ).compile()
    mem = compiled.memory_analysis()
    # one layer's int8 weights + embed/lm_head + this cache: well under
    # a gigabyte and a half of arguments, and no bf16 copy of a weight
    # among the temporaries
    assert mem.argument_size_in_bytes < 1.6e9
    assert mem.temp_size_in_bytes < 0.6e9
