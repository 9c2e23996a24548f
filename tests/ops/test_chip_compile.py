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
import re

import jax
import jax.numpy as jnp
import numpy as np
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


def _shapes_on(one_chip, make):
    """The shapes of what ``make()`` would build, placed on the chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(make),
    )


def _flash_compiled(one_chip, cfg, T: int, S: int):
    from gpustack_tpu.ops.flash_attention import flash_attention_prefill

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
    "preset,heads,kv_heads,block_q,sub_q",
    [
        ("qwen3-8b", 32, 8, 512, 256),        # four query heads a group
        ("qwen3-30b-a3b", 32, 4, 256, 128),   # eight
        ("qwen2.5-7b", 28, 4, 256, 128),      # seven: no power of two
    ],
)
@pytest.mark.parametrize(
    "T,S",
    [
        (1024, 1024),   # the 1024 bucket, from scratch
        (2048, 2048),   # the 2048 bucket, from scratch
        (512, 2048),    # chunked continuation: q_offset > 0, S > T
    ],
)
def test_flash_prefill_compiles_for_v5e(
    one_chip, T, S, preset, heads, kv_heads, block_q, sub_q
):
    """At the shapes the benchmark's cells run the kernel takes its large
    tiles (the G * sub_q = 1,024 rows a matmul and the four sub-blocks a
    basic block that the chip's sweep chose, PERF.md PR 32) and the
    chip's compiler takes them: a refusal for VMEM, or a chooser that
    fell back to 128 x 128 without saying, fails here."""
    from gpustack_tpu.ops.flash_attention import choose_tiles

    cfg = get_config(preset)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        heads, kv_heads, 128
    )
    tiles = choose_tiles(T, S, heads // kv_heads, cfg.head_dim, 2)
    assert tiles == (block_q, sub_q, 512, 4)
    compiled = _flash_compiled(one_chip, cfg, T, S)
    # the benchmark's reader finds the call by this name and shape
    # (perfbench/layer_metrics/kernel.flash_prefill_roofline.py)
    assert re.search(
        rf"%flash_attention_prefill[\w.\-]* = bf16\[1,{heads},{T},128\]"
        r".* custom-call\(",
        compiled.as_text(),
    )
    mem = compiled.memory_analysis()
    # the [T, S] score matrix never exists in HBM: temporaries stay near
    # the transposed/padded operands, far below Hq*T*S*4 bytes
    assert mem.temp_size_in_bytes < heads * T * S * 4 / 4


MAX_LEN = 2048


def _int8_step_compiled(one_chip, cfg, slots: int, T: int):
    """``forward`` over int8 weights and a donated ``slots`` x ``MAX_LEN``
    cache, ``T`` tokens a slot, compiled from shapes alone. Over the
    cache it attends as the chooser says for one TPU chip (``forward``
    itself sees this process's CPU and would say ``"xla"``), and its
    experts' products are enumerated as that chooser says."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
        moe_dispatch,
    )

    attends = decode_attention_impl(cfg, T, MAX_LEN, "tpu", None)
    experts = (
        moe_dispatch(slots * T, cfg, "tpu", None, decode=T == 1)
        if cfg.is_moe else None
    )

    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, MAX_LEN))
    tokens = jax.ShapeDtypeStruct((slots, T), jnp.int32, sharding=one_chip)

    def step(params, tokens, positions, cache):
        return forward(
            params, cfg, tokens, positions, cache, decode_attn_impl=attends,
            moe_dispatch_impl=experts,
        )

    return jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache
    ).compile()


@pytest.mark.parametrize(
    "preset,layers,slots,T",
    [
        ("qwen3-8b", 4, 12, 1),        # the 8B deployment's decode step
        ("qwen3-30b-a3b", 2, 32, 1),   # the MoE deployment's
        ("qwen3-8b", 4, 12, 4),        # the verify shape, B x T
    ],
)
def test_a_step_moves_only_its_rows_of_the_donated_cache(
    one_chip, preset, layers, slots, T
):
    """The cache is the layer scan's carry: with it donated, a step over
    int8 weights at published widths updates it in place. As ``xs`` in
    and ``ys`` out it cost a second cache of temporaries, two copies of
    the whole cache and each layer's slab written back whole (PERF.md,
    PR 29); this keeps them from coming back with a JAX upgrade.

    A decode step (``T`` = 1) attends through the kernel of
    ``ops/decode_attention.py``, which reads the cache where it lies: no
    value of a layer's slab's shape exists in the program at all, sliced,
    copied or transposed (the two slab slices and the attention over
    them were 7.75 of the 8B deployment's 24.1 ms step: PERF.md, PR 41).
    The verify shape keeps the XLA form and its slab."""
    cfg = dataclasses.replace(get_config(preset), num_layers=layers)
    compiled = _int8_step_compiled(one_chip, cfg, slots, T)
    slab = slots * MAX_LEN * cfg.num_kv_heads * cfg.head_dim
    cache_bytes = 2 * layers * slab * 2          # k and v, bf16
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.05 * cache_bytes
    assert mem.alias_size_in_bytes >= cache_bytes

    # every instruction's element count, by name; then no copy of the
    # whole cache, and no dynamic-update-slice that writes a layer's
    # whole slab or more
    text = compiled.as_text()
    size = {
        name: int(np.prod([int(d) for d in dims.split(",") if d]))
        for name, dims in re.findall(
            r"%([\w.-]+) = \w+\[([\d,]*)\]", text
        )
    }
    whole = f"bf16[{layers},{slots},{MAX_LEN},"
    assert not re.findall(rf"= {re.escape(whole)}[^ ]* copy\(", text)
    updates = re.findall(
        r"dynamic-update-slice\(%[\w.-]+, %([\w.-]+),", text
    )
    assert updates and max(size[u] for u in updates) < slab, updates

    # the slab, as stored or with positions and heads merged
    kv = cfg.num_kv_heads
    slabs = re.findall(
        rf"= bf16\[(?:1,)?{slots},(?:{MAX_LEN},{kv}|{MAX_LEN * kv}),128\]"
        r"[^ ]* ([\w-]+)\(", text,
    )
    kernel = re.search(
        rf"%gqa_decode_attention[\w.\-]* = bf16\[{slots},{cfg.num_heads},128\]"
        r".* custom-call\(", text,
    )
    if T == 1:
        assert kernel and slabs == [], slabs
        assert not re.findall(
            rf"= {re.escape(whole)}[^ ]* (?:transpose|dynamic-slice)\(", text
        )
    else:
        assert not kernel and slabs


@pytest.mark.parametrize(
    "preset,layers,slots",
    [
        ("qwen3-8b", 4, 12),          # q and k norms under the rope
        ("qwen3-30b-a3b", 2, 32),     # the MoE deployment's
        ("qwen2.5-7b", 4, 12),        # no qk-norm, biases: the reshape alone
    ],
)
def test_a_decode_step_reads_its_attention_weights_in_place(
    one_chip, preset, layers, slots
):
    """``wq``, ``wk`` and ``wv`` are read out of the stacked int8 arrays
    inside their products' fusions, as the MLP's and ``wo``'s are. With
    the reshape to heads (and the q/k norm's reduce) folded into the
    product, the compiler wanted each weight with the model dimension
    minor: a stand-alone ``dynamic-slice`` fusion took the layer's matrix
    out of the stack (``s8[1,4096,4096]``, ``s8[1,4096,1024]`` twice at
    the 8B's widths) and a ``copy`` wrote it again transposed, every
    layer of every step: 3.7 of the 8B deployment's 20.4 ms step
    (PERF.md, PR 47). ``transformer.finish_products`` keeps the fold
    from forming in a decode step; this keeps it from coming back."""
    cfg = dataclasses.replace(get_config(preset), num_layers=layers)
    text = _int8_step_compiled(one_chip, cfg, slots, 1).as_text()
    d, q, kv = cfg.hidden_size, cfg.q_dim, cfg.kv_dim
    taken_out = re.findall(
        rf"%([\w.-]+) = s8\[1,{d},(?:{q}|{kv})\][^ ]* (?:copy|fusion)\(",
        text,
    )
    assert taken_out == []
    # and no int8 value of any shape is copied: the weights are the
    # program's only int8 arrays
    assert not re.findall(r"%([\w.-]+) = s8\[[\d,]*\][^ ]* copy\(", text)
    # the three products are there, under their einsums' names
    assert "btd,dq->btq/dot_general" in text
    assert "btd,dk->btk/dot_general" in text


@pytest.mark.parametrize(
    "preset,program,rows",
    [
        ("qwen3-8b", "prefill", 2048),     # the 8B deployment's bucket
        # the MoE deployment's other bucket: at 2,048, its hidden size,
        # the head's own matrix is ``[2048, 151936]``
        ("qwen3-30b-a3b", "prefill", 1024),
        ("qwen3-8b", "verify", 4),         # needs every row's argmax
    ],
)
def test_a_prefill_computes_the_head_for_the_row_it_keeps(
    topo, one_chip, preset, program, rows
):
    """``ModelRunner``'s own prefill program (int8, a bucket of ``rows``,
    the flash kernel) holds no value of the bucket's rows by the
    vocabulary's columns, in any dtype, not even inside a fusion: the
    final norm and the head run on the one row ``true_len - 1`` names
    (``transformer.head``, ``logits_at``). With the ``take`` behind the
    product the compiler computed every row (``fusion
    bf16[2048,151936]``, 13-15 ms of the 8B deployment's 193 ms prefill
    and 1.2 GB of float32: PERF.md, PR 49); this keeps the fold from
    coming back. The verify program (12 slots x ``rows``) keeps all its
    rows."""
    import types
    from functools import partial

    from gpustack_tpu.engine.runner import DecodeState, ModelRunner
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.parallel.mesh import MeshPlan, make_mesh

    cfg = dataclasses.replace(get_config(preset), num_layers=2)
    # what the two programs ask of their runner, on a mesh of the one
    # described chip (so ``forward``'s choosers see a TPU)
    runner = types.SimpleNamespace(
        cfg=cfg, mesh=make_mesh(MeshPlan(), [topo.devices[0]]),
        sp_mode=False, max_seq_len=MAX_LEN,
    )
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if program == "prefill":
        lowered = jax.jit(
            partial(ModelRunner._prefill_impl, runner, attn_impl="flash")
        ).lower(params, ints(1, rows), ints())
    else:
        state = _shapes_on(
            one_chip, lambda: DecodeState.create(cfg, 12, MAX_LEN)
        )
        lowered = jax.jit(
            partial(ModelRunner._verify_impl, runner), donate_argnums=(1,)
        ).lower(params, state, ints(12, rows))
    text = lowered.compile().as_text()
    V = cfg.vocab_size
    # the head's product under its einsum's name, by its leading dims
    heads = re.findall(
        rf"= \w+\[([\d,]*){V}\]\S* (?:fusion|convolution|dot)\("
        r"[^\n]*btd,dv->btv/dot_general", text,
    )
    assert heads, "the head's product is not under its einsum's name"
    if program == "prefill":
        assert "flash_attention_prefill" in text
        assert not re.findall(rf"\w+\[(?:\d+,)*{rows},{V}\]", text)
        assert all(dims in ("", "1,", "1,1,") for dims in heads), heads
    else:
        assert f"12,{rows}," in heads, heads


@pytest.mark.parametrize(
    "preset,change,rows,max_len,platform,devices,want",
    [
        ("qwen3-8b", {}, 1, 2048, "tpu", 1, "kernel"),
        ("qwen3-30b-a3b", {}, 1, 2048, "tpu", 1, "kernel"),
        ("qwen2.5-7b", {}, 1, 32768, "tpu", 1, "kernel"),   # seven a group
        ("llama3-70b", {}, 1, 8192, "tpu", None, "kernel"),  # no mesh at all
        ("qwen3-8b", {}, 4, 2048, "tpu", 1, "xla"),         # verify, ingest
        ("qwen3-8b", {}, 512, 2048, "tpu", 1, "xla"),       # a continuation
        ("qwen3-8b", {}, 1, 2048, "tpu", 4, "xla"),         # tp, sp replicas
        ("qwen3-8b", {}, 1, 2048, "cpu", 1, "xla"),
        ("qwen3-8b", {}, 1, 2048, "gpu", 1, "xla"),
        ("qwen3-8b", {}, 1, 1000, "tpu", 1, "xla"),         # no block divides
        ("qwen3-8b", {"sliding_window": 1024}, 1, 2048, "tpu", 1, "xla"),
        ("qwen3-8b", {"attn_logit_softcap": 50.0}, 1, 2048, "tpu", 1, "xla"),
        ("qwen3-8b", {"attn_sinks": True}, 1, 2048, "tpu", 1, "xla"),
        ("gemma2-9b", {}, 1, 2048, "tpu", 1, "xla"),
        ("gpt-oss-20b", {}, 1, 2048, "tpu", 1, "xla"),
        ("qwen3-8b", {"head_dim": 64}, 1, 2048, "tpu", 1, "xla"),
        ("tiny", {}, 1, 2048, "tpu", 1, "xla"),             # heads of 16
        ("deepseek-v2-lite", {}, 1, 2048, "tpu", 1, "kernel"),   # the latent
        ("deepseek-v2-lite", {}, 1, 1000, "tpu", 1, "xla"),
        ("deepseek-v2-lite", {}, 4, 2048, "tpu", 1, "xla"),
        ("qwen3-30b-a3b", {}, 4, 2048, "tpu", 1, "xla"),    # its verify
        ("qwen3-30b-a3b", {}, 1, 2048, "tpu", 4, "xla"),    # its ep replica
        ("qwen3-30b-a3b", {}, 1, 2048, "cpu", 1, "xla"),
    ],
)
def test_the_chooser_s_table(
    preset, change, rows, max_len, platform, devices, want
):
    """``decode_attention_impl``: the kernel for a decode step on one TPU
    chip whose cache divides into blocks and whose scores it can compute;
    the XLA form for everything else. Shapes and the mesh, never a name.
    A model with experts has a second chooser at the same step
    (``moe_dispatch``): the touched experts for a decode step on one TPU
    chip unless the experts carry biases (GPT-OSS), dense otherwise,
    whatever the attention's answer."""
    from gpustack_tpu.models.transformer import (
        decode_attention_impl,
        moe_dispatch,
    )

    class Mesh:
        size = devices

    mesh = None if devices is None else Mesh()
    cfg = dataclasses.replace(get_config(preset), **change)
    assert decode_attention_impl(cfg, rows, max_len, platform, mesh) == want
    if cfg.is_moe:
        on_one_chip = platform == "tpu" and devices in (None, 1)
        touched = on_one_chip and rows == 1 and not cfg.moe_bias
        assert moe_dispatch(
            8 * rows, cfg, platform, mesh, decode=rows == 1
        ) == ("touched" if touched else "dense")


@pytest.mark.parametrize("T", [1, 4])
def test_a_position_sharded_cache_takes_its_rows_without_a_gather(topo, T):
    """``sp`` = 4 over the described 2x2: the cache is sharded over its
    positions and a step's rows land at offsets the partitioner does not
    know. One row a slot (``T`` = 1) it places shard by shard. A block of
    ``T`` = 4 it can only place in a gathered copy of the whole carry,
    every layer, whatever the carry is constrained to, so ``forward``
    writes it by position there (``_write_rows``): this fails with an
    all-gather of ``bf16[4,8,2048,8,128]`` when that goes."""
    from jax.sharding import NamedSharding, PartitionSpec

    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quant_pspecs, quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward
    from gpustack_tpu.parallel.mesh import MeshPlan, make_mesh
    from gpustack_tpu.parallel.sharding import SpecLayout, param_pspecs

    cfg = dataclasses.replace(QWEN3_8B, num_layers=4)
    slots = 8
    mesh = make_mesh(MeshPlan(sp=4), topo.devices)

    def placed(tree, specs):
        return jax.tree_util.tree_map(
            lambda x, spec: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)
            ),
            tree, specs,
        )

    shapes = jax.eval_shape(
        lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    params = placed(
        shapes, quant_pspecs(param_pspecs(shapes, train=False), shapes)
    )
    cache = jax.eval_shape(lambda: KVCache.create(cfg, slots, MAX_LEN))
    on_positions = SpecLayout(long_context=True).cache()
    cache = placed(cache, KVCache(on_positions, on_positions))
    tokens = jax.ShapeDtypeStruct(
        (slots, T), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec()),
    )

    def step(params, tokens, positions, cache):
        return forward(
            params, cfg, tokens, positions, cache,
            attn_impl="ring", mesh=mesh,
        )

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache
    ).compile()
    slab = slots * MAX_LEN * cfg.num_kv_heads * cfg.head_dim
    gathered = [
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(
            r"= \w+\[([\d,]+)\][^ ]* all-gather(?:-start)?\(",
            compiled.as_text(),
        )
    ]
    assert all(n < slab for n in gathered), gathered
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        2 * cfg.num_layers * slab * 2 // 4      # a shard of k and of v
    )


def test_int8_decode_layer_compiles_for_v5e(one_chip):
    """One int8 decode step over a single Qwen3-8B-wide layer, at the
    smoke deployment's batch (8 slots x 2048)."""
    cfg = dataclasses.replace(QWEN3_8B, num_layers=1)
    mem = _int8_step_compiled(one_chip, cfg, 8, 1).memory_analysis()
    # one layer's int8 weights + embed/lm_head + this cache: well under
    # a gigabyte and a half of arguments, and no bf16 copy of a weight
    # among the temporaries
    assert mem.argument_size_in_bytes < 1.6e9
    assert mem.temp_size_in_bytes < 0.6e9


def test_the_decode_program_sorts_no_row_of_the_vocabulary(one_chip):
    """A decode step's sampler at Qwen3-8B's widths (12 slots x 151,936
    columns): ``top_candidates`` ranks 64 chunks of 128, so the
    program's sorts are of 1,187 and of 8,192 columns. A ``lax.top_k``
    over the row compiles here to a sort of the whole row, because the
    top log-probs are sliced from the candidates (3.0 ms a step on the
    chip: PERF.md, PR 36); this keeps one from coming back unseen."""
    from gpustack_tpu.engine.sampling import (
        CAND, LANES, SamplingState, sample,
    )
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward

    cfg = dataclasses.replace(QWEN3_8B, num_layers=1)
    slots = 12

    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, MAX_LEN))
    state = _shapes_on(one_chip, lambda: SamplingState.create(slots))
    key = _shapes_on(one_chip, lambda: jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)

    def step(params, tokens, positions, cache, state, key):
        logits, cache = forward(
            params, cfg, tokens[:, None], positions[:, None], cache
        )
        return sample(logits[:, 0], state, key, positions), cache

    text = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache, state, key
    ).compile().as_text()
    # what the program ranks: the first operand of every sort, and of
    # every TopK call (what a top_k that nothing else reads becomes)
    dims = dict(re.findall(r"%([\w.-]+) = \(?\w+\[([\d,]*)\]", text))
    ranked = re.findall(r" sort\(%([\w.-]+)", text) + re.findall(
        r' custom-call\(%([\w.-]+)[^\n]*custom_call_target="TopK"', text
    )
    widths = sorted({int(dims[name].split(",")[-1]) for name in ranked})
    assert CAND * LANES in widths, widths        # the selection is there
    assert max(widths) < cfg.vocab_size // 8, widths


def test_the_moe_prefill_takes_grouped_experts_and_fits_beside_decode(
    one_chip,
):
    """``jit_prefill_2048`` of the benchmark's MoE (128 experts, 8 a
    token, 12 layers, int8, published widths) as the runner traces it on
    one TPU chip: the chooser says grouped, the kernel of
    ``ops/grouped_matmul.py`` is in the program (three calls a layer, in
    the scan's body once each), and what the program reserves fits the
    chip beside the 32-slot decode program, which stays dense."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        forward,
        moe_dispatch,
    )

    cfg = dataclasses.replace(get_config("qwen3-30b-a3b"), num_layers=12)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (128, 8)
    assert moe_dispatch(2048, cfg, "tpu", None) == "grouped"
    assert moe_dispatch(32, cfg, "tpu", None) == "dense"

    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one_chip)

    def prefill(params, tokens):   # ModelRunner._prefill_impl on a TPU
        positions = jnp.arange(2048, dtype=jnp.int32)[None, :]
        logits, cache = forward(
            params, cfg, tokens, positions, KVCache.create(cfg, 1, 2048),
            attn_impl="flash", moe_dispatch_impl="grouped",
        )
        return logits[0, -1], cache.k[:, 0], cache.v[:, 0]

    compiled = jax.jit(prefill).lower(params, tokens).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom-call\(.*moe_grouped_matmul", text)) == 3
    assert not re.findall(r"bf16\[2048,128,768\]", text)   # dense's g, u
    # the kernel reads a layer's experts in place: no copy of a layer's
    # slab out of the stacked weights (three a layer, 200 MB each, when
    # the scan sliced them: PERF.md section 6, PR 34)
    assert not re.findall(r"= s8\[128,(2048,768|768,2048)\]", text)
    pre = compiled.memory_analysis()
    dec_compiled = _int8_step_compiled(one_chip, cfg, 32, 1)
    assert "moe_grouped_matmul" not in dec_compiled.as_text()
    dec = dec_compiled.memory_analysis()
    resident = dec.argument_size_in_bytes + dec.temp_size_in_bytes
    reserved = pre.temp_size_in_bytes + pre.output_size_in_bytes
    assert resident + reserved < 15.75e9 * 0.9, (resident, reserved)
    # the padded rows (32,640 x 2,048 bf16, four of them at most) stay
    # under the vocabulary head's 0.62 GB, as the dense path's did
    assert pre.temp_size_in_bytes < 0.7e9, pre.temp_size_in_bytes


# ---- A.X-K1 (perfbench/configs/ax-k1-int8-ep16-l12): latent attention ----

AXK1_DIR = "perfbench/configs/ax-k1-int8-ep16-l12"


def _axk1(layers: int):
    import os

    from gpustack_tpu.models.config import load_hf_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    return dataclasses.replace(
        load_hf_config(os.path.join(root, AXK1_DIR)), num_layers=layers
    )


def _axk1_shapes(one_chip, cfg):
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params

    return _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )


@pytest.mark.parametrize("T", [8192, 4096])
def test_flash_prefill_takes_keys_of_192_and_values_of_128(one_chip, T):
    """The MLA prefill's call at the long-document cell's two buckets: 64
    heads, keys 192 and values 128 wide, nothing padded; the result is as
    wide as a value, which is where the benchmark's reader takes the
    value width from
    (perfbench/layer_metrics/kernel.mla_prefill_roofline.py). A group of
    one takes blocks of 1,024 query rows, one matmul of them, against
    2,048 keys (PR 59: 2,048 grid points at 8,192 where blocks of 512
    gave 16,384) and asks for the VMEM they need; the chip's compiler,
    which has the last word on VMEM, takes the tile."""
    from gpustack_tpu.ops.flash_attention import (
        Tiles,
        choose_tiles,
        flash_attention_prefill,
        grid_points,
    )

    tiles = choose_tiles(T, T, 1, 192, 2)
    assert tiles == Tiles(1024, 1024, 2048, 4)
    assert grid_points(tiles, T, T, 64) == 64 * (T // 1024) * (T // 2048)
    q = jax.ShapeDtypeStruct((1, T, 64, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, T, 64, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention_prefill(q, k, v, scale=0.1)
    ).lower(q, q, v).compile()
    assert re.search(
        rf"%flash_attention_prefill[\w.\-]* = bf16\[1,64,{T},128\]"
        r".* custom-call\(",
        compiled.as_text(),
    )
    # the head-major copies of q, k, v and o, no [T, T] scores
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


@pytest.mark.parametrize("layers", [2, 12])
def test_the_latent_decode_step_moves_no_cache(one_chip, layers):
    """A.X-K1's decode program at the cell's 16 slots of 8,192, at the
    cell's 12 layers (a scan over eleven alike) and at 2 (both written
    out): the absorbed attention is the kernel the benchmark's reader
    finds by name, and no operation copies, transposes or slices out the
    latent cache, the 7/8 of it that is 512 wide. Either array is
    written by an aliased call of its own, in place, one stored tile a
    slot. The latent's rows: no loop over the slots of one
    ``dynamic-update-slice`` each (until PR 57 the scatter's, two such
    ``while``s in the text, the first layer's and the scan's, 0.77 of a
    7.44 ms step). The rope keys (64 wide, stored with the positions on
    the lanes): nothing copies, relays out or passes over their array or
    a layer of it (until PR 54 a pass over the layer, and the array
    copied whole once in and once out a step: 202.6 MB of
    temporaries)."""
    from gpustack_tpu.models.transformer import KVCache, forward

    cfg = _axk1(layers)
    slots, S = 16, 8192
    shapes = _axk1_shapes(one_chip, cfg)
    cache = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: KVCache.create(cfg, slots, S)),
    )
    ids = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)

    def step(params, cache, tokens, positions):
        return forward(
            params, cfg, tokens, positions, cache,
            decode_attn_impl="kernel", moe_dispatch_impl="dense",
        )

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        shapes, cache, ids, ids
    ).compile()
    text = compiled.as_text()
    assert re.search(
        r"%mla_decode_attention[\w.\-]* = bf16\[16,64,512\].* custom-call\(",
        text,
    )
    moved = [
        line.strip()[:120] for line in text.splitlines()
        if re.search(rf"= bf16\[({layers},)?16,8192,(1,)?512\]", line)
        and re.search(r" (copy|transpose|dynamic-slice)\(", line)
    ]
    assert moved == []
    # its rows: written by the call into the operand it aliases, once
    # where the first (dense) layer is written out and once in the scan
    # over the rest (one call a layer) ...
    calls = re.findall(
        rf"%mla_write_latent_rows[\w.\-]* = bf16\[{layers},16,8192,512\]"
        r".* custom-call\(.*output_to_operand_aliasing={{}: \(3, {}\)}",
        text,
    )
    assert len(calls) == 2, calls
    # ... and by nothing else: no update of a window of the array, which
    # is what the scatter's loop over the slots is made of, and no loop
    # but the scan over the layers
    assert not re.findall(
        rf"= bf16\[{layers},16,8192,512\][^ ]* "
        r"(dynamic-update-slice|scatter)\(",
        text,
    )
    assert len(re.findall(r" while\(", text)) == (1 if layers > 2 else 0)
    # the rope keys: written by the call, as the TPU stores them ...
    assert re.search(
        rf"%mla_write_rope_keys[\w.\-]* = bf16\[{layers},16,64,8192\]"
        r".* custom-call\(",
        text,
    )
    # ... and nothing else has a result the shape of their array, of its
    # stored view or of one layer of it
    moved = [
        line.strip()[:120] for line in text.splitlines()
        if re.search(
            rf"= bf16\[({layers}|1),16,(8192,(1,)?64|64,8192)\]", line
        )
        and re.search(r" (copy|transpose|select|fusion)\(", line)
    ]
    assert moved == []
    # wq_b is read out of the stack inside its product
    # (transformer.finish_products: it met the fold a GQA layer's wq met)
    assert not re.findall(r"= s8\[1,1536,12288\][^ ]* (?:copy|fusion)\(", text)
    # the cache is the 1,152 bytes a position a layer, and is aliased
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= layers * slots * S * 1152
    assert mem.temp_size_in_bytes < 0.05e9


def test_the_latent_prefill_s_temporaries_leave_the_resident_model_room(one_chip):
    """The cell's 8,192 prefill program, grouped dispatch under the share
    of 12 experts (two layers: the scan's body is what takes the
    temporaries, whatever the depth): it compiles (the latent's own
    prefill call, the grouped kernel in rounds) and its temporaries, 1.75
    GB, leave the 10.05 GB that stay resident at 12 layers (tree and
    cache) room on a 16 GB chip. Round the call nothing a head wide is
    made or laid out again (PR 62; until then 6.0 ms a layer of the 8,192
    program): no key built out to 64 heads of 192, no transpose, copy,
    pad or slice of a query, a key, a value or the result; the call reads
    the three products as they come, ``[1, 8192, 64 * 192]`` and twice
    ``[1, 8192, 64 * 128]``, and ``wo`` reads its result as it lies."""
    from gpustack_tpu.models.transformer import KVCache, forward

    bucket = 8192
    cfg = _axk1(2)
    shapes = _axk1_shapes(one_chip, cfg)

    def prefill(params, tokens):
        cache = KVCache.create(cfg, 1, bucket)
        positions = jnp.arange(bucket, dtype=jnp.int32)[None]
        logits, cache, held = forward(
            params, cfg, tokens, positions, cache, attn_impl="flash",
            moe_dispatch_impl="grouped", count_held_pairs=True,
        )
        return logits[0, -1], cache.k[:, 0], cache.v[:, 0], held

    compiled = jax.jit(prefill).lower(
        shapes, jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    ).compile()
    text = compiled.as_text()
    assert "moe_grouped_matmul" in text
    assert "flash_attention_prefill" not in text
    calls = re.findall(
        r"%mla_prefill_attention[\w.\-]* = bf16\[1,8192,8192\][^ ]* "
        r"custom-call\(([^)]*)\)", text,
    )
    assert len(calls) == 2, calls       # a layer, both written out
    by_head = [
        line.strip()[:120] for line in text.splitlines()
        if re.search(r"= \(?bf16\[1,(8192,64|64,8192),\d+\]", line)
    ]
    assert by_head == []
    for operands in calls:
        # the query, k_nope and v: each the output of its product's own
        # fusion (the scale of an int8 weight rides it), nothing between
        made_by = [
            re.search(
                rf"{re.escape(name)} = bf16\[1,8192,\d+\][^ ]* (\w[\w\-]*)\(",
                text,
            ).group(1)
            for name in re.findall(r"%[\w.\-]+", operands)
            if re.search(
                rf"{re.escape(name)} = bf16\[1,8192,(12288|8192)\]", text
            )
        ]
        assert made_by == ["fusion"] * 3, (operands, made_by)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


@pytest.mark.parametrize("T", [8192, 4096])
def test_the_latent_s_prefill_call_compiles_for_v5e(one_chip, T):
    """``mla_prefill_attention`` at the long-document cell's two buckets,
    A.X-K1's 64 heads of 128 + 64 / 128, operands token-major as the
    projections make them: the tile is the flash rule's at a group of one
    and a key of 192 (1,024 query rows against 2,048 keys), the VMEM the
    call asks for is granted, the result is ``[1, T, 64 * 128]`` and
    nothing but the rotation's two small tables is made beside it."""
    from gpustack_tpu.ops.flash_attention import Tiles, choose_tiles
    from gpustack_tpu.ops.mla_attention import (
        mla_prefill_attention,
        mla_prefill_takes,
    )

    H, nope, rope, vd = 64, 128, 64, 128
    assert mla_prefill_takes(H, nope, rope, vd)
    assert choose_tiles(T, T, 1, nope + rope, 2) == Tiles(1024, 1024, 2048, 4)

    def on(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf16, f32 = jnp.bfloat16, jnp.float32
    compiled = jax.jit(
        lambda *operands: mla_prefill_attention(*operands, 0.1)
    ).lower(
        on(bf16, 1, T, H * (nope + rope)), on(bf16, 1, T, H * nope),
        on(bf16, 1, T, rope), on(bf16, 1, T, H * vd),
        on(f32, 1, T, rope // 2), on(f32, 1, T, rope // 2),
    ).compile()
    text = compiled.as_text()
    assert re.search(
        rf"%mla_prefill_attention[\w.\-]* = bf16\[1,{T},{H * vd}\]"
        r".* custom-call\(",
        text,
    )
    wide = re.findall(rf"= bf16\[1,{T},\d\d\d\d+\][^ ]* ([\w\-]+)\(", text)
    assert sorted(wide) == ["custom-call"] + ["parameter"] * 3, wide
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


@pytest.mark.parametrize(
    "which,slots,S,held,F",
    [
        ("qwen3-30b-a3b", 32, 2048, 128, 768),    # the MoE rag cell's
        ("ax-k1", 16, 8192, 12, 2048),            # the long-document cell's
    ],
)
def test_a_decode_step_reads_the_touched_experts_and_no_others(
    one_chip, which, slots, S, held, F
):
    """The decode program of both configurations with experts as the
    runner traces it on one TPU chip (``live`` given, the experts' count
    beside the logits; two layers: the scan's body is what is looked
    at): the chooser says ``touched``, the kernel of
    ``ops/grouped_matmul.py touched_experts`` is in the program once a
    scan, fed from the stacked weights, and the dense products'
    intermediate ``[B, E_held, F]`` exists nowhere (they were 9.3 of the
    MoE cell's 14.8 ms step and 7.9 of A.X-K1's 16.7: PERF.md, PR 43);
    no layer's slab of experts is copied out of the stack before the
    call either."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
        moe_dispatch,
    )

    cfg = (
        _axk1(2) if which == "ax-k1"
        else dataclasses.replace(get_config(which), num_layers=2)
    )
    assert (cfg.num_held_experts, cfg.moe_intermediate_size) == (held, F)
    assert moe_dispatch(slots, cfg, "tpu", None, decode=True) == "touched"
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    ids = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def step(params, cache, tokens, positions, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl=decode_attention_impl(cfg, 1, S, "tpu", None),
            moe_dispatch_impl="touched", count_experts_read=True,
        )

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, ids, ids, live
    ).compile()
    text = compiled.as_text()
    D = cfg.hidden_size
    assert len(re.findall(
        rf"%moe_touched_experts[\w.\-]* = f32\[{slots},{D}\].* custom-call\(",
        text,
    )) == 1
    assert not re.findall(rf"\[{slots},(?:1,)?{held},{F}\]", text)
    assert not re.findall(
        rf"= s8\[{held},(?:{D},{F}|{F},{D})\]", text
    )
    # the stacked experts go in as they are stored, not as temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize(
    "B,E,D,F,int8,block_f",
    [
        (8, 64, 2048, 1408, False, 128),    # DeepSeek-V2-Lite's, bf16
        (8, 64, 2048, 1408, True, 1408),
        (8, 8, 4096, 14336, False, 512),    # Mixtral's
        (1, 128, 2048, 768, True, 768),     # one slot
        (4, 8, 64, 96, False, 96),          # a tiny preset: whole arrays
    ],
)
def test_the_touched_kernel_takes_other_models_widths(
    one_chip, B, E, D, F, int8, block_f
):
    """``moe_dispatch`` names ``touched`` for every model with silu
    experts and no biases, so the kernel has to compile at widths no cell
    runs: an intermediate width that does not halve into lane tiles, an
    expert too large for VMEM in bf16, fewer rows than a sublane tile."""
    from gpustack_tpu.ops import grouped_matmul as gm

    w = jnp.int8 if int8 else jnp.bfloat16
    assert gm.choose_block_f(B, D, F, 2, jnp.dtype(w).itemsize) == block_f

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scales = (
        tuple(on((2, E, 1, n), jnp.bfloat16) for n in (F, F, D))
        if int8 else None
    )
    compiled = jax.jit(gm.touched_experts).lower(
        on((B, D), jnp.bfloat16), on((B, E), jnp.float32),
        on((min(E, 8 * B),), jnp.int32), on((1,), jnp.int32),
        on((2, E, D, F), w), on((2, E, D, F), w), on((2, E, F, D), w),
        scales, on((), jnp.int32),
    ).compile()
    assert "moe_touched_experts" in compiled.as_text()


# ---- Nemotron-3-Nano: a recurrent state beside K and V (PR 46) ----


def _nemotron(pattern: str):
    """The benchmark's configuration at its published widths and its
    share, ``pattern`` deep (every kind of layer; the programs are
    unrolled, so a layer's operations are what is looked at)."""
    import json
    import os

    from gpustack_tpu.models.config import config_from_hf

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    with open(os.path.join(
        root, "perfbench", "configs", "nemotron-3-nano-30b-a3b-int8-ep8",
        "config.json",
    )) as f:
        hf = json.load(f)
    hf.update(hybrid_override_pattern=pattern, num_hidden_layers=len(pattern))
    return config_from_hf(hf, "nemotron-3-nano")


@pytest.fixture(scope="module")
def hybrid_decode(one_chip):
    """The decode program of the benchmark's hybrid as the runner traces
    it on one TPU chip, ``MEM*EME`` deep at 32 slots of 4,096, compiled:
    ``(text, memory analysis, the parameters' shapes)``."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward

    cfg = _nemotron("MEM*EME")
    slots, S = 32, 4096
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    ids = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def step(params, cache, tokens, positions, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl="kernel", moe_dispatch_impl="touched",
            ssm_impl="kernel", count_experts_read=True,
        )

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, ids, ids, live
    ).compile()
    return compiled.as_text(), compiled.memory_analysis(), params


def test_the_hybrid_decode_step_moves_its_state_in_place(hybrid_decode):
    """The decode program of the benchmark's hybrid as the runner traces
    it on one TPU chip, 32 slots of 4,096: every state-space layer's
    ``ssm_state_update`` reads and writes the stacked state where it
    lies (donated and aliased; PR 29 found XLA copying a donated stacked
    carry whole twice a step), the GQA kernel takes 2 kv heads, the
    touched-experts kernel the two-matrix form, and the experts' stored
    width keeps their matrices out of the temporaries (at 1,856 columns
    the TPU stores ``we_up`` transposed and the program copied all of
    it before every call: 1.8 GB of temporaries at full depth)."""
    from gpustack_tpu.models.hybrid import ssm_update_impl
    from gpustack_tpu.models.transformer import (
        decode_attention_impl,
        moe_dispatch,
    )

    cfg = _nemotron("MEM*EME")
    slots, S = 32, 4096
    assert moe_dispatch(slots, cfg, "tpu", None, decode=True) == "touched"
    assert decode_attention_impl(cfg, 1, S, "tpu", None) == "kernel"
    assert ssm_update_impl(1, "tpu", None) == "kernel"
    assert ssm_update_impl(512, "tpu", None) == "scan"
    assert ssm_update_impl(1, "cpu", None) == "xla"
    text, mem, params = hybrid_decode
    assert params["moe_layers"]["we_up"].q.shape == (3, 16, 2688, 1920)
    state = f"f32[3,{slots},64,64,128]"
    assert len(re.findall(
        rf"%ssm_state_update[\w.\-]* = \({re.escape(state)}", text
    )) == 3
    assert not re.findall(
        rf"= {re.escape(state)}[^ ]* (?:copy|dynamic-update-slice|"
        r"dynamic-slice|transpose)\(", text,
    )
    # the decay goes in as one scalar a head (SMEM), not spread over a
    # head's rows outside the kernel and picked back out of a lane
    # inside it: no operand of the call is built by a broadcast
    for operands in re.findall(
        r"%ssm_state_update[\w.\-]* = .* custom-call\(([^)]*)\)", text
    ):
        for name in re.findall(r"%[\w.\-]+", operands):
            made = re.search(
                rf"^\s*{re.escape(name)} = (\S+) ([\w\-]+)\(", text, re.M
            )
            assert made and made.group(2) != "broadcast", (name, made)
            assert not made.group(1).startswith(f"f32[{slots},64,64]"), made
    assert re.search(
        rf"%gqa_decode_attention[\w.\-]* = bf16\[{slots},32,128\]"
        r".* custom-call\(", text,
    )
    assert len(re.findall(
        rf"%moe_touched_experts[\w.\-]* = f32\[{slots},2688\].* custom-call\(",
        text,
    )) == 3
    # the stacked experts go in as they are stored: no copy of the stack
    # into another layout, no layer's slab cut out of it
    assert not re.findall(
        r"= s8\[(?:3,)?16,(?:2688,1920|1920,2688)\][^ ]* "
        r"(?:copy|dynamic-slice|transpose)\(", text,
    )
    # the attention layer's wq, wk and wv are read where they lie too
    # (transformer.finish_products; s8[4096,2688] and s8[1,2688,256]
    # twice were copied transposed every step until PR 47)
    assert not re.findall(
        r"= s8\[(?:1,)?(?:2688,4096|4096,2688|2688,256|256,2688)\][^ ]* "
        r"copy\(", text,
    )
    state_bytes = 3 * slots * 64 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    # no copy of the state (0.2 GB here, 1.57 GB at full depth)
    assert mem.temp_size_in_bytes < 0.25 * state_bytes
    # and no more than the program held before the kernel's body changed
    # (PR 54's tree, this compile: 3,354,624)
    assert mem.temp_size_in_bytes <= 3_354_624


def test_the_hybrid_decode_step_writes_its_conv_rows_once(hybrid_decode):
    """The same program's stacked conv rows (``KVCache.conv``,
    ``bf16[3,32,18432]`` here, ``[23,..]`` and 27 MB in the cell): a
    layer fetches its own rows, and only those, out of the stack as the
    step received it, and the step makes the stack once, after its last
    layer (``jnp.stack``). Until PR 64 every mixer updated the stack it
    was handed: compiled, each update worked on a copy of the whole
    array in the chip's second memory space, fetched in parts before
    the layer that needed one of them and written back by a
    ``copy-start`` / ``copy-done`` of the full shape (at this depth the
    copy stays there between the layers and is written back once; at
    the cell's 23 layers it went in and out every layer, 1.25 GB a
    step, 0.86 ms of a 15 ms step on the chip). What the compiler makes
    of ``jnp.stack`` at the cell's depth is 23 updates of one layer's
    rows in a buffer there and one ``copy-start`` of it back, which the
    chip's clock does not tell from no traffic at all (PERF.md section
    6, PR 64): so the text is held to a fetch a layer and at most one
    write of the whole."""
    text, mem, _ = hybrid_decode
    slots, layers = 32, 3
    stack = rf"bf16\[{layers},{slots},18432\]"
    # each layer's rows are read out of the stack the step was given, a
    # layer's at a time (asynchronous slices of one layer each)
    reads = re.findall(
        r" ([\w\-]+)\(%cache_conv[\w.]*\), slice=\{\[(\d+):(\d+)\]", text
    )
    assert sorted(reads) == [
        ("slice-start", str(l), str(l + 1)) for l in range(layers)
    ], reads
    # the whole stack goes nowhere but out, once at most
    moved = re.findall(
        rf"= \(?{stack}[^ ]*(?:, [^ ]+)*\)? "
        r"(copy|copy-start|slice-start|dynamic-slice)\(", text,
    )
    assert moved in ([], ["copy-start"]), moved
    # and it is made once: one operation of the entry computation has
    # it for a result, beside the step's own argument
    made = re.findall(
        rf"^  (?:ROOT )?%[\w.\-]+ = {stack}[^ ]* ([\w\-]+)\(",
        text[text.index("\nENTRY "):], re.M,
    )
    assert sorted(set(made) - {"parameter", "copy-done"}) == ["fusion"], made
    assert made.count("fusion") == 1, made
    # the result is the donated buffer, and nothing of the stack's size
    # is held beside it
    assert mem.alias_size_in_bytes >= layers * slots * 18432 * 2
    assert mem.temp_size_in_bytes <= 3_354_624


def test_the_touched_kernel_takes_two_matrix_experts_of_1920(one_chip):
    from gpustack_tpu.ops import grouped_matmul as gm

    B, E, D, F = 32, 16, 2688, 1920
    assert gm.choose_block_f(B, D, F, 2, 1, matrices=2) == 640

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scales = (
        None, on((23, E, 1, F), jnp.bfloat16), on((23, E, 1, D), jnp.bfloat16)
    )
    compiled = jax.jit(
        lambda x, c, ids, n, up, down, scales, layer: gm.touched_experts(
            x, c, ids, n, None, up, down, scales, layer
        )
    ).lower(
        on((B, D), jnp.bfloat16), on((B, E), jnp.float32),
        on((E,), jnp.int32), on((1,), jnp.int32),
        on((23, E, D, F), jnp.int8), on((23, E, F, D), jnp.int8),
        scales, on((), jnp.int32),
    ).compile()
    assert "moe_touched_experts" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


def test_flash_prefill_takes_two_kv_heads_of_sixteen_query_heads(one_chip):
    cfg = _nemotron("*")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    compiled = _flash_compiled(one_chip, cfg, 1024, 1024)
    assert re.search(
        r"%flash_attention_prefill[\w.\-]* = bf16\[1,32,1024,128\]"
        r".* custom-call\(", compiled.as_text(),
    )


# ---- Command A+: window and full layers in one stack ------------------------

COMMAND_A_DIR = "perfbench/configs/command-a-plus-int8-ep8-l8"


def _command_a(periods: int = 2):
    """The benchmark's configuration at its published widths and its
    share, ``periods`` periods of three sliding layers and a full one."""
    import os

    from gpustack_tpu.models.config import load_hf_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    cfg = load_hf_config(os.path.join(root, COMMAND_A_DIR))
    return dataclasses.replace(
        cfg, num_layers=4 * periods,
        layer_sliding=cfg.layer_sliding[:4 * periods],
    )


def _no_score_tensor(text: str, T: int, S: int):
    """No array of the program holds a query axis of ``T`` beside a key
    axis of ``S``: the ``[B, H, T, S]`` scores of the einsum path."""
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        sizes = [int(d) for d in dims.split(",")]
        both = sizes.count(T) >= 2 if T == S else T in sizes and S in sizes
        assert not (both and len(sizes) >= 3 and T * S <= np.prod(sizes)), dims


def test_a_window_stack_s_decode_step_walks_its_rings_in_place(one_chip):
    """The decode program of the benchmark's Command A+ share as the
    runner traces it on one TPU chip, 16 slots of 8,192: each sliding
    layer's kernel walks its ring of 4,096 rows in the window store,
    the full layer's its 8,192, both stores donated and aliased, every
    layer's matrices read where they lie in the stacks (scanned as
    ``[periods, 4, ...]`` slices they were copied out a period at a
    time: 1.3 GB of temporaries), 128 query heads a block of 512
    positions, and no score tensor over a slot's whole context."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
        moe_dispatch,
        needs_xla_attention,
    )
    from gpustack_tpu.ops.decode_attention import gqa_block_positions

    cfg = _command_a()
    slots, S = 16, 8192
    assert not needs_xla_attention(cfg)
    assert decode_attention_impl(cfg, 1, S, "tpu", None) == "kernel"
    assert decode_attention_impl(cfg, 4, S, "tpu", None) == "xla"
    assert moe_dispatch(slots, cfg, "tpu", None, decode=True) == "touched"
    assert gqa_block_positions(4096, 8, 128, 2) == 512
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    assert "mlp_norm" not in params["layers"]
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    assert cache.k.shape == (2, slots, S, 8, 128)
    assert cache.wk.shape == (6, slots, 4096, 8, 128)
    rows = cache.k.size + cache.wk.size
    assert cache.wk.size / rows == 0.6
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def step(params, tokens, positions, cache, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl="kernel", moe_dispatch_impl="touched",
            count_experts_read=True,
        )

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache, live
    ).compile()
    text = compiled.as_text()
    # one period in the scan's body: three rings and a full layer
    assert len(re.findall(
        r"%gqa_window_decode_attention[\w.\-]* = bf16\[16,128,128\]", text
    )) == 3
    assert len(re.findall(
        r"%gqa_decode_attention[\w.\-]* = bf16\[16,128,128\]", text
    )) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * rows * 2
    assert mem.temp_size_in_bytes < 64 * 2**20
    # the einsum path's scores, [B, Hkv, G, 1, S] float32, over a slab
    assert not re.search(r"f32\[16,(8,16|128),(1,)?(4096|8192)\]", text)


def test_a_window_stack_s_prefill_runs_the_band_and_no_score_tensor(one_chip):
    """An 8,192 prefill of one period at the published widths: the three
    sliding layers call the flash kernel with the band (under a name of
    their own), the full layer without, 128 query heads on 8 in tiles of
    128 query rows, the experts grouped; no ``[B, H, T, S]`` scores
    anywhere (34 GB at these shapes), and temporaries that leave the
    resident model room."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward, moe_dispatch
    from gpustack_tpu.ops.flash_attention import choose_tiles

    cfg = _command_a(periods=1)
    T = 8192
    assert moe_dispatch(T, cfg, "tpu", None) == "grouped"
    assert choose_tiles(T, T, 16, 128, 2) == (128, 128, 512, 4)
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    tokens = jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip)
    true_len = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def prefill(params, tokens, true_len):
        cache = KVCache.create(cfg, 1, T)
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        logits, cache, held = forward(
            params, cfg, tokens, positions, cache, attn_impl="flash",
            moe_dispatch_impl="grouped", count_held_pairs=True,
            true_len=true_len[None],
        )
        return (
            jnp.take(logits[0], true_len - 1, axis=0), cache.k[:, 0],
            cache.v[:, 0], cache.wk[:, 0], cache.wv[:, 0], held,
        )

    compiled = jax.jit(prefill).lower(params, tokens, true_len).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r"%flash_attention_window[\w.\-]* = bf16\[1,128,8192,128\]", text
    )) == 3
    assert len(re.findall(
        r"%flash_attention_prefill[\w.\-]* = bf16\[1,128,8192,128\]", text
    )) == 1
    _no_score_tensor(text, T, T)
    mem = compiled.memory_analysis()
    # the ring's rows go out at window size: 3 layers x 4,096 rows
    assert mem.output_size_in_bytes < 2 * (8192 + 3 * 4096) * 2048 * 2 * 1.1 + 2**20
    assert mem.temp_size_in_bytes < 2.5e9


OLMO_DIR = "perfbench/configs/olmo-hybrid-7b-int8"


def _olmo(periods: int = 2):
    """The benchmark's Olmo-Hybrid-7B at its published widths, ``periods``
    periods of three linear-attention layers and a full one."""
    import os

    from gpustack_tpu.models.config import load_hf_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    cfg = load_hf_config(os.path.join(root, OLMO_DIR))
    return dataclasses.replace(
        cfg, num_layers=4 * periods, layer_types=cfg.layer_types[:4 * periods],
    )


def test_a_delta_stack_s_decode_step_moves_state_and_rows_in_place(one_chip):
    """The decode program of the benchmark's Olmo-Hybrid as the runner
    traces it on one TPU chip, 12 slots of 2,560: each linear-attention
    layer's ``delta_state_update`` reads and writes the stacked state
    ``[L, B, 96, 5760]`` where it lies (nothing padded: 45 whole lane
    tiles; donated and aliased, no copy, slice or update of it), the full
    layer's GQA kernel walks rows of **32 stored heads** in place (at 30
    the TPU stores the rows with positions on the sublanes, and the
    program copied both caches whole into the other order and back every
    step: ``ModelConfig.kv_heads_stored``), every layer's matrices read
    where they lie in their stacks."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.hybrid import ssm_update_impl
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
    )

    cfg = _olmo()
    slots, S = 12, 2560
    assert (cfg.num_kv_heads, cfg.kv_heads_stored) == (30, 32)
    assert decode_attention_impl(cfg, 1, S, "tpu", None) == "kernel"
    assert ssm_update_impl(1, "tpu", None) == "kernel"
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    assert cache.ssm.shape == (6, slots, 96, 5760)
    assert cache.ssm.shape[-1] % 128 == 0 and cache.ssm.shape[-2] % 8 == 0
    assert cache.k.shape == cache.v.shape == (2, slots, S, 32, 128)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def step(params, tokens, positions, cache, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl="kernel", ssm_impl="kernel",
        )

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache, live
    ).compile()
    text = compiled.as_text()
    state = f"f32[6,{slots},96,5760]"
    rows = f"bf16[2,{slots},{S},32,128]"
    # one period in the scan's body: three updates and a full layer
    assert len(re.findall(
        rf"%delta_state_update[\w.\-]* = \({re.escape(state)}", text
    )) == 3
    assert len(re.findall(
        rf"%gqa_decode_attention[\w.\-]* = bf16\[{slots},32,128\]"
        r".* custom-call\(", text,
    )) == 1
    assert not re.findall(
        rf"= {re.escape(state)}[^ ]* (?:copy|dynamic-update-slice|"
        r"dynamic-slice|transpose)\(", text,
    )
    # the step's own rows are written into the donated caches; neither
    # is copied or stored in another order (any order of its axes)
    assert not re.findall(r"= bf16\[2,12,[\d,]+\][^ ]* (?:copy|transpose)\(", text)
    assert rows in text
    # the mixers' and the MLP's matrices go in as they are stored
    assert not re.findall(
        r"= s8\[(?:\d+,)?(?:3840,(?:2880|5760|3840|11008)|"
        r"(?:5760|11008),3840)\][^ ]* (?:copy|transpose)\(", text,
    )
    mem = compiled.memory_analysis()
    held = (cache.ssm.size * 4 + 2 * cache.k.size * 2)
    assert mem.alias_size_in_bytes >= held
    # no copy of the state (0.16 GB here) or of a cache (0.5 GB)
    assert mem.temp_size_in_bytes < 16 * 2**20


def test_a_delta_stack_s_prefill_keeps_its_temporaries_small(one_chip):
    """A 1,024 prefill of one period at the published widths: the three
    linear layers through the chunked rule as einsums in float32 (16
    chunks of 64, the solve for all heads and chunks together), the full
    layer through the flash kernel at 32 stored heads; temporaries that
    leave the resident model room (0.30 GB at full depth beside 12.1)."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward

    cfg = _olmo(1)
    T = 1024
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )

    def prefill(params, tokens, true_len):
        cache = KVCache.create(cfg, 1, T)
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        return forward(
            params, cfg, tokens, positions, cache, attn_impl="flash",
            logits_at=(true_len - 1)[None], true_len=true_len[None],
            ssm_impl="scan",
        )

    compiled = jax.jit(prefill).lower(
        params,
        jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r"%flash_attention_prefill[\w.\-]* = .* custom-call\(", text
    )) == 1
    _no_score_tensor(text, T, T)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30


SOLAR_DIR = "perfbench/configs/solar-open2-250b-int8-ep8-l12"


def _solar(periods: int = 1):
    """The benchmark's Solar-Open2 share at its published widths,
    ``periods`` periods of a gated attention layer and three KDA layers."""
    import os

    from gpustack_tpu.models.config import load_hf_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    cfg = load_hf_config(os.path.join(root, SOLAR_DIR))
    return dataclasses.replace(
        cfg, num_layers=4 * periods, layer_types=cfg.layer_types[:4 * periods],
    )


def test_a_kda_stack_s_decode_step_moves_state_rows_and_experts_in_place(
    one_chip,
):
    """The decode program of the benchmark's Solar-Open2 share as the
    runner traces it on one TPU chip, 32 slots of 2,560, one period: each
    KDA layer's ``kda_state_update`` (the decay a key channel, a column a
    head beside ``q`` and ``k``: nothing of the state's size is made for
    it) reads and writes the stacked state ``[L, B, 128, 8192]`` where it
    lies, the attention layer's GQA kernel walks its rows in place, and
    every layer's touched-experts kernel reads the 40 held experts of the
    stacked weights by the layer's index inside the scan over periods."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.hybrid import ssm_update_impl
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
        moe_dispatch,
    )

    cfg = _solar()
    slots, S = 32, 2560
    assert decode_attention_impl(cfg, 1, S, "tpu", None) == "kernel"
    assert ssm_update_impl(1, "tpu", None) == "kernel"
    assert moe_dispatch(slots, cfg, "tpu", None, decode=True) == "touched"
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    assert cache.ssm.shape == (3, slots, 128, 8192)
    assert cache.k.shape == cache.v.shape == (1, slots, S, 8, 128)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def step(params, tokens, positions, cache, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl="kernel", ssm_impl="kernel",
            moe_dispatch_impl="touched", count_experts_read=True,
        )

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache, live
    ).compile()
    text = compiled.as_text()
    state = f"f32[3,{slots},128,8192]"
    assert len(re.findall(
        rf"%kda_state_update[\w.\-]* = \({re.escape(state)}", text
    )) == 3
    assert not re.findall(r"%delta_state_update[\w.\-]* = ", text)
    assert len(re.findall(
        rf"%gqa_decode_attention[\w.\-]* = bf16\[{slots},64,128\]"
        r".* custom-call\(", text,
    )) == 1
    assert len(re.findall(
        r"%moe_touched_experts[\w.\-]* = .* custom-call\(", text
    )) == 4
    # no decay of the state's size: nothing f32 [.., 128, 8192] but the
    # state itself, and that neither copied, sliced nor updated
    assert not re.findall(
        rf"= {re.escape(state)}[^ ]* (?:copy|dynamic-update-slice|"
        r"dynamic-slice|transpose)\(", text,
    )
    assert not re.findall(rf"= f32\[{slots},128,8192\]", text)
    assert not re.findall(
        r"= bf16\[1,32,[\d,]+\][^ ]* (?:copy|transpose)\(", text
    )
    # the experts', the mixers' and the gate's matrices as they are stored
    assert not re.findall(
        r"= s8\[(?:\d+,)*(?:4096,(?:1280|8192|1024)|(?:1280|8192),4096)\]"
        r"[^ ]* (?:copy|transpose)\(", text,
    )
    mem = compiled.memory_analysis()
    held = cache.ssm.size * 4 + (cache.conv.size + 2 * cache.k.size) * 2
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 32 * 2**20


def test_a_kda_stack_s_prefill_keeps_its_temporaries_small(one_chip):
    """A 1,024 prefill of one period at the published widths: the three
    KDA layers through the chunked rule with a decay a key channel as
    einsums in float32 (16 chunks of 64 in sub-blocks of 16; the diagonal
    sub-blocks' ``[16, 16, 128]`` differences are reduced where they are
    made, never stored for all 64 heads), the attention layer through the
    flash kernel, every layer's experts through the grouped kernel;
    temporaries that leave the resident model room (0.51 GB at full depth
    beside 11.8)."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward, moe_dispatch

    cfg = _solar()
    T = 1024
    assert moe_dispatch(T, cfg, "tpu", None) == "grouped"
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )

    def prefill(params, tokens, true_len):
        cache = KVCache.create(cfg, 1, T)
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        return forward(
            params, cfg, tokens, positions, cache, attn_impl="flash",
            logits_at=(true_len - 1)[None], true_len=true_len[None],
            ssm_impl="scan", moe_dispatch_impl="grouped",
            count_held_pairs=True,
        )

    compiled = jax.jit(prefill).lower(
        params,
        jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r"%flash_attention_prefill[\w.\-]* = .* custom-call\(", text
    )) == 1
    # the chunked form runs under the scope of a decay a channel
    assert re.search(r'op_name="[^"]*/kda_chunk_scan/', text)
    assert not re.search(r'op_name="[^"]*/delta_chunk_scan/', text)
    _no_score_tensor(text, T, T)
    # the explicit differences of every head and sub-block at once would
    # be f32[1,16,64,4,16,16,128], 0.54 GB: inside a fusion that sums
    # over the channels, never a fusion's result
    assert re.findall(r"= f32\[1,16,64,4,16,16,128\]", text)
    assert not re.findall(
        r"%(?:[\w\-]*fusion|copy)[\w.\-]* = f32\[1,16,64,4,16,16,128\]", text
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 0.75 * 2**30


GRANITE_DIR = "perfbench/configs/granite-4.0-h-micro-int8"


def _granite(periods: int = 1):
    """The benchmark's Granite 4.0-H Micro at its published widths,
    ``periods`` periods of ten layers (``MMMMM*MMMM``)."""
    import os

    from gpustack_tpu.models.config import load_hf_config

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    cfg = load_hf_config(os.path.join(root, GRANITE_DIR))
    return dataclasses.replace(
        cfg, num_layers=10 * periods,
        layer_types=cfg.layer_types[:10 * periods],
    )


def test_a_mamba_stack_s_decode_step_moves_state_and_rows_in_place(one_chip):
    """The decode program of the benchmark's Granite 4.0-H Micro as the
    runner traces it on one TPU chip, 64 slots of 2,048: each Mamba-2
    layer's ``ssm_state_update`` (one group for all 64 heads) reads and
    writes the stacked state ``[L, 64, 64, 64, 128]`` where it lies, the
    conv rows ``[L, 64, 13056]`` are updated a layer at a time, and the
    attention layer's GQA kernel walks rows of **4 stored rows of 128
    lanes** in place, two heads of 64 to a row
    (``ModelConfig.kv_heads_a_row``): no copy, transpose or slice of the
    state, the conv rows or the rows; every layer's matrices read where
    they lie in their stacks."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.hybrid import ssm_update_impl
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
    )

    cfg = _granite(2)
    slots, S = 64, 2048
    assert (cfg.head_dim, cfg.kv_heads_a_row) == (64, 2)
    assert decode_attention_impl(cfg, 1, S, "tpu", None) == "kernel"
    assert ssm_update_impl(1, "tpu", None) == "kernel"
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    assert cache.ssm.shape == (18, slots, 64, 64, 128)
    assert cache.conv.shape == (18, slots, 13056)
    assert cache.k.shape == cache.v.shape == (2, slots, S, 4, 128)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def step(params, tokens, positions, cache, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl="kernel", ssm_impl="kernel",
        )

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, tokens, tokens, cache, live
    ).compile()
    text = compiled.as_text()
    state = f"f32[18,{slots},64,64,128]"
    # one period in the scan's body: nine updates and an attention layer
    assert len(re.findall(
        rf"%ssm_state_update[\w.\-]* = \({re.escape(state)}", text
    )) == 9
    assert len(re.findall(
        rf"%gqa_decode_attention[\w.\-]* = bf16\[{slots},32,128\]"
        r".* custom-call\(", text,
    )) == 1
    assert not re.findall(
        rf"= {re.escape(state)}[^ ]* (?:copy|dynamic-update-slice|"
        r"dynamic-slice|transpose)\(", text,
    )
    # the conv rows and the rows are written a layer's or a step's part
    # at a time into the donated arrays; neither is copied or stored in
    # another order
    assert not re.findall(
        rf"= bf16\[(?:18,{slots},13056|2,{slots},[\d,]+)\][^ ]* "
        r"(?:copy|transpose)\(", text,
    )
    assert f"bf16[2,{slots},{S},4,128]" in text
    # the mixers' and the MLP's matrices go in as they are stored
    assert not re.findall(
        r"= s8\[(?:\d+,)?(?:2048,(?:8512|8192|2048|512)|"
        r"(?:4096|8192),2048)\][^ ]* (?:copy|transpose)\(", text,
    )
    mem = compiled.memory_analysis()
    held = cache.ssm.size * 4 + cache.conv.size * 2 + 2 * cache.k.size * 2
    assert mem.alias_size_in_bytes >= held
    # no copy of the state (2.4 GB here), the conv rows (30 MB) or a
    # cache (0.27 GB)
    assert mem.temp_size_in_bytes < 16 * 2**20


def test_a_mamba_stack_s_prefill_keeps_its_temporaries_small(one_chip):
    """A 1,024 prefill of one period at the published widths: nine
    chunked scans as einsums in float32 (4 chunks of 256, one group),
    the attention layer through the flash kernel at heads of 64;
    temporaries that leave the resident model room (0.04 GB at full
    depth beside 9.4)."""
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import KVCache, forward

    cfg = _granite()
    T = 1024
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )

    def prefill(params, tokens, true_len):
        cache = KVCache.create(cfg, 1, T)
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        return forward(
            params, cfg, tokens, positions, cache, attn_impl="flash",
            logits_at=(true_len - 1)[None], true_len=true_len[None],
            ssm_impl="scan",
        )

    compiled = jax.jit(prefill).lower(
        params,
        jax.ShapeDtypeStruct((1, T), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r"%flash_attention_prefill[\w.\-]* = .* custom-call\(", text
    )) == 1
    _no_score_tensor(text, T, T)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30


def test_a_diffusion_block_pass_reads_cache_and_experts_where_they_lie(
    one_chip,
):
    """The block pass of the model generated by diffusion over blocks, as
    ``forward`` is traced for it on one TPU chip at the cell's shapes (32
    slots of 2,560, 4 rows a slot; two layers: the scan's body is what is
    looked at): the choosers say ``kernel`` and ``touched`` for its 128
    rows over the cache, both kernels are in the program once a scan (the
    decode kernel with the block's rows folded into its group: 4 kv heads
    x 4 rows x 8 query heads = 128 query rows), no layer's slab of rows
    and no expert matrix is copied, sliced or transposed, the dense
    products' ``[128, 128, 768]`` exists nowhere, and the block's rows
    reach the cache by ``ops/cache_write.py``'s call, not the scatter's
    loop."""
    import os

    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.config import load_hf_config
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
        moe_dispatch,
    )

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    cfg = dataclasses.replace(load_hf_config(os.path.join(
        root, "perfbench", "configs", "sdar-30b-a3b-chat-int8-l12"
    )), num_layers=2)
    slots, S, L = 32, 2560, cfg.diffusion_block
    assert L == 4
    assert decode_attention_impl(cfg, L, S, "tpu", None) == "kernel"
    assert decode_attention_impl(cfg, 2, S, "tpu", None) == "xla"
    assert moe_dispatch(slots * L, cfg, "tpu", None, decode=True) == "touched"
    params = _shapes_on(
        one_chip, lambda: quantize_params(init_params(cfg, jax.random.key(0)))
    )
    cache = _shapes_on(one_chip, lambda: KVCache.create(cfg, slots, S))
    ids = jax.ShapeDtypeStruct((slots, L), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)

    def block_pass(params, cache, tokens, positions, live):
        return forward(
            params, cfg, tokens, positions, cache, live=live,
            decode_attn_impl="kernel", moe_dispatch_impl="touched",
            count_experts_read=True,
        )

    compiled = jax.jit(block_pass, donate_argnums=(1,)).lower(
        params, cache, ids, ids, live
    ).compile()
    text = compiled.as_text()
    D, E, F = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    rows = slots * L
    assert len(re.findall(
        rf"%moe_touched_experts[\w.\-]* = f32\[{rows},{D}\].* custom-call\(",
        text,
    )) == 1
    assert len(re.findall(
        rf"%gqa_decode_attention[\w.\-]* = bf16\[{slots},{rows},128\].* "
        r"custom-call\(", text,
    )) == 1
    assert not re.findall(rf"\[{rows},(?:1,)?{E},{F}\]", text)
    assert not re.findall(rf"= s8\[{E},(?:{D},{F}|{F},{D})\]", text)
    # no slab of a layer's rows beside the cache, which is updated in place
    assert not re.findall(rf"= bf16\[{slots},{S},4,128\]", text)
    assert not re.findall(
        rf"= bf16\[2,{slots},{S},4,128\][^ ]* (?:copy|transpose)\(", text
    )
    # the block's rows go over their stored tile in one aliased call a
    # layer, keys and values, on the view the decode kernel reads; the
    # scatter's loop of one update a slot (a ``while`` that carries the
    # cache, for K and again for V) is in the program no more: the one
    # ``while`` left is the layers' scan
    written = re.findall(
        rf"%gqa_write_block_rows[\w.\-]* = \(bf16\[2,{slots},{S * 4},128\]"
        rf"[^ ]*, bf16\[2,{slots},{S * 4},128\][^ ]*\) custom-call\(.*", text
    )
    assert len(written) == 1
    assert "output_to_operand_aliasing={{0}: (4, {}), {1}: (5, {})}" in (
        written[0]
    )
    assert not re.findall(
        rf"= bf16\[2,{slots},{S * 4},128\][^ ]* (?:copy|transpose)\(", text
    )
    assert len(re.findall(r" while\(", text)) == 1
    assert not re.findall(r' while\(.*op_name="[^"]*/scatter"', text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.fixture(scope="module")
def lowered_hashes(one_chip):
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lowered_programs

    return lowered_programs.hashes(one_chip)


def _lowered_names():
    import json
    import os

    with open(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "lowered_programs.json"
    )) as f:
        return json.load(f)


@pytest.mark.parametrize("program", sorted(_lowered_names()))
def test_the_other_models_programs_lower_to_the_text_they_had(
    lowered_hashes, program
):
    """The decode and prefill programs of the benchmark's six
    configurations, lowered for the chip at their cells' shapes, are to
    the letter what they were when their hashes were taken: the first
    four before the window store, the band and the parallel block went
    into ``forward``, Command A+'s before the three copies of a GQA
    layer over a cache became one, those five unchanged when the mixer
    by kind and the stored kv heads went in (PR 53), Olmo-Hybrid's with
    that PR, A.X-K1's prefill again with PR 59 (a group of one's flash
    call asks for its VMEM, which is in the call's text; the tile itself
    is in the kernel's body, which is not hashed, so Olmo-Hybrid's new
    tile moved nothing), Solar-Open2's taken with PR 60, which left the
    other fourteen as they were, and the diffusion model's block pass
    and 1,024 prefill with PR 63, which left those sixteen as they were
    though the block's mask, the kernels' ``block`` and the touched
    experts at several rows a slot went into ``forward``; Nemotron's
    two taken again with PR 64 (its mixers hand their conv rows back
    and the step writes the stack once: ``models/hybrid.py``) and
    Granite's two with it (``hybrid.mamba_layer`` places the rows its
    mixer hands back, so a row's update stands after the state's in the
    text: the same operations in another order, and compiled the
    parent's program), which left the other fourteen as they were
    (``lowered_programs.py`` says what is hashed and how to take the
    hashes again on purpose)."""
    assert lowered_hashes[program] == _lowered_names()[program]
