"""Functional transformer core (Llama/Qwen/Mistral dense + Mixtral-class MoE).

TPU-first design notes:

- **scan over stacked layers**: per-layer weights are stacked on a leading
  ``[L, ...]`` axis and the block loop is a ``lax.scan`` — compile time stays
  O(1) in depth (an 80-layer Llama-70B traces one block, not eighty).
- **static shapes everywhere**: prefill and decode are separate jit
  specializations over fixed ``[B, T]``; the KV cache is a preallocated
  ``[L, B, S_max, H_kv, hd]`` buffer written in place (slot model, JetStream
  style) — no dynamic shapes, so XLA tiles every matmul onto the MXU.
- **GQA without materializing repeated KV**: queries are reshaped to
  ``[B, T, H_kv, G, hd]`` and contracted against the *unexpanded* KV — saves
  HBM bandwidth, which is the decode bottleneck.
- **bf16 matmuls, fp32 softmax/norm accumulations**.

The reference (gpustack/gpustack) has no model code — its data plane is
vLLM/SGLang in containers; this module is the heart of our in-repo TPU
engine that replaces them (reference worker/backends/vllm.py role).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from gpustack_tpu.models.config import ModelConfig
from gpustack_tpu.models.quant import QuantW

Params = Dict[str, Any]


def _mm(eq: str, x: jax.Array, w) -> jax.Array:
    """Weight matmul that transparently handles int8 ``QuantW`` leaves.

    For quantized weights the contraction runs on the int8 tensor (upcast in
    the MXU feed; the dequantized weight never hits HBM) and the
    per-output-channel scale multiplies the result — valid because every
    weight einsum here puts its scale axes last in the output.
    """
    if isinstance(w, QuantW):
        return jnp.einsum(eq, x, w.q.astype(x.dtype)) * w.s.astype(x.dtype)
    return jnp.einsum(eq, x, w)


def finish_products(decode: bool, *products):
    """The projections ``products`` (each ``[B, T, heads * width]``)
    of a layer's attention, finished before anything reads them where
    ``decode`` says the step is one row a slot over a cache (static).

    Without the barrier the TPU's compiler folds the reshape to heads
    that follows (and a q/k norm's sum of squares) into each product as
    an output fusion that writes heads-major, and for that wants the
    weight with the model dimension minor: it slices the layer's matrix
    out of the stacked int8 array and copies it into the transposed
    layout, every layer of every step (``wq``, ``wk`` and ``wv``: 3.7
    of the 8B deployment's 20.4 ms step; PERF.md, PR 47). Behind the
    barrier each is a plain ``[B, width]`` product that reads the stack
    where it lies, as the MLP's and ``wo``'s do;
    ``tests/ops/test_chip_compile.py::
    test_a_decode_step_reads_its_attention_weights_in_place`` holds it.
    With ``T > 1`` the copy is under a hundredth of the layer and the
    barrier would cost a pass over the products; and the trainer
    differentiates the cacheless path, which a barrier stays out of.
    """
    return lax.optimization_barrier(products) if decode else products


def qkv_projections(h: jax.Array, lp, decode: bool):
    """``(q, k, v)`` of one GQA layer as ``[B, T, heads * head_dim]``,
    before bias, reshape, norm and rotation (:func:`finish_products`)."""
    return finish_products(
        decode,
        _mm("btd,dq->btq", h, lp["wq"]),
        _mm("btd,dk->btk", h, lp["wk"]),
        _mm("btd,dk->btk", h, lp["wv"]),
    )


def _embed_lookup(embed, tokens: jax.Array, dtype) -> jax.Array:
    if isinstance(embed, QuantW):
        x = jnp.take(embed.q, tokens, axis=0).astype(dtype)
        return x * embed.s[tokens].astype(dtype)[..., None]
    return jnp.take(embed, tokens, axis=0).astype(dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Slot-based KV cache: ``k, v`` are ``[L, B, S_max, heads, width]``
    (``L``: the layers that keep a row for every position,
    ``cfg.num_kv_layers``; a stack with a window store keeps its sliding
    layers' rows in ``wk, wv`` below), what a position holds being the
    configuration's to say
    (``ModelConfig.kv_row_shapes``): each kv head's key and value for
    GQA; for MLA the shared latent ``c_kv`` (after its norm) in ``k`` and
    the shared rope key (after its rotation) in ``v``, one head each and
    of different widths. Whatever stores, moves or copies a slot's rows
    treats ``k`` and ``v`` as two opaque blocks of ``[L, T, heads,
    width]``; only ``forward`` knows what is in them.

    Rows (batch slots) are owned by the engine's slot allocator; positions are
    absolute token indices, so writing at ``positions`` and masking with
    ``cache_index <= query_position`` is all the bookkeeping attention needs.

    A step writes only its own rows: ``forward`` carries ``k`` and ``v``
    whole through its scan over the layers and each layer scatters the
    step's ``[B, T, H_kv, head_dim]`` rows to ``(layer, row,
    start..start+T-1)``, ``start = positions[row, 0]`` (positions are
    contiguous per row; ``_write_rows``). Donated to the jitted step, the
    cache is updated in place; nothing else of it moves.

    Bounds contract: a start with ``start + T > max_len`` is CLAMPED to
    ``max_len - T`` instead of failing (static-shape jit semantics, what
    ``dynamic_update_slice`` did before the scatter) — the block lands on
    the tail of that row's own slot and silently corrupts it. Callers (the
    engine slot allocator) must enforce ``position + T <= max_len`` before
    dispatching a step.
    """

    k: jax.Array
    v: jax.Array
    # What a slot keeps beside its rows, None for a model without it;
    # ``k, v`` then hold the layers of ``cfg.num_kv_layers`` only. No
    # span of positions carries either: ``ModelConfig.beside_rows`` says
    # what follows, the methods below are what a holder of a cache calls
    # (docs/KV_CACHE.md, "What a slot keeps").
    # A state, of one of two kinds and of the shapes the configuration
    # says (``ModelConfig.state_shapes``): each state-space layer's
    # recurrent state ``[L_M, B, H, P, N]`` (models/hybrid.py) or each
    # delta-rule layer's ``[L_lin, B, Dk, H * Dv]`` (models/delta.py),
    # float32, and the last ``kernel - 1`` rows of its convolutions'
    # input, side by side, ``[L, B, (K-1) * C]``: one a slot whatever its
    # length, a prefill ends in one and a decode step moves it on.
    ssm: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    # A ring (``cfg.window_rows``): the sliding layers' rows, ``wk, wv
    # [L_sliding, B, W, heads, width]``, ``W = min(sliding_window,
    # S_max)``: position ``p`` lies in row ``p mod W``, so a slot longer
    # than the window holds the last ``W`` positions, all a sliding layer
    # attends. Keys are stored rotated, so the rows' order means nothing
    # to the softmax.
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @staticmethod
    def create(
        cfg: ModelConfig, batch: int, max_len: int, dtype=None
    ) -> "KVCache":
        if dtype is None:
            # follow the model's compute dtype: K/V written by forward
            # must match the buffer (the row write is dtype-strict)
            dtype = (
                jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
            )
        k_row, v_row = cfg.kv_row_shapes
        lead = (cfg.num_kv_layers, batch, max_len)
        state = {}
        if cfg.state_shapes:
            layers, ssm, conv = cfg.state_shapes
            state = dict(
                ssm=jnp.zeros((layers, batch) + ssm, jnp.float32),
                conv=jnp.zeros((layers, batch) + conv, dtype),
            )
        if cfg.window_rows:
            ring = (
                cfg.num_window_layers, batch,
                min(cfg.sliding_window, max_len),
            )
            state = dict(
                wk=jnp.zeros(ring + k_row, dtype),
                wv=jnp.zeros(ring + v_row, dtype),
            )
        return KVCache(
            k=jnp.zeros(lead + k_row, dtype), v=jnp.zeros(lead + v_row, dtype),
            **state,
        )

    def slot_share(self):
        """Of a one-slot cache (a prefill's), what slot 0 keeps beside
        ``k, v``, for :meth:`with_slot`'s ``beside``; None for rows alone."""
        if self.ssm is not None:
            return self.ssm[:, 0], self.conv[:, 0]
        if self.wk is not None:
            return self.wk[:, 0], self.wv[:, 0]
        return None

    def with_slot(self, slot, k, v, beside=None) -> "KVCache":
        """A prefill's rows ``k, v [L, Tb, heads, width]`` in the first
        ``Tb`` positions of ``slot``, and its :meth:`slot_share`."""
        Tb = k.shape[1]
        new = dict(
            k=self.k.at[:, slot, :Tb].set(k), v=self.v.at[:, slot, :Tb].set(v)
        )
        if self.ssm is not None:
            # the slot's whole state is the prompt's, nothing of its last
            # tenant's stays (zeros without one)
            ssm, conv = beside if beside is not None else (0.0, 0.0)
            new.update(
                ssm=self.ssm.at[:, slot].set(ssm),
                conv=self.conv.at[:, slot].set(conv),
            )
        if self.wk is not None:
            # the prefill's ring rows lie where the slot's ring wants
            # them (row = position mod W; a bucket under the window is
            # its own first rows); what the last tenant left above them
            # is overwritten before a length reaches it
            wk, wv = beside
            new.update(
                wk=self.wk.at[:, slot, :wk.shape[1]].set(wk),
                wv=self.wv.at[:, slot, :wv.shape[1]].set(wv),
            )
        return KVCache(**new)

    def unmaskable(self) -> Tuple[jax.Array, ...]:
        """Copies of what a rollback by position cannot mask out, for
        :meth:`with_unmaskable` to put back whole: a state. Not a ring:
        a model with one is refused speculation at engine start, nobody
        snapshots it, and ``wk, wv`` are copied for nobody."""
        if self.ssm is None:
            return ()
        return jnp.array(self.ssm), jnp.array(self.conv)

    def with_unmaskable(self, saved) -> "KVCache":
        if not saved:
            return self
        return dataclasses.replace(self, ssm=saved[0], conv=saved[1])

    def shardings(self, rows, beside) -> "KVCache":
        """What to ``jax.device_put`` this cache with: ``rows`` for ``k,
        v``, ``beside`` for whatever else it keeps."""
        return dataclasses.replace(
            jax.tree.map(lambda _: beside, self), k=rows, v=rows
        )

    def memory(self) -> Dict[str, Any]:
        """Bytes by kind, a state's dtype and the rows of a slot's ring
        (None, 0 without one): ``/healthz`` ``cache`` and the exporter."""

        def nbytes(*bufs):
            return sum(int(b.nbytes) for b in bufs if b is not None)

        return {
            "kv_bytes": nbytes(self.k, self.v),
            "state_bytes": nbytes(self.ssm, self.conv),
            "state_dtype": None if self.ssm is None else str(self.ssm.dtype),
            "window_bytes": nbytes(self.wk, self.wv),
            "window_rows": 0 if self.wk is None else self.wk.shape[2],
        }


_ROW_WRITE = lax.ScatterDimensionNumbers(
    update_window_dims=(1, 2, 3),         # [T, heads, width] a row
    inserted_window_dims=(0,),            # one layer
    scatter_dims_to_operand_dims=(0, 2),  # index = (layer, start)
    operand_batching_dims=(1,),           # row b of the cache takes
    scatter_indices_batching_dims=(0,),   # row b of the step
)


_ROW_WRITE_1HEAD = lax.ScatterDimensionNumbers(
    update_window_dims=(1, 2),            # [T, width] a row
    inserted_window_dims=(0,),
    scatter_dims_to_operand_dims=(0, 2),
    operand_batching_dims=(1,),
    scatter_indices_batching_dims=(0,),
)


def _write_rows(
    buf: jax.Array,      # [L, B, S_max, heads, width], one of a KVCache
    rows: jax.Array,     # [B, T, heads, width], this step's rows for it
    layer: jax.Array,    # int32 scalar
    start: jax.Array,    # [B] int32, each row's first position
    by_position: bool = False,
    decode_attn_impl: str = "xla",
) -> jax.Array:
    """``buf`` with ``rows[b]`` at ``[layer, b, start[b]:start[b]+T]``
    (the bounds contract is ``KVCache``'s).

    A scatter of ``B`` blocks: only the step's rows move. ``by_position``
    gives the same result for a cache sharded over its positions (``sp``),
    where GSPMD cannot place a block of ``T > 1`` at an offset it does not
    know without gathering all of ``buf``, whatever sharding the carry
    and the rows are pinned to (``with_sharding_constraint`` on either
    compiles to the same all-gathers; PERF.md, PR 29): every position of
    the layer takes its row of the step or keeps its own, one elementwise
    pass over the layer that each shard makes over its own positions.
    ``tests/ops/test_chip_compile.py`` holds both to a described 2x2.

    A cache of one head (the MLA latent) comes **without its head**,
    ``[L, B, S_max, width]`` and ``[B, T, width]``: that is how the TPU
    stores it, and a scatter of ``[T, 1, width]`` windows asks for
    another layout, to which the whole cache is copied and back, every
    step (compiled for a described v5e: 4 GB of temporaries at 16 slots
    of 8,192). Where its width is no whole number of lane tiles (the
    rope keys, 64) the TPU stores it with the **positions on the
    lanes**, which no scatter writes in place. A decode step whose
    attention is the kernel's (``decode_attn_impl`` not ``"xla"``: one
    chip, one row a slot) writes either through the aliased call that
    lies beside that kernel, one stored tile a slot: the rope keys a
    lane tile (``ops/mla_attention.py mla_write_rope_keys``), the latent
    a tile of 16 rows (``mla_write_latent_rows``), which the scatter
    below writes in place too but as a loop of one update a slot (0.86
    of A.X-K1's 7.43 ms step: PERF.md section 6, PR 57). Everything
    else keeps what it had: a mesh (a Mosaic call is not partitioned),
    any other platform, ``T > 1`` over a cache (a prefill, a verify
    step, a continuation, a chunk) and ``by_position``. For the rope
    keys that is the pass over the layer's positions, which writes
    whatever the layout: 17 MB read and written a layer at 16 slots of
    8,192, and the array copied whole once in and once out of the scan
    (0.2 GB and 0.6 ms each in a decode step: PERF.md section 6, PR 54).

    A GQA cache's rows come here from ``attend_over_cache`` in every
    case but one, a **whole tile**: a diffusion block's ``T`` rows a slot
    under the decode kernel, where ``T`` rows of the stored heads are
    whole stored tiles, go over their tile by the aliased call beside
    that kernel's view (``ops/cache_write.py gqa_write_block_rows``,
    keys and values in one call, nothing fetched) and not through the
    loop of one update a slot below (2.5 of SDAR's 11.45 ms pass: PERF.md
    section 6, PR 66). A **part tile**, one row a slot of any GQA model's
    decode step, is a quarter of such a tile and wants the latent's
    read-modify-write; it keeps the scatter until the benchmark's count
    of experts read is mended (ROADMAP A5(b), B5). **Everything else**
    keeps the scatter as the latent does: a prefill, a verify step, a
    chunk, ``by_position``, a mesh, any other platform, a block that is
    no whole tile.
    """
    T = rows.shape[1]
    one_head = buf.ndim == 4
    on_lanes = one_head and buf.shape[3] % 128 != 0
    if one_head and T == 1 and not by_position and decode_attn_impl != "xla":
        from gpustack_tpu.ops import mla_attention

        write = (
            mla_attention.mla_write_rope_keys if on_lanes
            else mla_attention.mla_write_latent_rows
        )
        return write(
            buf, rows[:, 0], layer, start,
            interpret=decode_attn_impl == "kernel_interpret",
        )
    # a row's trailing axes, for what is indexed by [B, S_max] or [B, T]
    each = (slice(None), slice(None)) + (None,) * (rows.ndim - 2)
    if by_position or on_lanes:
        at = jnp.arange(buf.shape[2], dtype=jnp.int32)[None, :] - jnp.clip(
            start, 0, buf.shape[2] - T
        )[:, None]                                    # [B, S_max] into rows
        # one row a slot (a decode step) goes to its position as it is:
        # a select against the broadcast row runs at the memory's rate,
        # where the gather below took 0.57 ms a layer of [16, 8192, 64]
        # (my chip run, PR 35: 6.8 of a decode step's 26 ms)
        new = rows if T == 1 else jnp.take_along_axis(
            rows, jnp.clip(at, 0, T - 1)[each], axis=1
        )
        old = lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)
        hit = ((at >= 0) & (at < T))[each]
        return lax.dynamic_update_index_in_dim(
            buf, jnp.where(hit, new, old), layer, 0
        )
    index = jnp.stack([jnp.broadcast_to(layer, start.shape), start], axis=1)
    return lax.scatter(
        buf, index, rows, _ROW_WRITE_1HEAD if one_head else _ROW_WRITE,
        unique_indices=True, mode=lax.GatherScatterMode.CLIP,
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16
) -> Params:
    """Random init with layer weights stacked on a leading [L] axis."""
    if cfg.layer_kinds is not None:
        # the hybrid: three stacks, one a kind of layer (models/hybrid.py)
        from gpustack_tpu.models.hybrid import init_hybrid_layers

        k_layers, k_embed, k_head = jax.random.split(key, 3)
        d = cfg.hidden_size
        params = init_hybrid_layers(cfg, k_layers, dtype)
        params["embed"] = (
            jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32) * 0.02
        ).astype(dtype)
        params["final_norm"] = jnp.ones((d,), dtype)
        if not cfg.tie_word_embeddings:
            params["lm_head"] = (
                jax.random.normal(k_head, (d, cfg.vocab_size), jnp.float32)
                / math.sqrt(d)
            ).astype(dtype)
        return params
    if cfg.is_moe and cfg.first_k_dense:
        # DeepSeek's heterogeneous stack is two homogeneous ones, each
        # drawn at its own depth: a dense prefix (own MLP shapes) and the
        # MoE remainder (forward scans them back to back). Drawn as one
        # stack of L and cut in two, a stacked matrix is materialised in
        # float32 before its slices (three times 7.9 GB for an expert
        # matrix of A.X-K1's share, on a v5e, PR 35), where a leaf drawn
        # whole fuses into one pass.
        kd = cfg.first_k_dense
        k_dense, k_rest = jax.random.split(key)
        params = init_params(
            dataclasses.replace(
                cfg, num_layers=cfg.num_layers - kd, first_k_dense=0
            ),
            k_rest, dtype,
        )
        params["dense_layers"] = init_params(
            dataclasses.replace(
                cfg, num_layers=kd, first_k_dense=0, num_experts=0
            ),
            k_dense, dtype,
        )["layers"]
        return params
    d, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    keys = iter(jax.random.split(key, 32))

    def w(k, *shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    if cfg.layer_types is not None:
        # a mixer by kind and an MLP in every layer: what every layer
        # has (its two norms, the MLP) in one stack of L, each kind of
        # mixer's leaves in a stack of its own (models/delta.py,
        # models/hybrid.py)
        from gpustack_tpu.models.delta import init_delta_layers
        from gpustack_tpu.models.hybrid import init_mamba_layers

        La = cfg.num_kv_layers
        params = {
            "embed": w(next(keys), cfg.vocab_size, d, scale=0.02),
            "final_norm": jnp.ones((d,), dtype),
            "layers": {
                "attn_norm": jnp.ones((L, d), dtype),
                "mlp_norm": jnp.ones((L, d), dtype),
                **(
                    _init_experts(
                        cfg, L, lambda *a, **k: w(next(keys), *a, **k), dtype
                    )
                    if cfg.is_moe else {
                        "w_gate": w(next(keys), L, d, f),
                        "w_up": w(next(keys), L, d, f),
                        "w_down": w(next(keys), L, f, d),
                    }
                ),
            },
        }
        if La:
            params["attn_layers"] = {
                "wq": w(next(keys), La, d, cfg.q_dim),
                "wk": w(next(keys), La, d, cfg.kv_dim),
                "wv": w(next(keys), La, d, cfg.kv_dim),
                "wo": w(next(keys), La, cfg.q_dim, d),
            }
            if cfg.attn_output_gate:
                params["attn_layers"]["wg"] = w(next(keys), La, d, cfg.q_dim)
            if cfg.qk_norm_whole:
                params["attn_layers"].update(
                    q_norm=jnp.ones((La, cfg.q_dim), dtype),
                    k_norm=jnp.ones((La, cfg.kv_dim), dtype),
                )
        if cfg.num_linear_layers:
            params["delta_layers"] = init_delta_layers(
                cfg, next(keys), dtype
            )
        if cfg.num_mamba_layers:
            params["ssm_layers"] = init_mamba_layers(
                cfg, cfg.num_mamba_layers,
                lambda *shape, scale=None: w(next(keys), *shape, scale=scale),
                keys, dtype,
            )
        if not cfg.tie_word_embeddings:
            params["lm_head"] = w(next(keys), d, cfg.vocab_size)
        return params
    if cfg.is_mla:
        qk = cfg.head_dim
        layers: Dict[str, jax.Array] = {
            "attn_norm": jnp.ones((L, d), dtype),
            "wkv_a": w(
                next(keys), L, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim
            ),
            "kv_a_norm": jnp.ones((L, cfg.kv_lora_rank), dtype),
            # the checkpoint's kv_b_proj, its key and value columns
            # apart (W_uk, W_uv): a step over the latent cache absorbs
            # the one into the query and the other into the output
            "wk_b": w(
                next(keys), L, cfg.kv_lora_rank,
                cfg.num_heads * cfg.qk_nope_head_dim,
            ),
            "wv_b": w(
                next(keys), L, cfg.kv_lora_rank,
                cfg.num_heads * cfg.v_head_dim,
            ),
            "wo": w(next(keys), L, cfg.num_heads * cfg.v_head_dim, d),
            "mlp_norm": jnp.ones((L, d), dtype),
        }
        if cfg.q_lora_rank:
            layers["wq_a"] = w(next(keys), L, d, cfg.q_lora_rank)
            layers["q_a_norm"] = jnp.ones((L, cfg.q_lora_rank), dtype)
            layers["wq_b"] = w(
                next(keys), L, cfg.q_lora_rank, cfg.num_heads * qk
            )
        else:
            layers["wq"] = w(next(keys), L, d, cfg.num_heads * qk)
    else:
        layers = {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": w(next(keys), L, d, cfg.q_dim),
            "wk": w(next(keys), L, d, cfg.kv_dim),
            "wv": w(next(keys), L, d, cfg.kv_dim),
            "wo": w(next(keys), L, cfg.q_dim, d),
            "mlp_norm": jnp.ones((L, d), dtype),
        }
    if cfg.parallel_block:
        # one norm a layer: attention and MLP both read attn_norm's
        del layers["mlp_norm"]
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, cfg.q_dim), dtype)
        layers["bk"] = jnp.zeros((L, cfg.kv_dim), dtype)
        layers["bv"] = jnp.zeros((L, cfg.kv_dim), dtype)
    if cfg.o_bias:
        layers["bo"] = jnp.zeros((L, d), dtype)
    if cfg.attn_sinks:
        layers["sinks"] = jnp.zeros((L, cfg.num_heads), jnp.float32)
    if cfg.norm_delta_gain:
        # gemma stores norm gains as deltas: zero == identity gain
        for name in ("attn_norm", "mlp_norm"):
            layers[name] = jnp.zeros((L, d), dtype)
    if cfg.qk_norm:
        init = jnp.zeros if cfg.norm_delta_gain else jnp.ones
        layers["q_norm"] = init((L, cfg.head_dim), dtype)
        layers["k_norm"] = init((L, cfg.head_dim), dtype)
    if cfg.post_norms:
        init = jnp.zeros if cfg.norm_delta_gain else jnp.ones
        layers["post_attn_norm"] = init((L, d), dtype)
        layers["post_mlp_norm"] = init((L, d), dtype)
    if cfg.is_moe:
        layers.update(_init_experts(
            cfg, L, lambda *a, **k: w(next(keys), *a, **k), dtype
        ))
    else:
        layers["w_gate"] = w(next(keys), L, d, f)
        layers["w_up"] = w(next(keys), L, d, f)
        layers["w_down"] = w(next(keys), L, f, d)

    params: Params = {
        "embed": w(next(keys), cfg.vocab_size, d, scale=0.02),
        "layers": layers,
        "final_norm": (
            jnp.zeros if cfg.norm_delta_gain else jnp.ones
        )((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), d, cfg.vocab_size)
    return params


def _init_experts(cfg: ModelConfig, L: int, w, dtype) -> Dict[str, jax.Array]:
    """``L`` layers' router, routed experts and shared expert, random
    (``w(*shape, scale=)`` draws one leaf), beside whatever mixer the
    layers have."""
    d, fm, E = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
    # the router scores every expert; weights exist for those held
    Eh = cfg.num_held_experts
    layers = {
        "router": w(L, d, E),
        "we_gate": w(L, Eh, d, fm),
        "we_up": w(L, Eh, d, fm),
        "we_down": w(L, Eh, fm, d, scale=1.0 / math.sqrt(fm)),
    }
    if cfg.shared_expert_intermediate_size:
        fs = cfg.shared_expert_intermediate_size
        layers["ws_gate"] = w(L, d, fs)
        layers["ws_up"] = w(L, d, fs)
        layers["ws_down"] = w(L, fs, d)
        if cfg.shared_expert_gated:
            layers["shared_gate"] = w(L, d, 1)
    if (
        cfg.moe_scoring in ("sigmoid", "softmax_topk")
        and cfg.router_correction_bias
    ):
        # DeepSeek-V3 correction bias / GPT-OSS affine router
        layers["router_bias"] = jnp.zeros((L, E), jnp.float32)
    if cfg.moe_bias:
        layers["we_gate_b"] = jnp.zeros((L, Eh, fm), dtype)
        layers["we_up_b"] = jnp.zeros((L, Eh, fm), dtype)
        layers["we_down_b"] = jnp.zeros((L, Eh, d), dtype)
    return layers


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(
    x: jax.Array, w: jax.Array, eps: float, delta_gain: bool = False
) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    n = xf * lax.rsqrt(var + eps)
    if delta_gain:
        # gemma convention: stored weight is a delta on a unit gain,
        # multiplied in fp32 before the downcast
        return (n * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return n.astype(x.dtype) * w


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Mean-centred LayerNorm without bias (Cohere): ``(x - mean) /
    sqrt(var + eps) * w``, float32 inside."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return (xc * lax.rsqrt(var + eps)).astype(x.dtype) * w


def model_norm(x: jax.Array, w: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The norm ``cfg`` names, round every layer and before the head."""
    if cfg.layer_norm:
        return layer_norm(x, w, cfg.rms_norm_eps)
    return rms_norm(x, w, cfg.rms_norm_eps, cfg.norm_delta_gain)


def head(
    x: jax.Array,                     # [B, T, d] after the last layer
    params: Params,
    cfg: ModelConfig,
    logits_at: Optional[jax.Array] = None,
    return_hidden: bool = False,
) -> jax.Array:
    """The final norm and the vocabulary head, the one copy every model's
    ``forward`` ends in: float32 logits ``[B, T, vocab]``, or with
    ``return_hidden`` the normalised hidden states ``[B, T, d]``.

    ``logits_at`` (int32 ``[B]``, an index along ``T``; None: every row)
    names the one row a sequence whose result is wanted: the hidden
    state is gathered to ``[B, 1, d]`` *before* the norm and the
    product, in the dtype it has, so the product keeps its operands and
    its accumulation and only its row count changes. The compiler does
    not move a ``take`` of the logits through the product: a 2,048
    prefill of 151,936 columns computed all 2,048 rows and kept one
    (13-15 ms of the 8B's 193 ms prefill and 1.2 GB of float32: PERF.md,
    PR 49)."""
    if logits_at is not None:
        x = jnp.take_along_axis(
            x, logits_at[:, None, None], axis=1, mode="clip"
        )
    x = model_norm(x, params["final_norm"], cfg)
    if return_hidden:
        # embeddings path: final normalized hidden states, no LM head
        return x.astype(jnp.float32)
    if cfg.tie_word_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"])
    else:
        logits = _mm("btd,dv->btv", x, params["lm_head"])
    logits = logits.astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    if cfg.final_logit_softcap:
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits


def _inv_freq(theta: float, head_dim: int) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )


def rope_params(cfg: ModelConfig) -> Tuple[jax.Array, float]:
    """(inv_freq, attention_factor) for the main RoPE path.

    Handles HF llama3/linear/yarn scaling plus GGUF ``rope_freqs.weight``
    exports: llama.cpp ships the blended llama3 divisors as a precomputed
    per-frequency tensor instead of metadata (convert_hf_to_gguf
    generate_extra_tensors), surfaced here as ``rs["factors"]`` — those
    divisors are authoritative over the formula when present.
    attention_factor scales sin/cos (squaring into scores), matching HF's
    ``attention_scaling`` on the rotary embedding; it is 1.0 for
    non-yarn types."""
    rs = cfg.rope_scaling or {}
    rope_type = rs.get("rope_type") or rs.get("type")
    factors = rs.get("factors")
    inv = _inv_freq(cfg.rope_theta, cfg.head_dim)
    if rope_type == "yarn":
        yarn_inv, att = yarn_inv_freq(cfg.rope_theta, cfg.head_dim, rs)
        if factors is not None:
            return inv / jnp.asarray(factors, jnp.float32), att
        return yarn_inv, att
    if factors is not None:
        return inv / jnp.asarray(factors, jnp.float32), 1.0
    if rope_type == "linear":
        inv = inv / rs["factor"]
    elif rope_type == "llama3":
        # HF reference semantics: high-freq band (short wavelength) keeps
        # raw frequencies, low-freq band divides by `factor`, and the
        # medium band interpolates between the two.
        factor = rs["factor"]
        low = rs.get("low_freq_factor", 1.0)
        high = rs.get("high_freq_factor", 4.0)
        orig = rs.get("original_max_position_embeddings", 8192)
        wavelen = 2 * math.pi / inv
        smooth = (orig / wavelen - low) / (high - low)
        interpolated = (1 - smooth) * inv / factor + smooth * inv
        inv = jnp.where(
            wavelen > orig / low,
            inv / factor,
            jnp.where(wavelen < orig / high, inv, interpolated),
        )
    elif rope_type not in (None, "default"):
        raise ValueError(
            f"unsupported rope_scaling type {rope_type!r} (supported: "
            "default/linear/llama3/yarn/gguf rope_freqs)"
        )
    return inv, 1.0


def rope_inv_freq(cfg: ModelConfig) -> jax.Array:
    """Inverse RoPE frequencies with HF-compatible scaling (see
    rope_params; this back-compat wrapper drops the attention factor)."""
    return rope_params(cfg)[0]


def yarn_get_mscale(scale: float, m: float = 1.0) -> float:
    """DeepSeek's yarn_get_mscale (modeling_deepseek_v2): attention
    magnitude correction for YaRN-interpolated rope."""
    if scale <= 1:
        return 1.0
    return 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(
    theta: float, dim: int, rs: Dict[str, Any]
) -> Tuple[jax.Array, float]:
    """YaRN NTK scaling (HF _compute_yarn_parameters semantics):
    interpolated and extrapolated frequency tables blended over a linear
    ramp between the beta correction dims; returns (inv_freq,
    attention_factor) — the factor scales sin/cos, which squares into
    the attention scores exactly like HF's freqs_cis scaling."""
    factor = float(rs["factor"])
    beta_fast = float(rs.get("beta_fast") or 32)
    beta_slow = float(rs.get("beta_slow") or 1)
    orig = int(
        rs.get("original_max_position_embeddings") or 4096
    )
    mscale = rs.get("mscale")
    mscale_all = rs.get("mscale_all_dim")
    attention_factor = rs.get("attention_factor")

    if attention_factor is None:
        if mscale and mscale_all:
            attention_factor = yarn_get_mscale(
                factor, mscale
            ) / yarn_get_mscale(factor, mscale_all)
        else:
            attention_factor = yarn_get_mscale(factor)

    def correction_dim(n_rot):
        return (
            dim * math.log(orig / (n_rot * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = correction_dim(beta_fast)
    high = correction_dim(beta_slow)
    if rs.get("truncate", True):
        # HF find_correction_range: integer bounds unless the config
        # opts out (GPT-OSS ships truncate: false — fractional ramp)
        low, high = math.floor(low), math.ceil(high)
    low = max(low, 0)
    high = min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0,
    )
    extrapolation_factor = 1.0 - ramp
    pos_freqs = theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )
    inv_extra = 1.0 / pos_freqs
    inv_interp = 1.0 / (factor * pos_freqs)
    inv = (
        inv_interp * (1 - extrapolation_factor)
        + inv_extra * extrapolation_factor
    )
    return inv, float(attention_factor)


def rope_sin_cos(
    positions: jax.Array, inv_freq: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """positions [B, T] -> (sin, cos) each [B, T, head_dim/2], fp32."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """HF 'rotate_half' convention. x: [B, T, H, hd], sin/cos: [B, T, hd/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[:, :, None, :].astype(x.dtype)
    cos = cos[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope_interleaved(
    x: jax.Array, sin: jax.Array, cos: jax.Array
) -> jax.Array:
    """Interleaved-pair (complex) convention — DeepSeek's decoupled rope
    parts rotate (x[2i], x[2i+1]) pairs (transformers
    modeling_deepseek_v2.apply_rotary_emb via view_as_complex), NOT
    rotate_half. x: [B, T, H, d], sin/cos: [B, T, d/2]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    sin = sin[:, :, None, :].astype(x.dtype)
    cos = cos[:, :, None, :].astype(x.dtype)
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape)


def _attend(
    q: jax.Array,      # [B, T, Hkv, G, hd]
    k: jax.Array,      # [B, S, Hkv, hd]
    v: jax.Array,      # [B, S, Hkv, hd]
    mask: jax.Array,   # [B, T, S] bool (True = attend)
    scale: float,
    softcap: float = 0.0,
    sinks: Optional[jax.Array] = None,   # [Hkv, G] learned sink logits
) -> jax.Array:
    """Grouped-query attention; fp32 softmax; returns [B, T, Hkv*G*hd].

    ``sinks`` (GPT-OSS, modeling_gpt_oss eager_attention_forward): a
    per-head learned logit joins the softmax DENOMINATOR only — the
    probability mass it absorbs is dropped, softening every real score
    without a corresponding value row."""
    scores = jnp.einsum("bthgd,bshd->bhgts", q, k).astype(jnp.float32) * scale
    if softcap:
        # gemma2 attention-logit softcapping, applied before the mask
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    if sinks is not None:
        sink = sinks.astype(jnp.float32)[None, :, :, None]  # [1,Hkv,G,1]
        m = jnp.maximum(jnp.max(scores, axis=-1), sink)     # [B,Hkv,G,T]
        p = jnp.exp(scores - m[..., None])
        denom = jnp.sum(p, axis=-1) + jnp.exp(sink - m)
        weights = (p / denom[..., None]).astype(q.dtype)
    else:
        weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", weights, v)
    b, t = out.shape[0], out.shape[1]
    return out.reshape(b, t, -1)


def _flash_prefill(mesh, attn_impl):
    """The flash prefill kernel, to call with ``(q [B, T, H, hd], k, v,
    scale, q_offset=, [window=])``: on a mesh of several devices a shard
    of heads a device. Handed back and not called here, and on one
    device without the wrapper that shards: a Python frame between a
    program's ``jit`` and the trace of a kernel's body costs that trace
    some 50 ms (0.3 s of a start a prefill program: PERF.md, PR 52)."""
    from gpustack_tpu.ops.flash_attention import (
        flash_attention_prefill,
        sharded_flash_attention_prefill,
    )

    flash = (
        flash_attention_prefill if mesh is None or mesh.size == 1
        else partial(sharded_flash_attention_prefill, mesh)
    )
    return partial(flash, interpret=attn_impl == "flash_interpret")


def _mla_over_own_rows(
    q,                   # [B, T, H, nope + rope], the rope part not rotated
    c_kv, k_pe,          # [B, T, rank], normed; [B, T, 1, rope], rotated
    wk_b, wv_b,          # [rank, H * nope], [rank, H * vd]
    sin, cos,            # [B, T, rope / 2]: the positions' rotation
    mask, scale, mesh, attn_impl, q_offset,
) -> jax.Array:
    """A latent's decompressed attention over the step's own rows, every
    key there is: ``[B, T, H * vd]``. ``k_nope`` and ``v`` are made per
    head from ``c_kv`` inside the program, compute-bound.

    On one device the flash form is a call of the latent's own
    (``ops/mla_attention.py mla_prefill_attention``): it reads the query,
    ``k_nope`` and ``v`` as the projections make them, a head a block of
    their columns, rotates the query's rope part itself and takes the one
    rope key for all heads, and writes its result the same way, so no key
    of ``nope + rope`` a head is built and nothing is relaid round it
    (4.2 ms a layer of A.X-K1's 8,192 prefill until PR 62: PERF.md).
    Widths that are no whole lane tiles, a mesh of several devices and the
    XLA form take the keys built out to ``[B, T, H, nope + rope]``."""
    B, T, H, _ = q.shape
    rope_d = k_pe.shape[3]
    k_nope = _mm("btr,rq->btq", c_kv, wk_b)
    v = _mm("btr,rq->btq", c_kv, wv_b)
    nope, vd = k_nope.shape[2] // H, v.shape[2] // H
    if attn_impl != "xla" and (mesh is None or mesh.size == 1):
        from gpustack_tpu.ops.mla_attention import (
            mla_prefill_attention,
            mla_prefill_takes,
        )

        if mla_prefill_takes(H, nope, rope_d, vd):
            return mla_prefill_attention(
                q.reshape(B, T, -1), k_nope, k_pe[:, :, 0], v, sin, cos,
                scale, interpret=attn_impl == "flash_interpret",
            )
    k = jnp.concatenate(
        [
            k_nope.reshape(B, T, H, nope),
            jnp.broadcast_to(k_pe, (B, T, H, rope_d)),
        ],
        axis=-1,
    )
    q = jnp.concatenate(
        [q[..., :nope], apply_rope_interleaved(q[..., nope:], sin, cos)],
        axis=-1,
    )
    v = v.reshape(B, T, H, vd)
    if attn_impl == "xla":
        return _attend(q[:, :, :, None, :], k, v, mask, scale)
    return _flash_prefill(mesh, attn_impl)(q, k, v, scale, q_offset=q_offset)


def attend_over_cache(
    q, k, v,             # the step's [B, T, heads, hd], normed and rotated
    buf_k, buf_v,        # [L, B, S, Hkv, hd]: the layer's store in the cache
    index, start,        # the layer's place in it; [B] where a row's keys go
    *, positions, mask, scale, decode_attn_impl, walk=None,
    attn_impl="xla", mesh=None, softcap=0.0, sinks=None, name=None,
    block=0,
):
    """One GQA layer over its store in a cache, from where the families
    agree: the step's rows are written at ``start``, then the step
    attends: ``(attn [B, T, H * hd], buf_k, buf_v)``. By the decode
    kernel over the store where it lies as far as ``walk`` says
    (``decode_attn_impl`` not ``"xla"``; ``name``: the call's in a
    trace; the ``T`` rows of a diffusion block, ``block``, see one key
    set, every row below ``start + T``, and go in as ``T x G`` query
    rows of their kv head: :func:`block_rows_as_heads`; such a block
    that is whole stored tiles of the view the kernel reads is written
    over them by ``ops/cache_write.py``'s call, which floors a start
    that is no multiple of ``T``, a dead slot's, to its block; every
    other step's rows by :func:`_write_rows`, which says why), else over the
    layer's rows by ``attn_impl``: ``"ring"``
    (``sp``: a cache sharded over its positions), the flash kernel for
    several rows a slot over a cache that holds them, or ``_attend``
    under ``mask [B, T, S]``. Projection, biases, norms and rotation are
    the caller's.

    ``q`` has its heads flat or grouped by kv head, as its caller's
    family left them: each path reshapes to what it takes, a no-op where
    the caller had it so. The decode kernel's ``[B, H, hd]`` has two
    spellings, one value and two lowered texts, and each caller's
    serving program is held to the text it had
    (``tests/ops/lowered_programs.py``; ROADMAP C13)."""
    B, T, Hkv, hd = k.shape
    S = buf_k.shape[2]
    ring = attn_impl == "ring"
    write = partial(
        _write_rows, layer=index, start=start, by_position=ring and T > 1
    )
    # heads narrower than a lane tile lie ``side`` to a stored row
    # (``ModelConfig.kv_heads_a_row``): the same bytes in the same order
    side = buf_k.shape[-1] // hd
    if side > 1:
        k, v = (a.reshape(B, T, Hkv // side, side * hd) for a in (k, v))
    whole_tiles = False
    if block and T == block and decode_attn_impl != "xla":
        from gpustack_tpu.ops import cache_write

        whole_tiles = cache_write.a_block_is_whole_tiles(buf_k, T)
    if whole_tiles:
        # a diffusion block's rows are a stored tile of the view the
        # kernel below reads: written over it, keys and values in a call
        buf_k, buf_v = cache_write.gqa_write_block_rows(
            buf_k, buf_v, k, v, index, start,
            interpret=decode_attn_impl == "kernel_interpret",
        )
    else:
        buf_k, buf_v = write(buf_k, k), write(buf_v, v)
    if decode_attn_impl != "xla":
        # a decode step on one chip: the kernel reads the layer's rows
        # where they lie and no slab is taken out of the carry
        from gpustack_tpu.ops.decode_attention import gqa_decode_attention

        if block:
            q = block_rows_as_heads(q)
        else:
            q = q[:, 0] if q.ndim == 4 else q.reshape(B, -1, hd)
        if side > 1:
            q, own = queries_for_rows_of(side, q, Hkv)
        attn = gqa_decode_attention(
            q, buf_k, buf_v, index, walk, scale,
            interpret=decode_attn_impl == "kernel_interpret",
            **({"name": name} if name else {}),
        )[:, None]
        if block:
            attn = block_rows_as_heads(attn[:, 0], back=(T, Hkv))
        if side > 1:
            # of a stored row's values a head keeps its own head's
            attn = jnp.sum(
                jnp.where(own, attn.reshape(B, 1, -1, side, hd), 0), axis=3
            ).reshape(B, 1, -1)
        return attn, buf_k, buf_v
    all_k, all_v = (
        lax.dynamic_index_in_dim(buf, index, 0, keepdims=False)
        .reshape(B, S, Hkv, hd)
        for buf in (buf_k, buf_v)
    )
    grouped = q.reshape(B, T, Hkv, -1, hd)
    if ring:
        from gpustack_tpu.ops.ring_attention import (
            sharded_prefill_attention,
            sp_cache_attention,
        )

        if T > 1 and S == T:
            # from position 0: the step's rows are the whole cache
            attn = sharded_prefill_attention(
                mesh, grouped, k, v, positions, scale
            )
        else:
            # decode / verify: exact over the sp-sharded resident cache
            attn = sp_cache_attention(
                mesh, grouped, all_k, all_v, positions, scale
            )
    elif attn_impl in ("flash", "flash_interpret") and T > 1 and S >= T:
        # from zero or from a chunk's or prefix's offset, against the
        # freshly written cache: pad keys are masked via seq_k, rows
        # above the last query's position are causally invisible
        attn = _flash_prefill(mesh, attn_impl)(
            q.reshape(B, T, -1, hd), all_k, all_v, scale,
            q_offset=positions[0, 0], **({"block": block} if block else {}),
        )
    else:
        attn = _attend(
            grouped, all_k, all_v, mask, scale, softcap, sinks=sinks
        )
    return attn, buf_k, buf_v


def block_rows_as_heads(x: jax.Array, back=None) -> jax.Array:
    """A diffusion block's query rows as the decode kernel takes them:
    ``[B, T, Hkv, G, hd]`` to ``[B, Hkv * T * G, hd]``, a kv head's ``T x
    G`` rows together (the kernel's query head ``h`` is of kv head ``h //
    (Hq / Hkv)``); with ``back = (T, Hkv)`` the kernel's ``[B, Hq * hd]``
    to the layer's ``[B, T, Hkv * G * hd]``."""
    B = x.shape[0]
    if back is None:
        return x.transpose(0, 2, 1, 3, 4).reshape(B, -1, x.shape[-1])
    T, Hkv = back
    return x.reshape(B, Hkv, T, -1).transpose(0, 2, 1, 3).reshape(B, T, -1)


def queries_for_rows_of(side: int, q: jax.Array, kv_heads: int):
    """``q [B, Hq, hd]`` as the decode kernel takes it over a cache whose
    stored row holds ``side`` kv heads on its lanes: ``([B, Hq, side *
    hd], own)``, a query head's numbers on its own kv head's lanes and
    zeros on the others', so that its product with a stored row is its
    product with its own head's key; ``own`` (bool ``[Hq, side, 1]``)
    says which lanes those are, for the result's values."""
    Hq = q.shape[1]
    at = (np.arange(Hq) // (Hq // kv_heads)) % side     # [Hq]
    own = jnp.asarray(at[:, None, None] == np.arange(side)[None, :, None])
    wide = jnp.where(own, q[:, :, None, :], jnp.zeros((), q.dtype))
    return wide.reshape(q.shape[0], Hq, -1), own


def _kept_groups(sel: jax.Array, cfg: ModelConfig) -> jax.Array:
    """``sel`` with the experts outside the token's ``topk_group`` best
    groups set to 0, as the family's public ports do before the top-k
    (``masked_fill(~score_mask, 0.0)``). A group scores the sum of its
    two best experts under sigmoid scoring (DeepSeek-V3 ``noaux_tc``)
    and its best expert under softmax scoring (DeepSeek-V2
    ``group_limited_greedy``); ``lax.top_k`` breaks ties towards the
    lower index, among groups as among experts."""
    lead, E = sel.shape[:-1], sel.shape[-1]
    grouped = sel.reshape(*lead, cfg.n_group, E // cfg.n_group)
    if cfg.moe_scoring == "sigmoid":
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    else:
        group_score = jnp.max(grouped, axis=-1)
    _, kept = lax.top_k(group_score, cfg.topk_group)
    keep = jnp.any(
        jax.nn.one_hot(kept, cfg.n_group, dtype=jnp.bool_), axis=-2
    )
    return jnp.where(keep[..., None], grouped, 0.0).reshape(sel.shape)


def _route(
    x: jax.Array,           # [B, T, D]
    router_w: jax.Array,    # [D, E]
    cfg: ModelConfig,
    router_bias=None,
) -> Tuple[jax.Array, jax.Array]:
    """Each token's ``num_experts_per_tok`` experts and their combine
    weights: ``(top_idx int32 [B, T, k], top_w float32 [B, T, k])``,
    over all of the router's ``E`` experts, whichever of them this
    replica holds."""
    # Router math in fp32: top-k selection must not flip on bf16 rounding
    # (which differs between sharded and unsharded contraction orders).
    logits = jnp.einsum(
        "btd,de->bte",
        x.astype(jnp.float32),
        router_w.astype(jnp.float32),
    )
    if cfg.moe_scoring == "sigmoid":
        # DeepSeek-V3: sigmoid scores; SELECTION adds the learned
        # correction bias, the combine WEIGHTS use the raw scores
        scores = jax.nn.sigmoid(logits)
        sel = scores + (router_bias if router_bias is not None else 0.0)
        if cfg.n_group > 1:
            sel = _kept_groups(sel, cfg)
        _, top_idx = lax.top_k(sel, cfg.num_experts_per_tok)
        top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
    elif cfg.moe_scoring == "softmax_topk":
        # GPT-OSS (modeling_gpt_oss GptOssTopKRouter): the router is a
        # true affine map; softmax runs over the SELECTED top-k logits,
        # not the full expert set
        if router_bias is not None:
            logits = logits + router_bias.astype(jnp.float32)
        top_v, top_idx = lax.top_k(logits, cfg.num_experts_per_tok)
        top_w = jax.nn.softmax(top_v, axis=-1)
    elif cfg.n_group > 1:
        gates = jax.nn.softmax(logits, axis=-1)
        _, top_idx = lax.top_k(
            _kept_groups(gates, cfg), cfg.num_experts_per_tok
        )
        top_w = jnp.take_along_axis(gates, top_idx, axis=-1)
    else:
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_idx = lax.top_k(gates, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob and cfg.moe_scoring != "softmax_topk":
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_idx, top_w


def _expert_act(g, u: jax.Array, cfg: ModelConfig) -> jax.Array:
    """What goes into an expert's down matrix. The gated forms take the
    gate's product ``g`` and the up matrix's ``u``; the plain form
    (``"relu2"``, two matrices an expert) has no gate: ``g`` is None."""
    if cfg.moe_act == "relu2":
        return jnp.square(jax.nn.relu(u))
    if cfg.moe_act == "gptoss":
        # GptOssExperts: clamped glu — gate capped above, up clamped
        # both ways, (up + 1) multiplies gate*sigmoid(1.702*gate)
        limit = 7.0
        g = jnp.clip(g, None, limit)
        u = jnp.clip(u, -limit, limit)
        return (u + 1.0) * (g * jax.nn.sigmoid(1.702 * g))
    return jax.nn.silu(g) * u


def _experts_dense(x, top_idx, top_w, we_gate, we_up, we_down, cfg, biases):
    """Every expert over every token; the top-k weights, zero elsewhere,
    combine the results. ``E / k`` times the products the router asked
    for, and no token moves: what GSPMD partitions over ``ep`` (each
    device its own experts for all tokens, the combine a psum), what
    differentiates, and what every platform runs. It reads every held
    expert's weights whatever was routed: a step of few rows (verify,
    ingest, a decode step where no kernel is to be had) pays for all of
    them, a full prefill for ``E / k`` times its products. Under a share
    (``cfg.experts_held``) the one-hot is over the held ids only: a pair
    on an absent expert matches none of them and adds nothing."""
    held_idx = (
        top_idx - cfg.first_held_expert if cfg.first_held_expert else top_idx
    )
    combine = jnp.sum(
        jax.nn.one_hot(held_idx, cfg.num_held_experts, dtype=jnp.float32)
        * top_w[..., None],
        axis=-2,
    ).astype(x.dtype)
    g = None if we_gate is None else _mm("btd,edf->btef", x, we_gate)
    u = _mm("btd,edf->btef", x, we_up)
    if biases is not None:
        bg, bu, _bd = biases
        g = g + bg[None, None].astype(g.dtype)
        u = u + bu[None, None].astype(u.dtype)
    y = _mm("btef,efd->bted", _expert_act(g, u, cfg), we_down)
    if biases is not None:
        _bg, _bu, bd = biases
        y = y + bd[None, None].astype(y.dtype)
    return jnp.einsum("bted,bte->btd", y, combine)


def _grouped_products(xs, rows, we_gate, we_up, we_down, cfg, biases,
                      interpret, layer):
    """The three expert products over rows laid out by ``group_rows``:
    ``[M, D] -> [M, D]``, each row against its tile's expert."""
    from gpustack_tpu.ops.grouped_matmul import BLOCK_M, grouped_matmul

    def mm(a, w, bias):
        q, s = (w.q, w.s) if isinstance(w, QuantW) else (w, None)
        out = grouped_matmul(
            a, q, rows.tile_group, rows.n_active, s, layer,
            interpret=interpret,
        )
        if bias is not None:
            expert_of_row = jnp.repeat(rows.tile_group, BLOCK_M)
            out = out + jnp.take(bias, expert_of_row, axis=0).astype(
                out.dtype
            )
        return out

    bg, bu, bd = biases if biases is not None else (None, None, None)
    g = None if we_gate is None else mm(xs, we_gate, bg)
    return mm(_expert_act(g, mm(xs, we_up, bu), cfg), we_down, bd)


def _experts_grouped(
    x, top_idx, top_w, we_gate, we_up, we_down, cfg, biases, interpret,
    layer=None,
):
    """Only the (token, expert) pairs the router chose, sorted by expert
    (``ops/grouped_matmul.py``): the same three products as the dense
    formulation, bf16 operands and float32 accumulation, each pair's row
    against its own expert. A token's ``k`` results are weighed in
    float32 and summed in the router's order, so the same input gives
    the same bits. Every pair is computed, whatever the skew. With
    ``layer`` the three weights are all the layers', as stored, and the
    kernel reads that layer's blocks (``grouped_matmul``)."""
    from gpustack_tpu.ops.grouped_matmul import group_rows

    B, T, D = x.shape
    k = cfg.num_experts_per_tok
    rows = group_rows(
        top_idx.reshape(-1).astype(jnp.int32), cfg.num_experts
    )
    # a padding row computes the last token against its tile's expert;
    # nothing reads the result
    xs = jnp.take(x.reshape(B * T, D), rows.src // k, axis=0, mode="clip")
    y = jnp.take(
        _grouped_products(
            xs, rows, we_gate, we_up, we_down, cfg, biases, interpret, layer
        ),
        rows.dest, axis=0, mode="clip",
    )
    out = jnp.sum(
        y.reshape(B * T, k, D).astype(jnp.float32)
        * top_w.reshape(B * T, k, 1),
        axis=1,
    )
    return out.astype(x.dtype).reshape(B, T, D)


def held_capacity(pairs: int, cfg: ModelConfig) -> int:
    """Pairs one round of :func:`_experts_grouped_held` takes: twice what
    an even router sends the held experts, in whole tiles. The work is
    sized for what a share is expected to receive, not for the worst
    routing (every pair on a held expert), whose rows would be sixteen
    times the expected at 12 of 192."""
    from gpustack_tpu.ops.grouped_matmul import BLOCK_M

    even = pairs * cfg.num_held_experts / cfg.num_experts
    return min(pairs, max(1, -(-int(2 * even) // BLOCK_M)) * BLOCK_M)


def _experts_grouped_held(
    x, top_idx, top_w, we_gate, we_up, we_down, cfg, biases, interpret,
    layer=None,
):
    """:func:`_experts_grouped` under a share (``cfg.experts_held``): the
    pairs on absent experts are dropped before the sort, and the held
    ones, in the router's order, are taken ``held_capacity`` at a time,
    as many rounds as the routing needs (one where it is at most twice
    as heavy on the held experts as an even one): every held pair is
    computed, whatever the skew, and nothing is sized for the worst. A
    round lays its pairs out by expert, runs the three products, and
    adds each token's results, weighed in float32, in the router's
    order."""
    from gpustack_tpu.ops.grouped_matmul import group_rows

    B, T, D = x.shape
    k, Eh = cfg.num_experts_per_tok, cfg.num_held_experts
    R, P = B * T, B * T * k
    C = held_capacity(P, cfg)
    x2 = x.reshape(R, D)
    expert = top_idx.reshape(P).astype(jnp.int32) - cfg.first_held_expert
    held = (expert >= 0) & (expert < Eh)
    # a held pair's place among the held pairs, in the router's order
    place = jnp.cumsum(held, dtype=jnp.int32) - 1
    n_held = place[-1] + 1
    # the pairs with the held ones first, each kind in its own order
    _, order = lax.sort_key_val(
        jnp.logical_not(held).astype(jnp.int32),
        jnp.arange(P, dtype=jnp.int32),
    )
    order = jnp.concatenate([order, jnp.full((C,), P - 1, jnp.int32)])
    place = jnp.where(held, place, -1).reshape(R, k)
    w = top_w.reshape(R, k).astype(jnp.float32)

    def one_round(state):
        r, out = state
        first = r * C
        pair = lax.dynamic_slice(order, (first,), (C,))
        live = first + jnp.arange(C, dtype=jnp.int32) < n_held
        # a slot past the last held pair joins no expert's group: it
        # sorts behind them all and no tile of it is computed
        rows = group_rows(
            jnp.where(live, jnp.take(expert, pair), Eh), Eh
        )
        token = jnp.concatenate([pair // k, jnp.full((1,), R - 1, jnp.int32)])
        xs = jnp.take(x2, jnp.take(token, rows.src), axis=0, mode="clip")
        y = jnp.take(
            _grouped_products(
                xs, rows, we_gate, we_up, we_down, cfg, biases, interpret,
                layer,
            ),
            rows.dest, axis=0, mode="clip",
        )
        # rows of tiles that were not computed hold whatever was there
        y = jnp.where(live[:, None], y, jnp.zeros_like(y))
        for j in range(k):
            at = place[:, j] - first
            mine = (at >= 0) & (at < C)
            got = jnp.take(y, jnp.clip(at, 0, C - 1), axis=0)
            out = out + jnp.where(
                mine[:, None], got.astype(jnp.float32) * w[:, j:j + 1], 0.0
            )
        return r + 1, out

    _, out = lax.while_loop(
        lambda state: state[0] * C < n_held,
        one_round,
        (jnp.int32(0), jnp.zeros((R, D), jnp.float32)),
    )
    return out.astype(x.dtype).reshape(B, T, D)


def _experts_touched(
    x, top_idx, top_w, we_gate, we_up, we_down, cfg, live, interpret,
    layer=None,
):
    """A step's rows over the cache (one a slot, or a diffusion block's
    ``T`` a slot: ``B * T`` rows) against the held experts its live rows
    chose and no others (``ops/grouped_matmul.py touched_experts``):
    ``(out [B, T, D], experts read int32)``.

    The rows are one tile, so nothing is laid out: every touched
    expert meets all of them, and ``combine [B, E_held]`` (float32, as
    the grouped prefill weighs) is zero where a row did not choose the
    expert, where the expert is not held, and all along a row nobody
    holds (``live``). The touched ids, ascending, come from compares
    against the held ids and one sort of ``E_held`` keys. What is read is
    ``touched / held`` of the dense products' bytes at any routing, so
    nothing has to choose between the two at run time."""
    from gpustack_tpu.ops.grouped_matmul import touched_experts

    B, T, D = x.shape
    if not steps_over_cache(cfg, T):
        raise ValueError(
            f"dispatch 'touched' is a step's over the cache: T={T}"
        )
    if T > 1:
        # a block's rows as so many slots' (a reshape of nothing at T=1,
        # and no operation of a decode program's text)
        x, top_idx, top_w = (
            a.reshape(B * T, 1, -1) for a in (x, top_idx, top_w)
        )
        live = None if live is None else jnp.repeat(live, T)
    Eh = cfg.num_held_experts
    held = jnp.arange(Eh, dtype=jnp.int32)
    chosen = (
        top_idx[:, 0].astype(jnp.int32) - cfg.first_held_expert
    )[..., None] == held                                   # [B, k, Eh]
    if live is not None:
        chosen = chosen & live[:, None, None]
    combine = jnp.sum(
        jnp.where(chosen, top_w[:, 0, :, None].astype(jnp.float32), 0.0),
        axis=1,
    )
    touched = jnp.any(chosen, axis=(0, 1))
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    # the touched ids first, ascending; the rest name the last expert
    ids = lax.sort(jnp.where(touched, held, Eh - 1))
    ids = ids[: min(Eh, B * T * cfg.num_experts_per_tok)]
    # the plain form (cfg.moe_act "relu2") has no gate: None all along
    weights, scales = zip(*(
        (w.q, w.s) if isinstance(w, QuantW) else (w, None)
        for w in (we_gate, we_up, we_down)
    ))
    out = touched_experts(
        x[:, 0], combine, ids, n_touched[None], *weights,
        scales if scales[1] is not None else None, layer,
        interpret=interpret,
    )
    out = out.astype(x.dtype)
    return (out[:, None] if T == 1 else out.reshape(B, T, D)), n_touched


def _moe_mlp(
    x: jax.Array,           # [B, T, D]
    router_w: jax.Array,    # [D, E]
    we_gate,                # [E, D, Fm]; None for the plain form (relu2)
    we_up: jax.Array,       # [E, D, Fm]
    we_down: jax.Array,     # [E, Fm, D]
    cfg: ModelConfig,
    router_bias=None,       # [E] sigmoid-selection bias (DeepSeek-V3)
                            # or logit bias (GPT-OSS softmax_topk)
    shared=None,            # (ws_gate, ws_up, ws_down, gate_w|None)
    biases=None,            # (bg [E,Fm], bu [E,Fm], bd [E,D]) GPT-OSS
    dispatch: str = "dense",
    layer=None,             # not dense: we_* are [L, E, ...], this layer's
    count_held: bool = False,
    routing_out: bool = False,
    live=None,              # bool [B]: the slots somebody holds
    count_read: bool = False,
):
    """Top-k mixture of experts: one router, one set of products, three
    ways to enumerate them (:func:`moe_dispatch` chooses).

    ``"dense"`` runs every expert over every token and zeroes all but the
    chosen ``k`` in the combine; ``"grouped"`` sorts the chosen pairs by
    expert and runs only those; ``"touched"`` runs a decode step's rows
    against the experts its live rows chose, each read once
    (``"grouped_interpret"``, ``"touched_interpret"``: the same kernels
    in interpret mode, for hermetic CPU tests). On the chip a 2,048-token
    prefill at 128 experts, 8 a token, spent 190 of its 210 ms in the
    dense products, 15/16 of them multiplied by zero (PERF.md section 6,
    PR 34), and a decode step of 8 live slots of 32 spent 9.3 of its
    14.8 ms reading all 128 experts where 52 were chosen (PR 43). Dense
    stays for sharded replicas, other platforms, whoever differentiates,
    and the steps of a few rows a slot (:func:`_experts_dense`).

    ``live`` takes a dead slot's row out of the routing under
    ``"touched"``: it touches no expert and its routed part is zero (its
    logits mean nothing either way: ``forward``). With ``count_read``
    one more value is returned: the held experts whose weights the layer
    read (int32; all of them but under ``"touched"``).
    """
    top_idx, top_w = _route(x, router_w, cfg, router_bias)
    n_read = jnp.int32(cfg.num_held_experts)
    if dispatch == "dense":
        out = _experts_dense(
            x, top_idx, top_w, we_gate, we_up, we_down, cfg, biases
        )
    elif dispatch.startswith("touched"):
        if biases is not None or cfg.moe_act not in ("silu", "relu2"):
            raise ValueError(
                "dispatch 'touched' takes no expert biases and no "
                f"activation but silu or relu2 (moe_act={cfg.moe_act!r})"
            )
        out, n_read = _experts_touched(
            x, top_idx, top_w, we_gate, we_up, we_down, cfg, live,
            interpret=dispatch == "touched_interpret", layer=layer,
        )
    else:
        grouped = (
            _experts_grouped_held if cfg.experts_held else _experts_grouped
        )
        out = grouped(
            x, top_idx, top_w, we_gate, we_up, we_down, cfg, biases,
            interpret=dispatch == "grouped_interpret", layer=layer,
        )
    if cfg.routed_scaling_factor != 1.0:
        out = out * jnp.asarray(
            cfg.routed_scaling_factor, out.dtype
        )
    if shared is not None:
        # Shared experts: a dense MLP every token passes through, added
        # to the routed output — ungated (DeepSeek) or gated by
        # sigmoid(x @ g) (Qwen2-MoE)
        ws_gate, ws_up, ws_down, gate_w = shared
        if ws_gate is None:
            # the plain form's shared expert has two matrices as well
            sh = jnp.square(jax.nn.relu(_mm("btd,df->btf", x, ws_up)))
        else:
            sg = _mm("btd,df->btf", x, ws_gate)
            su = _mm("btd,df->btf", x, ws_up)
            sh = jax.nn.silu(sg) * su
        shared_out = _mm("btf,fd->btd", sh, ws_down)
        if gate_w is not None:
            shared_out = shared_out * jax.nn.sigmoid(
                _mm("btd,dg->btg", x, gate_w)
            )
        if cfg.shared_expert_average:
            # the n shared experts are the one wide MLP's columns side
            # by side, so its output is their sum: the mean is 1/n of it
            shared_out = shared_out * jnp.asarray(
                1.0 / cfg.n_shared_experts, shared_out.dtype
            )
        out = out + shared_out
    extras = ()
    if count_held:
        at = top_idx - cfg.first_held_expert
        extras += (jnp.sum(
            (at >= 0) & (at < cfg.num_held_experts), dtype=jnp.int32
        ),)
    if count_read:
        extras += (n_read,)
    if routing_out:
        # the router's logits as _route computes them (the compiler
        # merges the two), for a comparison with a reference
        extras += ((top_idx, jnp.einsum(
            "btd,de->bte", x.astype(jnp.float32),
            router_w.astype(jnp.float32),
        )),)
    return (out, *extras) if extras else out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def needs_xla_attention(cfg: ModelConfig) -> bool:
    """True for a model whose scores only the XLA einsum path computes.
    The blocked kernels know a causal mask and a band under it (the
    flash kernel's ``window``; a decode step over a ring of window rows
    is the decode kernel over a short cache), so a plain window no
    longer counts where its rows are kept at window size
    (``cfg.window_rows``). A logit softcap or attention sinks still
    rule the kernels out, and so does a window that is a mask over
    ``S_max`` rows a slot (the Gemma / GPT-OSS / Mistral files): the
    decode kernel walks a slot from its first row, and the ring kernel
    (``sp``) knows no band."""
    return bool(
        (cfg.sliding_window and not cfg.window_rows)
        or cfg.attn_logit_softcap or cfg.attn_sinks
    )


# Grouped dispatch where the router's (token, expert) pairs come to at
# least this many an expert on the average. Below it the rows do not
# fill the groups: every group still costs a tile of 128 rows and the
# read of its weights, and the dense products, which read every expert's
# weights once too, are as fast or faster (PERF.md section 6, PR 34: the
# sweep on the chip over rows 32-2,048 at 128 experts, 8 a token, crosses
# between 128 and 256 rows, 8 and 16 pairs an expert).
GROUPED_MIN_FILL = 16


def moe_dispatch(
    rows: int, cfg: ModelConfig, platform: str, mesh, decode: bool = False
) -> str:
    """How a program of ``rows`` tokens enumerates its experts' products
    (``_moe_mlp``): the one place that decides, from what ``forward`` can
    observe. ``decode``: the rows are a step's over a cache
    (:func:`steps_over_cache`: one a slot, or a diffusion block's ``L``
    a slot).

    On one TPU chip: ``"touched"`` for a decode step, and for a
    diffusion model's block pass, of a model the kernel takes (no expert
    biases; silu, or the plain relu2): it reads the experts the live
    rows chose, at most what the dense form reads, so no row count
    chooses between them (at 32 slots a block pass is 128 rows, 8 pairs
    an expert on the average: under ``GROUPED_MIN_FILL``, and the dense
    form would compute 128 rows x 128 experts a layer); ``"grouped"``
    where the rows fill the groups. ``"dense"`` otherwise: a verify,
    ingest, chunked or prefix step's few rows a slot; a mesh of more
    than one device (the
    dense einsum is what GSPMD partitions over ``ep`` / ``tp`` /
    ``fsdp``, and the kernels are not wrapped in a ``shard_map``); any
    other platform (the compiled kernels exist only for the TPU, as the
    flash kernel's).
    """
    one_chip = platform == "tpu" and (mesh is None or mesh.size == 1)
    if not one_chip:
        return "dense"
    if decode:
        taken = cfg.moe_act in ("silu", "relu2") and not cfg.moe_bias
        return "touched" if taken else "dense"
    # pairs an expert, held or not, on the average: under a share the
    # held experts get their part of the pairs, not all of them
    fills = (
        rows * cfg.num_experts_per_tok >= GROUPED_MIN_FILL * cfg.num_experts
    )
    return "grouped" if fills else "dense"


def decode_attention_impl(
    cfg: ModelConfig, rows: int, max_len: int, platform: str, mesh
) -> str:
    """How a step of ``rows`` tokens a slot attends over cached rows,
    whichever cache the model keeps (a GQA cache, or MLA's latent in its
    absorbed form): the one place that decides, from what ``forward``
    can observe.

    ``"kernel"`` (``ops/decode_attention.py``, ``ops/mla_attention.py``)
    for a decode step on one TPU chip whose cache divides into the
    kernel's blocks: one token a slot, each block of cached positions
    read where it lies, once for all heads and only as far as the
    slot's length; and for a diffusion model's block pass
    (``cfg.diffusion_block`` rows a slot: they see one key set, every
    row below the block's end, and go in as so many more query rows of
    their kv head). ``"xla"`` otherwise: a verify step, an ingest or a
    continuation (several rows a slot), a mesh of more than one device
    (the kernels are not wrapped in a ``shard_map``), any other
    platform, and for a GQA cache a model whose scores only the einsum
    computes (:func:`needs_xla_attention`) or whose stored rows are no
    whole number of lane tiles wide (``ModelConfig.kv_row_shapes``: a
    head of 128 or a multiple, or narrower heads stored side by side on
    a row, ``kv_heads_a_row``; a head of 64 stored alone, 96, 80 take
    the XLA form).
    """
    from gpustack_tpu.ops.decode_attention import (
        block_positions,
        gqa_block_positions,
    )

    if cfg.is_mla:
        block = block_positions(max_len)
    elif needs_xla_attention(cfg):
        block = None
    else:
        itemsize = 2 if cfg.dtype == "bfloat16" else 4
        heads, width = cfg.kv_row_shapes[0]     # of a row as it is stored
        block = gqa_block_positions(max_len, heads, width, itemsize)
        if cfg.window_rows and gqa_block_positions(
            min(cfg.sliding_window, max_len), heads, width, itemsize,
        ) is None:
            # the ring of a sliding layer is walked by the same kernel
            block = None
    one_chip = platform == "tpu" and (mesh is None or mesh.size == 1)
    a_step = steps_over_cache(cfg, rows)
    return "kernel" if one_chip and a_step and block is not None else "xla"


def steps_over_cache(cfg: ModelConfig, rows: int) -> bool:
    """Whether ``rows`` tokens a slot over a cache are the model's own
    step, the one its decode kernels serve: one row, or a diffusion
    block's (``cfg.diffusion_block``; 0, and never a row count, for any
    other model)."""
    return rows == 1 or 1 < rows == cfg.diffusion_block


def heads_of_zeros_behind(n: int, *arrays: jax.Array):
    """Each of ``[B, T, heads, ...]`` with ``n`` heads of zeros behind its
    own (``ModelConfig.kv_heads_stored``)."""
    return tuple(
        jnp.pad(a, ((0, 0), (0, 0), (0, n)) + ((0, 0),) * (a.ndim - 3))
        for a in arrays
    )


@dataclasses.dataclass(frozen=True, eq=False)
class Step:
    """What one call of ``forward`` works out once and every layer
    reads: :func:`make_step` makes it, the drivers hand it to the layer
    functions, nothing else does either (docs/MODELS.md). A layer of a
    stack with ``layer_sliding`` but no window store reads its own
    ``mask``, ``sin`` and ``cos``, chosen by its flag
    (:func:`_uniform_layer`)."""

    cfg: ModelConfig
    B: int
    T: int
    max_len: Optional[int]      # a slot's rows in the cache; None without one
    positions: jax.Array        # [B, T] int32
    mesh: Any
    # how each thing is computed, decided once a program
    attn_impl: str
    use_flash: bool             # the flash kernel for this step's attention
    decode_attn_impl: Optional[str]
    moe_dispatch_impl: Optional[str]
    ssm_impl: Optional[str]
    scale: float
    # mask[b, t, s], query t attends key s: one for every layer, or with
    # ``layer_sliding`` one each for the full and the sliding layers
    mask: Optional[jax.Array]
    mask_full: Optional[jax.Array]
    mask_slide: Optional[jax.Array]
    # rotation tables [B, T, width / 2] float32 (None: no rope): the
    # main one, the sliding layers' own (gemma3) and the latent's
    sin: Optional[jax.Array]
    cos: Optional[jax.Array]
    sin_loc: Optional[jax.Array]
    cos_loc: Optional[jax.Array]
    mla_sin: Optional[jax.Array]
    mla_cos: Optional[jax.Array]
    # the decode kernel's walk over the slots, and over a ring's live rows
    walk: Any
    walk_w: Any
    live: Optional[jax.Array]       # bool [B]: the slots somebody holds
    true_len: Optional[jax.Array]   # int32 [B]: a prefill's real positions
    # for the mixers that keep a state: which positions count, bool
    # [B, T], and which slots the one-step kernel moves, bool [B]
    real: Optional[jax.Array]
    alive: Optional[jax.Array]
    count_held_pairs: bool
    count_experts_read: bool
    routing_out: bool
    # the routed experts' matrices of every layer, as the grouped and
    # the touched kernels read them; empty under dense dispatch
    stacked: Dict[str, Any]
    kd: int                         # layers of DeepSeek's dense prefix


def _counted(B: int, T: int, true_len, live):
    """``(real, alive)`` of :class:`Step`; all of them where nobody
    says (``true_len``, ``live`` None)."""
    real = (
        jnp.ones((B, T), bool) if true_len is None
        else jnp.arange(T, dtype=jnp.int32)[None, :] < true_len[:, None]
    )
    return real, live if live is not None else jnp.ones((B,), bool)


def make_step(
    params: Params, cfg: ModelConfig, tokens, positions, cache=None,
    attn_impl: str = "xla", mesh=None, embeds_override=None,
    moe_dispatch_impl=None, decode_attn_impl=None, live=None,
    count_held_pairs=False, routing_out=False, count_experts_read=False,
    true_len=None, ssm_impl=None,
) -> Tuple[Step, jax.Array]:
    """``forward``'s arguments to ``(the Step, x [B, T, d] the embedded
    tokens)``: the choices (:func:`moe_dispatch`,
    :func:`decode_attention_impl`, ``models/hybrid.py ssm_update_impl``,
    each called once and only where the caller named none), the
    refusals, then the arrays in the order the lowered programs have
    them: walk, embedding, rotation tables, masks, the stacked experts'
    relayout."""
    B, T = tokens.shape
    over = cache is not None
    sharded = mesh is not None and mesh.size > 1
    ring = attn_impl == "ring"
    platform = (
        mesh.devices.flat[0].platform if mesh is not None
        else jax.default_backend()
    )
    if cfg.is_moe and moe_dispatch_impl is None:
        moe_dispatch_impl = moe_dispatch(
            B * T, cfg, platform, mesh,
            decode=over and steps_over_cache(cfg, T),
        )
    if over and decode_attn_impl is None:
        decode_attn_impl = decode_attention_impl(
            cfg, T, cache.max_len, platform, mesh
        )
    if cfg.state_mixer and ssm_impl is None:
        from gpustack_tpu.models.hybrid import ssm_update_impl

        ssm_impl = ssm_update_impl(T if over else 2, platform, mesh)
    use_flash = (
        attn_impl in ("flash", "flash_interpret") and over and T > 1
        and cache.max_len >= T
    )
    use_ring = ring and over
    if cfg.layer_kinds is not None and embeds_override is not None:
        raise ValueError("a hybrid model takes no embedding override")
    if cfg.state_mixer and over and (ring or sharded):
        raise ValueError(
            f"{cfg.name}: a recurrent state is not sharded; serve it on one "
            "device (a cache sharded over its positions cannot carry one)"
        )
    if (use_flash or use_ring) and needs_xla_attention(cfg):
        raise ValueError(
            f"attn_impl={attn_impl!r} needs no attention softcapping, no "
            "attention sinks and no sliding window but one whose rows "
            "are kept at window size"
        )
    if cfg.window_rows and over:
        if use_ring or sharded:
            raise ValueError(
                f"{cfg.name}: a window store is not sharded; serve it on "
                "one device"
            )
        if T > 1 and cache.max_len != T:
            raise ValueError(
                f"{cfg.name}: {T} rows a slot over a cache of "
                f"{cache.max_len}: a stack that keeps its sliding layers' "
                "rows at window size takes a prefill from position 0 into "
                "a cache of its own length, or one row a slot (a chunk, a "
                "prefix or a draft would need rows the ring has dropped)"
            )
    if use_ring and mesh is None:
        raise ValueError("attn_impl='ring' needs a mesh")
    if use_ring and cfg.is_mla:
        raise ValueError(
            "attn_impl='ring': a cache sharded over its positions cannot "
            "carry a latent (MLA) yet; serve this model with sp=1"
        )

    # ``real`` and ``alive`` stand where each family's programs had
    # them: first under ``layer_types``, last under ``layer_kinds``
    # (one place when Nemotron's hashes are next taken: ROADMAP C17)
    real = alive = None
    if cfg.layer_types is not None:
        real, alive = _counted(B, T, true_len, live)
    walk = walk_w = None
    if over and decode_attn_impl != "xla":
        lengths = positions[:, 0] + T    # a block's rows see its end
        if live is not None:
            lengths = jnp.where(live, lengths, 0)
        # the kernel's walk over the slots, once a step: inside the scan
        # XLA makes it again every layer
        if cfg.is_mla:
            from gpustack_tpu.ops.mla_attention import mla_walk

            walk = mla_walk(lengths, cache.max_len)
        else:
            from gpustack_tpu.ops.decode_attention import gqa_walk

            walk = gqa_walk(lengths, cache.k)
            if cfg.window_rows:
                # a sliding layer walks its ring's live rows
                walk_w = gqa_walk(
                    jnp.minimum(lengths, cache.wk.shape[2]), cache.wk
                )
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = _embed_lookup(params["embed"], tokens, dtype)
    if embeds_override is not None:
        # VLM token splicing: rows flagged by the mask (image
        # placeholders) take projected vision embeddings instead of the
        # vocab row (models/vlm.py build_mm_prompt)
        ov, ov_mask = embeds_override
        x = jnp.where(ov_mask[..., None], ov.astype(dtype), x)
    if cfg.embed_multiplier != 1.0:
        # gemma's sqrt(d), Granite's embedding_multiplier; HF casts the
        # number to the compute dtype before multiplying
        x = x * jnp.asarray(cfg.embed_multiplier).astype(dtype)
    sin = cos = sin_loc = cos_loc = mla_sin = mla_cos = None
    scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)
    if cfg.is_mla:
        # decoupled rope: only the qk_rope part rotates, with its own
        # frequency table (interleaved-pair convention); DeepSeek ships
        # YaRN scaling whose attention factor rides the sin/cos tables
        rs = cfg.rope_scaling or {}
        yarn = rs if (rs.get("rope_type") or rs.get("type")) == "yarn" else {}
        mla_inv, att_factor = (
            yarn_inv_freq(cfg.rope_theta, cfg.qk_rope_head_dim, yarn)
            if yarn else
            (_inv_freq(cfg.rope_theta, cfg.qk_rope_head_dim), 1.0)
        )
        mla_sin, mla_cos = rope_sin_cos(positions, mla_inv)
        if att_factor != 1.0:
            mla_sin, mla_cos = mla_sin * att_factor, mla_cos * att_factor
        if yarn.get("mscale_all_dim"):
            # DeepSeek YaRN applies a SECOND magnitude correction beyond
            # the sin/cos attention_factor: HF/vLLM multiply the softmax
            # scale by yarn_get_mscale(factor, mscale_all_dim)^2
            # (modeling_deepseek_v2 DeepseekV2Attention.__init__). For
            # the shipped V2/V3 configs mscale == mscale_all_dim, so the
            # sin/cos factor is 1.0 and THIS term carries the whole
            # correction (~1.59x for V2-Lite's factor=40,
            # mscale_all_dim=0.707).
            m = yarn_get_mscale(
                float(yarn["factor"]), float(yarn["mscale_all_dim"])
            )
            scale = scale * m * m
    elif cfg.rope:
        main_inv, main_att_factor = rope_params(cfg)
        sin, cos = rope_sin_cos(positions, main_inv)
        if main_att_factor != 1.0:
            # yarn on the standard attention path (Qwen/Llama
            # long-context configs): HF's attention_scaling rides cos/sin
            sin, cos = sin * main_att_factor, cos * main_att_factor
        sin_loc, cos_loc = sin, cos
        if cfg.rope_local_theta:
            # gemma3: sliding layers rotate with a separate, unscaled theta
            sin_loc, cos_loc = rope_sin_cos(
                positions, _inv_freq(cfg.rope_local_theta, cfg.head_dim)
            )

    # causal; over blocks under ``cfg.diffusion_block``, both ways inside
    # one: a query sees as far as the last position of its block (the
    # one place the mask is made; the kernels are told the block's length)
    sees = positions
    if cfg.diffusion_block:
        sees = positions - positions % cfg.diffusion_block + (
            cfg.diffusion_block - 1
        )
    if not over:
        causal = sees[:, :, None] >= positions[:, None, :]
        delta = positions[:, :, None] - positions[:, None, :]
    else:
        cache_pos = jnp.arange(cache.max_len, dtype=jnp.int32)
        causal = cache_pos[None, None, :] <= sees[:, :, None]
        delta = positions[:, :, None] - cache_pos[None, None, :]
    mask = mask_full = mask_slide = None
    if cfg.layer_sliding is not None:
        # gemma-style alternating layers: both masks exist, each layer
        # takes one by its slide flag
        mask_full = causal
        mask_slide = causal & (delta < cfg.sliding_window)
    elif cfg.sliding_window:
        mask = causal & (delta < cfg.sliding_window)
    else:
        mask = causal
    if cfg.layer_kinds is not None:
        real, alive = _counted(B, T, true_len, live)

    # Grouped experts read their layer's blocks out of the stacked
    # weights by the layer's index: as the scan's slices they would be
    # copied whole before every kernel call (``grouped_matmul``).
    stacked = {}
    if cfg.is_moe and moe_dispatch_impl != "dense":
        layers = params.get(
            "layers" if cfg.layer_kinds is None else "moe_layers", {}
        )
        stacked = {
            k: layers[k] for k in ("we_gate", "we_up", "we_down")
            if k in layers
        }
        if moe_dispatch_impl.startswith("touched"):
            # the scales as the kernel's blocks take them, [L, E, 1, N]:
            # a relayout, made here once a step and not once a layer
            stacked = {
                k: QuantW(q=w.q, s=w.s[:, :, None, :])
                if isinstance(w, QuantW) else w
                for k, w in stacked.items()
            }
    kd = (
        len(next(iter(params["dense_layers"].values())))
        if "dense_layers" in params else 0
    )
    return Step(
        cfg=cfg, B=B, T=T, max_len=cache.max_len if over else None,
        positions=positions, mesh=mesh, attn_impl=attn_impl,
        use_flash=use_flash,
        decode_attn_impl=decode_attn_impl,
        moe_dispatch_impl=moe_dispatch_impl, ssm_impl=ssm_impl, scale=scale,
        mask=mask, mask_full=mask_full, mask_slide=mask_slide,
        sin=sin, cos=cos, sin_loc=sin_loc, cos_loc=cos_loc,
        mla_sin=mla_sin, mla_cos=mla_cos, walk=walk, walk_w=walk_w,
        live=live, true_len=true_len, real=real, alive=alive,
        count_held_pairs=count_held_pairs,
        count_experts_read=count_experts_read, routing_out=routing_out,
        stacked=stacked, kd=kd,
    ), x


# ---------------------------------------------------------------------------
# A layer: one function a kind of mixer, and what follows any of them
# ---------------------------------------------------------------------------
#
# A mixer is ``f(h, lp, carried, at, step) -> (out [B, T, d], carried)``:
# the layer's input behind whatever norm stands before it, the layer's
# leaves, the cache as the layers before left it (None without one),
# where the layer's rows or state lie in their store, the Step. Beside
# the three here: ``models/delta.py delta_mixer``, ``models/hybrid.py
# mamba_layer`` and, for its one mixer a layer, ``mamba_mixer``,
# ``experts_layer`` and ``attention_layer``.


def gqa_attention(h, lp, carried, store, step: Step):
    """One layer of grouped-query attention, with whatever of biases,
    q/k norms, rotation, sinks, an output gate and heads of zeros behind
    the stored ones the configuration names; ``store``: the layer's
    index in ``carried.k, .v``."""
    cfg, B, T = step.cfg, step.B, step.T
    q, k, v = qkv_projections(h, lp, decode=carried is not None and T == 1)
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if cfg.qk_norm_whole:
        # over the whole projection, before the heads are split
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        # Qwen3/Gemma3: per-head RMSNorm on q/k BEFORE RoPE
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.norm_delta_gain)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.norm_delta_gain)
    if cfg.rope:
        q = apply_rope(q, step.sin, step.cos)
    q = q.reshape(B, T, cfg.num_kv_heads, cfg.group_size, cfg.head_dim)
    if cfg.rope:
        k = apply_rope(k, step.sin, step.cos)
    unstored = cfg.kv_heads_stored - cfg.num_kv_heads
    if unstored:
        # so that the rows lie in the cache in whole tiles; what the
        # heads that are none attend to is dropped below
        q, k, v = heads_of_zeros_behind(unstored, q, k, v)
    sinks_l = (
        lp["sinks"].reshape(cfg.num_kv_heads, cfg.group_size)
        if cfg.attn_sinks else None
    )
    if carried is None:
        attn = _attend(
            q, k, v, step.mask, step.scale, cfg.attn_logit_softcap,
            sinks=sinks_l,
        )
    else:
        attn, new_k, new_v = attend_over_cache(
            q, k, v, carried.k, carried.v, store, step.positions[:, 0],
            positions=step.positions, mask=step.mask, scale=step.scale,
            decode_attn_impl=step.decode_attn_impl, walk=step.walk,
            attn_impl=step.attn_impl, mesh=step.mesh,
            softcap=cfg.attn_logit_softcap, sinks=sinks_l,
            block=cfg.diffusion_block,
        )
        carried = dataclasses.replace(carried, k=new_k, v=new_v)
    if unstored:
        attn = attn[..., :cfg.q_dim]
    if cfg.attn_output_gate:
        # elementwise over the q_dim channels, from the layer's input,
        # before Wo (arXiv:2505.06708)
        with jax.named_scope("attn_output_gate"):
            gate = _mm("btd,dq->btq", h, lp["wg"])
            attn = (
                attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))
            ).astype(attn.dtype)
    return _mm("btq,qd->btd", attn, lp["wo"]), carried


def mla_attention(h, lp, carried, layer, step: Step):
    """One layer of latent attention (DeepSeek-V2/V3 family) over the
    latent cache, ``layer`` its index there.

    The step's latent rows (``c_kv`` after its norm, the shared rope
    key after its rotation) are written to the cache; nothing wider
    is ever stored. Then one of two forms of the same attention:

    - **decompressed**, where the step's own rows are every key
      there is (no cache, or a prefill from position 0 into a cache
      of the step's length): ``k_nope`` and ``v`` are made per head
      from ``c_kv`` inside the program, compute-bound, and attended
      over as the projections make them (``_mla_over_own_rows``);
    - **absorbed**, over cached rows (decode, verify, a
      continuation): ``W_uk`` goes into the query (``q' = q_nope
      W_uk^T``, 128 -> 512 a head) and ``W_uv`` into the output, and
      all heads attend over the latent as one shared key/value head
      of width 576 / 512, so a cached position is read once for all
      64 heads and never decompressed.
    """
    cfg, B, T = step.cfg, step.B, step.T
    H = cfg.num_heads
    nope = cfg.qk_nope_head_dim
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    if cfg.q_lora_rank:
        q_c = rms_norm(
            _mm("btd,dr->btr", h, lp["wq_a"]),
            lp["q_a_norm"], cfg.rms_norm_eps, False,
        )
        q = _mm("btr,rq->btq", q_c, lp["wq_b"])
    else:
        q = _mm("btd,dq->btq", h, lp["wq"])
    # wq_b meets the same fold as a GQA layer's wq
    (q,) = finish_products(carried is not None and T == 1, q)
    q = q.reshape(B, T, H, cfg.head_dim)
    q_nope = q[..., :nope]
    q_pe = apply_rope_interleaved(q[..., nope:], step.mla_sin, step.mla_cos)
    kv_a = _mm("btd,dr->btr", h, lp["wkv_a"])
    c_kv = rms_norm(
        kv_a[..., :rank], lp["kv_a_norm"], cfg.rms_norm_eps, False
    )
    k_pe = apply_rope_interleaved(
        kv_a[..., rank:][:, :, None, :], step.mla_sin, step.mla_cos
    )                                               # [B, T, 1, rope]
    if carried is not None:
        # the cache rides the scan without its one head (scan_layers)
        write = partial(
            _write_rows, layer=layer, start=step.positions[:, 0],
            decode_attn_impl=step.decode_attn_impl,
        )
        carried = KVCache(
            k=write(carried.k, c_kv), v=write(carried.v, k_pe[:, :, 0])
        )
    if carried is None or (T > 1 and step.max_len == T):
        attn = _mla_over_own_rows(
            q, c_kv, k_pe, lp["wk_b"], lp["wv_b"], step.mla_sin,
            step.mla_cos, step.mask, step.scale, step.mesh,
            step.attn_impl if step.use_flash else "xla",
            step.positions[0, 0],
        )
        return _mm("btq,qd->btd", attn, lp["wo"]), carried

    # absorbed, over this layer of the cache. An int8 weight's
    # scales are per output channel of kv_b_proj, (head, nope) or
    # (head, v): absorbing W_uk contracts over nope, so its scales
    # go onto the query first; W_uv's multiply the output.
    wk, wv = lp["wk_b"], lp["wv_b"]
    if isinstance(wk, QuantW):
        q_nope = q_nope * wk.s.reshape(H, nope).astype(q_nope.dtype)
        wk = wk.q.astype(q_nope.dtype)
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, wk.reshape(rank, H, nope))
    if step.decode_attn_impl == "xla":
        c_all, r_all = (
            lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)
            for buf in (carried.k, carried.v)
        )                                   # [B, S, rank], [B, S, rope]
        scores = (
            jnp.einsum("bthr,bsr->bhts", q_lat, c_all)
            + jnp.einsum("bthe,bse->bhts", q_pe, r_all)
        ).astype(jnp.float32) * step.scale
        scores = jnp.where(step.mask[:, None, :, :], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1).astype(q_lat.dtype)
        u = jnp.einsum("bhts,bsr->bthr", weights, c_all)
    else:
        from gpustack_tpu.ops.mla_attention import mla_decode_attention

        u = mla_decode_attention(
            q_lat[:, 0], q_pe[:, 0], carried.k, carried.v, layer,
            step.walk, step.scale,
            interpret=step.decode_attn_impl == "kernel_interpret",
        )[:, None]
    if isinstance(wv, QuantW):
        attn = jnp.einsum(
            "bthr,rhv->bthv", u, wv.q.astype(u.dtype).reshape(rank, H, vd)
        ) * wv.s.reshape(H, vd).astype(u.dtype)
    else:
        attn = jnp.einsum("bthr,rhv->bthv", u, wv.reshape(rank, H, vd))
    return _mm("btq,qd->btd", attn.reshape(B, T, H * vd), lp["wo"]), carried


def among_its_kind(layer, kind, period):
    """Where a layer's rows, its state or its mixer's leaves lie in
    the store or the stack of its kind. ``kind`` (static) is ``(the
    kind, how many of it come before the layer in its period)``."""
    return (layer // len(period)) * period.count(kind[0]) + kind[1]


def leaves_at(stack, at):
    """Layer ``at``'s leaves of a stack, read where they lie."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, at, 0, keepdims=False), stack
    )


def window_attention(h, lp, carried, at, step: Step):
    """One GQA layer of a stack that keeps its sliding layers' rows
    at window size (``cfg.window_rows``). ``at`` is ``(the layer's
    index in the stack, kind)``, ``kind`` (static) ``(sliding, how many
    of its kind come before it in its period)``: with the period's
    number that says where its rows lie in its store.

    A sliding layer sees keys ``0 <= i - j < sliding_window``; its
    step's rows go to the ring at ``position mod W`` and a decode
    step attends the ring's live rows, ``min(length, W)`` of them. A
    full layer is a causal layer over ``k, v``. A prefill is from
    position 0 and its own rows are every key, so it attends over
    them as they come (a band in the flash kernel) and leaves each
    sliding layer's last ``min(true_len, W)`` rows in the ring: a
    row takes the newest real position of its residue, the padding
    of a bucket writes nothing."""
    cfg, B, T, positions = step.cfg, step.B, step.T, step.positions
    layer, kind = at
    sliding = kind[0]
    q, k, v = qkv_projections(h, lp, decode=carried is not None and T == 1)
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if sliding or not cfg.nope_full_layers:
        rotate = (
            apply_rope_interleaved if cfg.rope_interleaved else apply_rope
        )
        q, k = rotate(q, step.sin, step.cos), rotate(k, step.sin, step.cos)
    grouped = q.reshape(B, T, cfg.num_kv_heads, cfg.group_size, cfg.head_dim)
    mask_l = step.mask_slide if sliding else step.mask_full
    if carried is None:
        attn = _attend(grouped, k, v, mask_l, step.scale)
        return _mm("btq,qd->btd", attn, lp["wo"]), carried
    store = among_its_kind(layer, kind, cfg.window_period)
    buf_k, buf_v = (
        (carried.wk, carried.wv) if sliding else (carried.k, carried.v)
    )
    rows = buf_k.shape[2]
    if T == 1:
        # a ring's live rows are its first min(length, W)
        live_rows = step.mask_full if not sliding else (
            jnp.arange(rows, dtype=jnp.int32)[None, None, :]
            < jnp.minimum(positions + 1, rows)[:, :, None]
        )
        attn, buf_k, buf_v = attend_over_cache(
            q, k, v, buf_k, buf_v, store,
            positions[:, 0] % rows if sliding else positions[:, 0],
            positions=positions, mask=live_rows, scale=step.scale,
            decode_attn_impl=step.decode_attn_impl,
            walk=step.walk_w if sliding else step.walk,
            name="gqa_window_decode_attention" if sliding else None,
        )
    else:
        if sliding:
            # ring row r takes position r + W * ((n - 1 - r) // W),
            # the newest real one of its residue, where r < n
            n = (
                step.true_len if step.true_len is not None
                else jnp.full((B,), T, jnp.int32)
            )[:, None]
            r = jnp.arange(rows, dtype=jnp.int32)[None, :]
            src = jnp.clip(r + rows * ((n - 1 - r) // rows), 0, T - 1)
            kept = [
                jnp.where(
                    (r < n)[:, :, None, None],
                    jnp.take_along_axis(new, src[:, :, None, None], axis=1),
                    jnp.zeros((), new.dtype),
                ) for new in (k, v)
            ]
        else:
            kept = [k, v]
        buf_k, buf_v = (
            lax.dynamic_update_index_in_dim(buf, new, store, 0)
            for buf, new in zip((buf_k, buf_v), kept)
        )
        if step.use_flash:
            band = cfg.sliding_window if sliding else 0
            attn = _flash_prefill(None, step.attn_impl)(
                q, k, v, step.scale, q_offset=positions[0, 0],
                window=band if band < T else 0,
            )
        else:
            attn = _attend(grouped, k, v, mask_l, step.scale)
    carried = dataclasses.replace(
        carried, **(
            dict(wk=buf_k, wv=buf_v) if sliding else dict(k=buf_k, v=buf_v)
        )
    )
    return _mm("btq,qd->btd", attn, lp["wo"]), carried


def after_mixer(carry, h, attn_out, carried, lp, step: Step, moe_layer: bool,
                norm_first: bool = True):
    """The rest of a layer, whatever its mixer: the mixer's output
    ``attn_out [B, T, d]`` through its bias, post norm and multiplier
    onto the stream, then the dense MLP or the routed experts
    (``moe_layer``) with the counters the Step asks for: a scan's
    ``(carry, routing)`` from the ``carry`` the layer was handed.
    ``norm_first`` False: the layer's two norms stand on its sublayers'
    outputs, not on their inputs (``cfg.norm_after``)."""
    cfg = step.cfg
    x_in, _, layer, *counts = carry
    if cfg.o_bias:
        attn_out = attn_out + lp["bo"]
    if cfg.post_norms:
        attn_out = rms_norm(
            attn_out, lp["post_attn_norm"], cfg.rms_norm_eps,
            cfg.norm_delta_gain,
        )
    if not norm_first:
        attn_out = model_norm(attn_out, lp["attn_norm"], cfg)
    if cfg.residual_multiplier != 1.0:
        attn_out = attn_out * jnp.asarray(
            cfg.residual_multiplier, attn_out.dtype
        )
    if cfg.parallel_block:
        # attention and MLP both read the one norm's output, and both
        # are added to the stream
        x_mid, h2 = x_in, h
    else:
        x_mid = x_in + attn_out
        h2 = model_norm(x_mid, lp["mlp_norm"], cfg) if norm_first else x_mid
    routing = None
    if moe_layer:
        mlp = _moe_mlp(
            h2, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"], cfg,
            router_bias=lp.get("router_bias"),
            shared=(
                (
                    lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                    lp.get("shared_gate"),
                )
                if "ws_gate" in lp else None
            ),
            biases=(
                (lp["we_gate_b"], lp["we_up_b"], lp["we_down_b"])
                if cfg.moe_bias else None
            ),
            dispatch=step.moe_dispatch_impl,
            layer=layer - step.kd if step.stacked else None,
            count_held=step.count_held_pairs,
            routing_out=step.routing_out,
            live=step.live,
            count_read=step.count_experts_read,
        )
        if counts or step.routing_out:
            mlp, *extras = mlp
            if step.routing_out:
                routing = extras.pop()
            counts = [c + n for c, n in zip(counts, extras)]
    else:
        act = (
            jax.nn.silu if cfg.hidden_act == "silu"
            else lambda z: jax.nn.gelu(z, approximate=True)
        )
        with jax.named_scope("gated_mlp"):
            g = _mm("btd,df->btf", h2, lp["w_gate"])
            u = _mm("btd,df->btf", h2, lp["w_up"])
            mlp = _mm("btf,fd->btd", act(g) * u, lp["w_down"])
    if cfg.post_norms:
        mlp = rms_norm(
            mlp, lp["post_mlp_norm"], cfg.rms_norm_eps, cfg.norm_delta_gain
        )
    if not norm_first:
        mlp = model_norm(mlp, lp["mlp_norm"], cfg)
    if cfg.residual_multiplier != 1.0:
        mlp = mlp * jnp.asarray(cfg.residual_multiplier, mlp.dtype)
    x_out = x_mid + attn_out + mlp if cfg.parallel_block else x_mid + mlp
    return (x_out, carried, layer + 1, *counts), routing


# ---------------------------------------------------------------------------
# The drivers: a stack of layers as one program
# ---------------------------------------------------------------------------


def _first_carry(x, cache, step: Step):
    """A scan's carry before the first layer: the stream, the cache
    (None without one), the layer's index, and the counters asked for
    in ``_moe_mlp``'s order: held pairs, experts read."""
    return (x, cache, jnp.int32(0)) + (jnp.int32(0),) * (
        step.count_held_pairs + step.count_experts_read
    )


def _uniform_layer(carry, scanned, *, step: Step, mixer, moe_layer: bool):
    """:func:`scan_layers`' body: one layer of ``mixer``."""
    lp, sliding = scanned
    if step.mask_full is not None:
        # gemma-style alternating layers: the layer's own mask and
        # rotation, by its slide flag
        step = dataclasses.replace(
            step,
            mask=jnp.where(sliding, step.mask_slide, step.mask_full),
            sin=jnp.where(sliding, step.sin_loc, step.sin),
            cos=jnp.where(sliding, step.cos_loc, step.cos),
        )
    lp = {**lp, **step.stacked}
    h = model_norm(carry[0], lp["attn_norm"], step.cfg)
    attn_out, carried = mixer(h, lp, carry[1], carry[2], step)
    return after_mixer(carry, h, attn_out, carried, lp, step, moe_layer)


def scan_layers(params: Params, step: Step, x, cache):
    """A uniform stack, a ``lax.scan`` over the stacked layers:
    ``(x, cache, extras)``. DeepSeek ships heterogeneous stacks: the
    first ``first_k_dense`` layers use a dense MLP, the rest MoE;
    structurally different params can't share one scan, so the two run
    back to back, the second going on from the first's carry."""
    cfg, kd = step.cfg, step.kd
    body = partial(
        _uniform_layer, step=step,
        mixer=mla_attention if cfg.is_mla else gqa_attention,
    )
    slide_flags = (
        jnp.asarray(cfg.layer_sliding, jnp.bool_)
        if cfg.layer_sliding is not None
        else jnp.zeros((cfg.num_layers,), jnp.bool_)
    )
    layers = {
        k: v for k, v in params["layers"].items() if k not in step.stacked
    }
    if cfg.is_mla and cache is not None:
        # an MLA cache has one head: inside the scan it is the same
        # arrays without it, as the TPU stores them (``_write_rows``)
        cache = KVCache(k=cache.k[:, :, :, 0, :], v=cache.v[:, :, :, 0, :])
    carry = _first_carry(x, cache, step)
    if kd:
        carry, _ = lax.scan(
            partial(body, moe_layer=False),
            carry, (params["dense_layers"], slide_flags[:kd]),
        )
    (x, cache, _, *extras), routing = lax.scan(
        partial(body, moe_layer=cfg.is_moe), carry, (layers, slide_flags[kd:])
    )
    if step.routing_out:
        extras.append(routing)
    if cfg.is_mla and cache is not None:
        cache = KVCache(
            k=cache.k[:, :, :, None, :], v=cache.v[:, :, :, None, :]
        )
    return x, cache, extras


def _one_period(carry, _, *, params: Params, layers, step: Step, kinds):
    """:func:`scan_periods`' body: a period's layers written out, each
    reading its own store. ``kinds``: a layer's ``(kind, its mixer's
    function, the stack of its mixer's leaves or None)``."""
    cfg = step.cfg
    routings = []
    for kind, mixer, stack in kinds:
        # the layer's leaves read where they lie in the stacks, by its
        # index: as a scan's slices of [periods, layers a period, ...] a
        # period's four matrices of every kind are copied out before the
        # body reads them (1.3 GB of temporaries at Command A+'s widths,
        # compiled for a described v5e)
        lp = leaves_at(layers, carry[2])
        at = (carry[2], kind)
        if stack is not None:
            # and its mixer's, at its place among its kind, where its
            # rows or its state lie in their store too (worked out twice,
            # as the programs' text has it: ROADMAP C17)
            lp.update(leaves_at(
                params[stack],
                among_its_kind(carry[2], kind, cfg.mixer_period),
            ))
            at = among_its_kind(carry[2], kind, cfg.mixer_period)
        lp = {**lp, **step.stacked}
        norm_first = kind[0] not in cfg.norm_after
        h = carry[0]
        if norm_first:
            h = model_norm(h, lp["attn_norm"], cfg)
        attn_out, carried = mixer(h, lp, carry[1], at, step)
        carry, routing = after_mixer(
            carry, h, attn_out, carried, lp, step, cfg.is_moe, norm_first
        )
        routings.append(routing)
    if not step.routing_out:
        return carry, None
    return carry, tuple(jnp.stack(r) for r in zip(*routings))


def scan_periods(params: Params, step: Step, x, cache, period):
    """Several kinds of layer in a pattern that repeats (``period``:
    ``cfg.window_period``, window and full layers over two stores; or
    ``cfg.mixer_period``, a mixer by kind under ``layer_types``): a scan
    over the periods, a period's layers written out in its body:
    ``(x, cache, extras)``. (A ``lax.switch`` on the kind inside a scan
    over the layers copies the stacked cache whole through the
    conditional every step: models/hybrid.py.)"""
    from gpustack_tpu.models.delta import delta_mixer
    from gpustack_tpu.models.hybrid import mamba_layer

    cfg = step.cfg
    # a kind of ``layer_types``: its mixer and the stack of its leaves;
    # a window stack's (``layer_sliding``) are all in ``layers``
    by_kind = {
        "linear_attention": (delta_mixer, "delta_layers"),
        "mamba": (mamba_layer, "ssm_layers"),
        "full_attention": (gqa_attention, "attn_layers"),
    }
    # (the kind, how many of it come before the layer in the period)
    kinds = [
        ((s, period[:j].count(s)), *by_kind.get(s, (window_attention, None)))
        for j, s in enumerate(period)
    ]
    layers = {
        k: v for k, v in params["layers"].items() if k not in step.stacked
    }
    (x, cache, _, *extras), routing = lax.scan(
        partial(
            _one_period, params=params, layers=layers, step=step, kinds=kinds
        ),
        _first_carry(x, cache, step), None,
        length=cfg.num_layers // len(period),
    )
    if step.routing_out:
        # [periods, layers a period, ...] -> [L, ...]
        extras.append(tuple(r.reshape(-1, *r.shape[2:]) for r in routing))
    return x, cache, extras


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,                # [B, T] int32
    positions: jax.Array,             # [B, T] int32 absolute positions
    cache: Optional[KVCache] = None,
    return_hidden: bool = False,
    attn_impl: str = "xla",
    mesh=None,
    embeds_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    moe_dispatch_impl: Optional[str] = None,
    decode_attn_impl: Optional[str] = None,
    live: Optional[jax.Array] = None,
    count_held_pairs: bool = False,
    routing_out: bool = False,
    count_experts_read: bool = False,
    true_len: Optional[jax.Array] = None,
    ssm_impl: Optional[str] = None,
    logits_at: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Run the model: ``(logits [B, T, vocab] float32, the updated cache
    or None)``. How the pieces fit: docs/MODELS.md.

    Without ``cache``: plain causal forward (training / scoring path).
    With ``cache``: each layer writes the step's rows into it at
    ``positions`` (only those rows: ``KVCache``) and attends over its
    own layer of it. ``T > 1`` is a prefill step, ``T == 1`` a decode
    step: same code path, different jit specialization.

    ``attn_impl``: the prefill attention, ``"xla"`` (einsum scores),
    ``"flash"`` (the blocked kernel, for a prefill into a cache that
    holds its rows; ``"flash_interpret"`` for hermetic CPU tests) or
    ``"ring"`` (needs ``mesh`` with an ``sp`` axis: the cache stays
    sharded over its positions, ops/ring_attention.py). A model the
    kernel refuses (:func:`needs_xla_attention`) raises: the caller
    chooses (``engine/runner.py prefill_attention``), this function
    never falls back in silence.

    ``moe_dispatch_impl``, ``decode_attn_impl``, ``ssm_impl``: None, and
    :func:`moe_dispatch`, :func:`decode_attention_impl` and
    ``models/hybrid.py ssm_update_impl`` choose from what can be
    observed; a name instead for the tests (``"grouped_interpret"``,
    ``"touched_interpret"``, ``"kernel_interpret"``) and for a caller
    that differentiates (``"dense"``: the kernels have no VJP).

    ``live`` (bool ``[B]``; None: every slot): the slots somebody holds.
    A decode step's kernels read a dead slot as far as length 0, route
    it to no expert and move no state of it; its logits mean nothing,
    its row of the cache is still written. ``true_len`` (int32 ``[B]``;
    None: every position counts): how many of a prefill's ``T``
    positions are real, for what a slot keeps beside its rows (a
    recurrent state, a window's ring), which must end at the last real
    position and not in a bucket's padding.

    ``logits_at`` (int32 ``[B]``, an index along ``T``): logits ``[B, 1,
    vocab]`` of the one row a sequence it names (:func:`head`);
    ``return_hidden``: the normed hidden states in their place.

    After the cache, in this order, each only if asked for:
    ``count_held_pairs`` (a share of the experts, ``cfg.experts_held``):
    how many of the router's pairs fell on experts held here, summed
    over the layers (int32; ``gpustack_engine_moe_pairs_total``);
    ``count_experts_read``: the held experts whose weights the step
    read (int32; ``held * layers`` but under ``"touched"``:
    ``gpustack_engine_moe_decode_experts_total``); ``routing_out``:
    ``(chosen int32 [L_moe, B, T, k], router logits float32 [L_moe, B,
    T, E])`` for a reference that must follow the program's choices
    (``perfbench/reference_check.py``; no served program asks).
    """
    step, x = make_step(
        params, cfg, tokens, positions, cache, attn_impl, mesh,
        embeds_override, moe_dispatch_impl, decode_attn_impl, live,
        count_held_pairs, routing_out, count_experts_read, true_len, ssm_impl,
    )
    period = cfg.window_period if cfg.window_rows else cfg.mixer_period
    if cfg.layer_kinds is not None:
        from gpustack_tpu.models.hybrid import forward_hybrid

        x, cache, extras = forward_hybrid(params, step, x, cache)
    elif period:
        x, cache, extras = scan_periods(params, step, x, cache, period)
    else:
        x, cache, extras = scan_layers(params, step, x, cache)
    return (head(x, params, cfg, logits_at, return_hidden), cache, *extras)
