"""Model hyperparameter config for the transformer core.

Replaces the reference's scattered HF-config probing (reference
gpustack/policies/candidate_selectors/base_candidate_selector.py:56-165 parses
hidden_size / num_attention_heads / num_key_value_heads / moe experts for
memory estimation) with one typed config that both the serving engine and the
scheduler's HBM estimator consume.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Dict, NamedTuple, Optional, Tuple


class BesideRows(NamedTuple):
    """What a model keeps a slot beside the rows it keeps a position
    (:attr:`ModelConfig.beside_rows`), in the phrases a refusal needs:
    the engine and the runner refuse by these whatever cuts, stores,
    moves or rolls back a slot by its positions, and name no kind."""

    keeps: str   # "<model> <keeps>"
    lost: str    # what a span of positions does not bring with it
    span: str    # a sentence: why a span of cached rows is not enough


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description of a decoder-only LM of one of the
    families :data:`FAMILIES` lists: Llama / Qwen / Mistral / Gemma
    dense stacks, Mixtral / Qwen-MoE / GPT-OSS / DeepSeek (MLA) expert
    stacks, each layer attention + MLP; the Nemotron-H hybrid
    (``layer_kinds``), each layer ONE mixer: a Mamba-2 state-space
    mixer, a mixture of two-matrix experts, or GQA; and the hybrids with
    ``layer_types`` (Olmo-Hybrid, Granite 4.0-H, Solar-Open2), each
    layer a mixer by kind (a gated-delta-rule linear-attention mixer,
    its decay one number a head or one a key channel; a Mamba-2 mixer;
    or full attention) and then an MLP or routed experts. The Mamba-2
    mixer is one function for both (``models/hybrid.py mamba_mixer``).

    Two kinds of layer keep a state a slot beside the rows a position
    (a Mamba-2 mixer's, a delta-rule mixer's); :attr:`state_shapes` is
    the one place that says what shape either is.

    Attention type is derived, not stored: MHA when num_kv_heads ==
    num_heads, GQA when 1 < num_kv_heads < num_heads, MQA when
    num_kv_heads == 1 (mirrors the attention-type classification the
    reference scheduler uses for KV-cache sizing,
    base_candidate_selector.py:148-165).
    """

    name: str = "custom"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    # HF-style rope_scaling dict: {"rope_type": "llama3"|"linear", "factor": ..}
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    qkv_bias: bool = False          # Qwen2-style attention bias
    qk_norm: bool = False           # Qwen3-style per-head q/k RMSNorm
    max_position_embeddings: int = 8192
    sliding_window: int = 0         # 0 = full attention
    # ---- Gemma-family knobs (Gemma2/Gemma3 text) ----
    hidden_act: str = "silu"        # "gelu_tanh" for gemma
    norm_delta_gain: bool = False   # RMSNorm gain stored as (1 + w)
    # the embeddings times this number: sqrt(hidden) for gemma, a
    # Granite file's ``embedding_multiplier``
    embed_multiplier: float = 1.0
    post_norms: bool = False        # sandwich post-attn/post-mlp norms
    query_pre_attn_scalar: float = 0.0  # 0 = scale by 1/sqrt(head_dim)
    attn_logit_softcap: float = 0.0     # 0 = no softcapping
    final_logit_softcap: float = 0.0
    # per-layer sliding flags (True = sliding_attention); None = use the
    # global sliding_window for every layer (Mistral-style)
    layer_sliding: Optional[Tuple[bool, ...]] = None
    # rope theta for sliding layers (gemma3 local attention); 0 = shared
    rope_local_theta: float = 0.0
    # MoE (Mixtral / Qwen-MoE class); num_experts == 0 means dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # ---- DeepSeek-family knobs ----
    # MLA (multi-head latent attention, DeepSeek-V2/V3 family):
    # kv_lora_rank>0 switches the attention block to compressed-latent
    # projections, and the cache to the latent: a position holds the
    # normed c_kv (kv_lora_rank wide, in the cache's k) and the rotated
    # shared rope key (qk_rope_head_dim wide, in its v), one "head" for
    # all query heads (kv_row_shapes). A prefill decompresses K and V
    # inside its program (head_dim = qk_nope + qk_rope for q and k,
    # v_head_dim for v); a step over cached rows absorbs the
    # up-projections into the query and the output
    # (models/transformer.py). num_kv_heads stays num_heads: it counts
    # the heads the weights and the decompressed K/V divide into, not
    # the cache's.
    q_lora_rank: int = 0            # 0 = direct q projection
    kv_lora_rank: int = 0           # >0 = MLA
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # DeepSeek MoE: shared experts run on every token alongside routed
    # ones; routed outputs scale by routed_scaling_factor. The first
    # first_k_dense layers use a dense MLP (v2/v3 checkpoints ship 1).
    n_shared_experts: int = 0
    shared_expert_intermediate_size: int = 0
    # Qwen2-MoE: the shared expert's output is gated by
    # sigmoid(x @ gate); DeepSeek adds it ungated
    shared_expert_gated: bool = False
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0
    # "softmax" (v2) | "sigmoid" (v3: score + e_score_correction_bias)
    # | "softmax_topk" (GPT-OSS: softmax over the selected top-k logits)
    moe_scoring: str = "softmax"
    # group-limited selection (DeepSeek-V3 family): the experts form
    # n_group groups of equal size, a group scores the sum of its two
    # best experts, the topk_group best groups stay and the token's
    # experts are chosen within them. n_group 1 is plain top-k.
    n_group: int = 1
    topk_group: int = 1
    # One chip's share of an expert-parallel layer: the router keeps all
    # num_experts outputs, the weights hold only the experts_held
    # experts from first_held_expert on, and the layer computes their
    # part of the result for the tokens routed to them (absent experts
    # add nothing). 0 = every expert is held.
    experts_held: int = 0
    first_held_expert: int = 0
    # ---- GPT-OSS knobs ----
    # learned per-head attention-sink logits (join the softmax
    # denominator only — modeling_gpt_oss eager_attention_forward)
    attn_sinks: bool = False
    o_bias: bool = False            # bias on the attention out proj
    # the experts' form: gated, three matrices an expert, "silu"
    # (swiglu) | "gptoss" (clamped gate*sigmoid(1.702*gate), combined as
    # (up+1)*glu); or plain, two matrices an expert and no gate, "relu2"
    # (down(relu(up x)^2), Nemotron-H: the shared expert likewise) —
    # experts carry biases on gate/up/down when moe_bias is set
    moe_act: str = "silu"
    moe_bias: bool = False
    # ---- Nemotron-H (hybrid) knobs ----
    # One mixer a layer, by kind: "M" a Mamba-2 state-space mixer, "E"
    # experts alone, "*" GQA alone (``hybrid_override_pattern``), each
    # behind one pre-norm and one residual add. None: every layer is
    # attention + MLP, as every other family. num_layers == len of it.
    layer_kinds: Optional[Tuple[str, ...]] = None
    rope: bool = True               # False: attention without rotary
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    mamba_n_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk_size: int = 128
    # ---- Cohere2-MoE (Command A+) knobs ----
    # one norm a layer, attention and MLP both from it and both added to
    # the stream (``use_parallel_block``)
    parallel_block: bool = False
    # mean-centred LayerNorm without bias where every other family has
    # an RMS norm; ``rms_norm_eps`` holds its epsilon
    layer_norm: bool = False
    # rotary embedding in interleaved pairs over the whole head
    # (``rope_gptj``), and none at all on the full-attention layers of a
    # stack with ``layer_sliding``
    rope_interleaved: bool = False
    nope_full_layers: bool = False
    # the sliding layers' rows in a store of their own of
    # ``sliding_window`` rows a slot (``KVCache.wk / .wv``), written at
    # ``position mod window``; the full layers' rows in ``k, v``. False:
    # a window is a mask over ``S_max`` rows (the Gemma / GPT-OSS files)
    window_rows: bool = False
    # the shared experts' outputs are averaged, not summed
    # (``shared_expert_combination_strategy: "average"``)
    shared_expert_average: bool = False
    # False: a sigmoid router without the selection's correction bias
    router_correction_bias: bool = True
    logit_scale: float = 1.0
    # ---- Granite knobs ----
    # each sublayer's output times this number before it is added to
    # the stream. The family's other three multipliers are
    # ``embed_multiplier``, ``logit_scale`` (1 / logits_scaling) and
    # ``query_pre_attn_scalar`` (attention_multiplier ** -2).
    residual_multiplier: float = 1.0
    # ---- knobs of a stack with a mixer by kind (Olmo-Hybrid, Granite) ----
    # The kind of each layer's mixer: "linear_attention" a
    # gated-delta-rule mixer (ops/delta_rule.py: a matrix state a head a
    # slot, the ``linear_*`` keys as the hub file has them), "mamba" a
    # Mamba-2 mixer (the ``mamba_*`` fields above), "full_attention"
    # causal GQA. Every layer has an MLP after its mixer, or routed
    # experts where ``num_experts`` says so. None: every other family.
    # num_layers == len of it.
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # beta = 2 sigmoid(.) and not sigmoid(.): along a key the state's
    # transition has an eigenvalue in (-1, 1) (arXiv:2411.12537)
    linear_allow_neg_eigval: bool = False
    # What KDA (Kimi Linear, arXiv:2510.26692: Solar-Open2's linear
    # layers) changes of the delta-rule mixer, each a field:
    # the decay one number a head AND key channel (``g [B, T, H, Dk]``,
    # ``dt_bias [H * Dk]``), not one a head;
    linear_decay_a_channel: bool = False
    # the decay's and the output gate's projections through a
    # bottleneck of this rank (``Wf_a [D, r] Wf_b [r, H * Dk]``, ``Wg_a
    # [D, r] Wg_b [r, H * Dv]``); 0: one full matrix each (``Wa [D,
    # H]``, ``Wg [D, H * Dv]``);
    linear_low_rank: int = 0
    # the output gate under "sigmoid", not "silu"
    linear_gate_act: str = "silu"
    # full attention's output times ``sigmoid(h W_gate)``, elementwise
    # over its ``q_dim`` channels, before ``Wo`` (arXiv:2505.06708)
    attn_output_gate: bool = False
    # q and k normalised over the whole projection before the heads are
    # split (one gain of q_dim / kv_dim), not a head at a time (qk_norm)
    qk_norm_whole: bool = False
    # the kinds of ``layer_types`` whose two norms stand on each
    # sublayer's output inside the residual (x + norm(f(x))), not on its
    # input (x + f(norm(x)))
    norm_after: Tuple[str, ...] = ()
    # Generation by diffusion over blocks (SDAR): with ``L`` this length,
    # a query at position q sees the keys below ``(q // L + 1) * L``
    # (causal over blocks, both ways inside one), the undecided positions
    # of the block being generated hold ``mask_token_id`` and a step is a
    # pass over the block's ``L`` rows that decides 0 to ``L`` of them
    # (``engine/runner.py _denoise_impl``). 0: every other family, a
    # causal mask and a token a step.
    diffusion_block: int = 0
    # how many of a block's positions a pass decides at least: ``L //
    # steps``, the remainder spread over the first passes
    denoising_steps: int = 0
    # which ones: ``DIFFUSION_RULES`` (``engine/sampling.py decide``)
    remasking_strategy: str = ""
    confidence_threshold: float = 0.0
    mask_token_id: int = -1
    dtype: str = "bfloat16"

    # ---- derived ----
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def group_size(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def num_held_experts(self) -> int:
        """Experts whose weights this replica holds (``experts_held``)."""
        return self.experts_held or self.num_experts

    # ---- the hybrid's layers, by kind ----
    def layers_of(self, kind: str) -> int:
        """How many layers are of ``kind`` (``"M"``, ``"E"``, ``"*"``);
        0 for a model without ``layer_kinds``."""
        return sum(k == kind for k in self.layer_kinds or ())

    @property
    def num_kv_layers(self) -> int:
        """Layers that keep a row for every position of a slot in the
        cache's ``k, v``: every layer, the hybrid's attention layers, or
        under ``window_rows`` the full-attention layers."""
        if self.layer_types is not None:
            return self.layer_types.count("full_attention")
        if self.layer_kinds is None:
            return self.num_layers - self.num_window_layers
        return self.layers_of("*")

    @property
    def num_mamba_layers(self) -> int:
        """Layers whose mixer is a Mamba-2 state-space mixer, of either
        way to say so (``layer_kinds``' ``"M"``, ``layer_types``'
        ``"mamba"``)."""
        return self.layers_of("M") + (self.layer_types or ()).count("mamba")

    @property
    def num_linear_layers(self) -> int:
        """Layers whose mixer is a gated delta rule (``layer_types``)."""
        return (self.layer_types or ()).count("linear_attention")

    @property
    def window_period(self) -> Tuple[bool, ...]:
        """The shortest run of ``layer_sliding`` that the stack repeats
        (three sliding layers and a full one); the whole stack where it
        repeats nothing. ``forward`` scans over periods and writes a
        period's layers out in the scan's body."""
        return _period(self.layer_sliding or ())

    @property
    def mixer_period(self) -> Tuple[str, ...]:
        """:attr:`window_period` for ``layer_types`` (three linear layers
        and a full one)."""
        return _period(self.layer_types or ())

    @property
    def num_window_layers(self) -> int:
        """Layers whose rows live in the window store (``window_rows``)."""
        return sum(self.layer_sliding) if self.window_rows else 0

    @property
    def num_moe_layers(self) -> int:
        """Layers with routed experts."""
        if self.layer_kinds is not None:
            return self.layers_of("E")
        return self.num_layers - self.first_k_dense if self.is_moe else 0

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Width of ``xBC``, what the causal convolution runs over."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.ssm_state_size

    @property
    def linear_conv_dim(self) -> int:
        """Width of a delta-rule mixer's q, k and v side by side, what
        its three causal convolutions run over."""
        return (
            2 * self.linear_num_key_heads * self.linear_key_head_dim
            + self.linear_num_value_heads * self.linear_value_head_dim
        )

    @property
    def state_shapes(
        self,
    ) -> Optional[Tuple[int, Tuple[int, ...], Tuple[int, ...]]]:
        """``(layers, state, conv)``: how many layers keep a state a slot
        whatever its length, the shape of one layer's recurrent state
        (float32) and of the rows its convolution keeps (the ``kernel -
        1`` last ones side by side on the lanes: as ``[kernel - 1, C]``
        the TPU pads three rows to a tile of sixteen). None for a model
        without such layers. The one place that says so:
        ``KVCache.create`` and the byte counts follow it.

        A Mamba-2 mixer keeps ``[H, P, N]`` a head's ``[P, N]`` at a
        time; a delta-rule mixer ``[Dk, H * Dv]``, the key width on the
        sublanes and every head's values side by side on the lanes, so
        that widths that are no whole lane tiles (96, 192) store nothing
        padded (``ops/delta_rule.py``)."""
        if self.num_mamba_layers:
            return (
                self.num_mamba_layers,
                (self.mamba_num_heads, self.mamba_head_dim,
                 self.ssm_state_size),
                ((self.conv_kernel - 1) * self.mamba_conv_dim,),
            )
        if self.num_linear_layers:
            return (
                self.num_linear_layers,
                (self.linear_key_head_dim,
                 self.linear_num_value_heads * self.linear_value_head_dim),
                ((self.linear_conv_kernel_dim - 1) * self.linear_conv_dim,),
            )
        return None

    @property
    def state_mixer(self) -> Optional[str]:
        """The kind of mixer whose state :attr:`state_shapes` describes,
        as the flight records and ``/metrics`` label it: ``"ssm"``
        (Mamba-2), ``"delta"`` (gated delta rule, a decay a head),
        ``"kda"`` (the same rule, a decay a key channel), None without
        one."""
        if self.num_mamba_layers:
            return "ssm"
        if self.num_linear_layers:
            return "kda" if self.linear_decay_a_channel else "delta"
        return None

    @property
    def kv_row_shapes(self) -> Tuple[Tuple[int, int], Tuple[int, int]]:
        """``((heads, width) of k, (heads, width) of v)``: what one
        position of one layer holds in the cache. The one place that
        says so: ``KVCache.create`` and the byte counts follow it. GQA
        holds each kv head's key and value; MLA the shared latent in
        ``k`` and the shared rope key in ``v``, one head each."""
        if self.is_mla:
            return (1, self.kv_lora_rank), (1, self.qk_rope_head_dim)
        side = self.kv_heads_a_row
        row = (self.kv_heads_stored // side, side * self.head_dim)
        return row, row

    @property
    def kv_heads_a_row(self) -> int:
        """How many kv heads lie side by side on the lanes of one stored
        row of a GQA cache: 1, or ``128 // head_dim`` for heads narrower
        than a lane tile (64 -> 2: ``[S, 8, 64]`` is stored ``[S, 4,
        128]``, the same bytes in the same order, nothing padded). The
        TPU tiles the last dimension in 128 lanes, so ``[.., 8, 64]``
        bf16 is half-empty tiles or a transposed array, and the decode
        kernel's merged view of positions and heads (``ops/
        decode_attention.py``) is not the stored one; with two heads a
        row it is, and a query head attends with zeros in the other
        head's lanes and keeps its own half of the result
        (``transformer.attend_over_cache``). Only where the heads fill
        whole rows, and only for a model that is served on one device
        whatever is asked (:attr:`beside_rows`): a mesh divides a cache
        by its kv heads, and a row of two is not divided."""
        hd = self.head_dim
        if (
            hd >= 128 or 128 % hd or self.kv_dim % 128
            or self.state_mixer is None
        ):
            return 1
        return 128 // hd

    @property
    def kv_heads_stored(self) -> int:
        """The kv heads a position's rows hold in a GQA cache:
        ``num_kv_heads``, or, where those are more than one bf16 sublane
        tile (16) and no whole number of them, the next whole number (30
        -> 32), the heads behind the real ones zeros that no query head
        reads. The TPU stores ``[S, Hkv, hd]`` with the heads on a tile's
        sublanes; at 30 it stores the array with the positions there
        instead (transposed), which the decode kernel cannot read in
        place: compiled for a described v5e, the decode program copied
        both caches whole into the other order and back every step (PR
        53; ``models/hybrid.py pad_expert_width``'s lesson again).
        ``transformer.gqa_attention`` pads q, k and v on their way to the
        cache and drops the heads that are none on the way out."""
        h = self.num_kv_heads
        return h if h <= 16 or h % 16 == 0 else -(-h // 16) * 16

    @property
    def beside_rows(self) -> Optional[BesideRows]:
        """None for a model whose slot is rows a position and nothing
        else (``kv_row_shapes``): any span of a slot's positions can
        then be cut out, stored, moved, gone on from or rolled back.
        Else what the slot keeps beside them (``KVCache`` holds it:
        a state-space or linear-attention layer's recurrent state, a
        sliding layer's ring of window rows). Such a model is served on
        one device, and the prefix cache, the spill tier, a KV handoff,
        a verify step and a chunked prefill are refused for it
        (``engine/engine.py _refuse_what_moves_a_slot``,
        ``engine/runner.py``)."""
        if self.state_mixer:
            return BesideRows(
                {"ssm": "has state-space layers",
                 "delta": "has linear-attention layers",
                 "kda": "has linear-attention layers"}[self.state_mixer],
                "a recurrent state",
                "the rows carry no recurrent state to go on from",
            )
        if self.window_rows:
            return BesideRows(
                "keeps its sliding layers' rows at window size",
                "rows a ring has overwritten",
                "a span's sliding rows are gone once the window has "
                "passed it",
            )
        return None

    @property
    def attention_type(self) -> str:
        if self.num_kv_heads == 1:
            return "MQA"
        if self.num_kv_heads == self.num_heads:
            return "MHA"
        return "GQA"

    def validate(self) -> "ModelConfig":
        assert self.num_heads % self.num_kv_heads == 0, (
            "num_heads must be divisible by num_kv_heads"
        )
        if self.is_moe:
            assert self.num_experts_per_tok > 0
            assert self.moe_intermediate_size > 0
            assert self.num_experts % self.n_group == 0, (
                "n_group must divide the experts"
            )
            assert 1 <= self.topk_group <= self.n_group
            assert (
                0 <= self.first_held_expert
                and self.first_held_expert + self.num_held_experts
                <= self.num_experts
            ), "the held experts must lie among the router's"
        if self.layer_sliding is not None:
            assert len(self.layer_sliding) == self.num_layers
            assert self.sliding_window > 0
        if self.window_rows:
            assert self.layer_sliding is not None and not self.is_mla
            assert not (self.attn_logit_softcap or self.attn_sinks), (
                "the blocked kernels take a window, no softcap or sinks"
            )
        if self.layer_kinds is not None:
            assert len(self.layer_kinds) == self.num_layers
            assert set(self.layer_kinds) <= {"M", "E", "*"}, self.layer_kinds
            if self.layers_of("M"):
                assert self.mamba_num_heads % self.mamba_n_groups == 0
                assert self.mamba_inner and self.ssm_state_size
        if self.kv_heads_stored != self.num_kv_heads:
            # only ``transformer.gqa_attention`` pads its heads
            assert self.layer_kinds is None and not self.window_rows
            assert not self.attn_sinks
        if self.layer_types is not None:
            assert len(self.layer_types) == self.num_layers
            assert set(self.layer_types) <= {
                "linear_attention", "mamba", "full_attention"
            }, self.layer_types
            assert self.layer_kinds is None and self.layer_sliding is None
            assert not self.is_mla, "latent attention beside a mixer by kind"
            # experts stand in every layer or in none (no dense prefix),
            # gated three-matrix ones without biases
            assert not (self.is_moe and (
                self.first_k_dense or self.moe_bias or self.moe_act != "silu"
            ))
            if "mamba" in self.layer_types:
                assert self.mamba_num_heads % self.mamba_n_groups == 0
                assert self.mamba_inner and self.ssm_state_size
            if self.num_linear_layers:
                assert (
                    self.linear_num_key_heads == self.linear_num_value_heads
                ), "a delta-rule mixer is served with a key head a value head"
                assert self.linear_key_head_dim and self.linear_value_head_dim
                assert self.linear_gate_act in ("silu", "sigmoid")
        else:
            assert not self.attn_output_gate, (
                "an output gate on attention is read in a stack with "
                "layer_types only"
            )
        if self.diffusion_block:
            L = self.diffusion_block
            # the blocked kernels' tiles are of 128 rows: a block may
            # not straddle one
            assert L > 1 and 128 % L == 0, f"diffusion_block {L}"
            assert 1 <= self.denoising_steps <= L, self.denoising_steps
            assert self.remasking_strategy in DIFFUSION_RULES, (
                self.remasking_strategy
            )
            assert 0 <= self.mask_token_id < self.vocab_size
            # causal GQA layers with positions, and nothing a slot keeps
            # beside its rows: the block's mask is made in ``forward``
            assert not (
                self.is_mla or self.sliding_window or self.attn_sinks
                or self.attn_logit_softcap or self.layer_kinds
                or self.layer_types
            ), "generation by diffusion is read for a causal GQA stack"
        return self

    # ---- memory accounting (used by scheduler + engine sizing) ----
    def _gated_experts_params(self) -> int:
        """One layer's router, held three-matrix experts and shared
        expert."""
        d = self.hidden_size
        n = d * self.num_experts + 3 * d * (
            self.num_held_experts * self.moe_intermediate_size
            + self.shared_expert_intermediate_size
        )
        if self.moe_scoring == "sigmoid" and self.router_correction_bias:
            n += self.num_experts     # e_score_correction_bias
        return n

    def param_count(self) -> int:
        """Exact parameter count of what this replica holds: embedding,
        head, every layer's matrices, norms, biases and router, with the
        experts it holds (``experts_held``) and not the absent ones. A
        file cut to a share counts the share; the published file counts
        the published model."""
        d, v = self.hidden_size, self.vocab_size
        embed = v * d
        lm_head = 0 if self.tie_word_embeddings else d * v
        if self.layer_kinds is not None:
            inner, conv = self.mamba_inner, self.mamba_conv_dim
            heads = self.mamba_num_heads
            mamba = (
                d * (inner + conv + heads)      # in_proj: z | xBC | dt
                + conv * self.conv_kernel + conv    # conv1d and its bias
                + 3 * heads                     # A_log, D, dt_bias
                + inner                         # the gated norm's gain
                + inner * d                     # out_proj
            )
            experts = (
                d * self.num_experts + self.num_experts     # router, bias
                + self.num_held_experts * 2 * d * self.moe_intermediate_size
                + 2 * d * self.shared_expert_intermediate_size
            )
            attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            return (
                embed + lm_head + d
                + self.layers_of("M") * (mamba + d)
                + self.layers_of("E") * (experts + d)
                + self.layers_of("*") * (attn + d)
            )
        if self.layer_types is not None:
            keys = self.linear_num_key_heads * self.linear_key_head_dim
            values = self.linear_num_value_heads * self.linear_value_head_dim
            heads = self.linear_num_value_heads
            rank = self.linear_low_rank
            decays = keys if self.linear_decay_a_channel else heads
            linear = (
                2 * d * keys + d * values        # wq, wk; wv
                + values * d                     # wo
                + d * heads + heads + decays     # wb; A_log, dt_bias
                + self.linear_conv_kernel_dim * self.linear_conv_dim
                + self.linear_value_head_dim     # the output norm's gain
            )
            # the decay's and the gate's projections: one matrix each, or
            # each through the bottleneck
            linear += (
                2 * d * rank + rank * (decays + values) if rank
                else d * decays + d * values
            )
            full = (
                d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
                + self.q_dim + self.kv_dim       # the whole-width q, k norms
            )
            if not self.qk_norm_whole:
                full -= self.q_dim + self.kv_dim
            if self.attn_output_gate:
                full += d * self.q_dim
            inner, conv = self.mamba_inner, self.mamba_conv_dim
            mamba = (
                d * (inner + conv + self.mamba_num_heads)  # z | xBC | dt
                + conv * self.conv_kernel + conv    # conv1d and its bias
                + 3 * self.mamba_num_heads          # A_log, D, dt_bias
                + inner + inner * d                 # the gated norm, out_proj
            )
            mlp = (
                self._gated_experts_params() if self.is_moe
                else 3 * d * self.intermediate_size
            )
            return (
                embed + lm_head + d
                + self.num_linear_layers * linear
                + self.num_mamba_layers * mamba
                + self.num_kv_layers * full
                + self.num_layers * (mlp + 2 * d)
            )
        if self.is_mla:
            qk_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
            if self.q_lora_rank:
                attn = (
                    d * self.q_lora_rank
                    + self.q_lora_rank * self.num_heads * qk_dim
                    + self.q_lora_rank
                )
            else:
                attn = d * self.num_heads * qk_dim
            attn += (
                d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * self.num_heads
                * (self.qk_nope_head_dim + self.v_head_dim)
                + self.kv_lora_rank
                + self.num_heads * self.v_head_dim * d
            )
        else:
            attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
            if self.qkv_bias:
                attn += self.q_dim + 2 * self.kv_dim
            if self.qk_norm:
                attn += 2 * self.head_dim
        if self.is_moe:
            mlp = self._gated_experts_params()
        else:
            mlp = 3 * d * self.intermediate_size
        norms = (4 if self.post_norms else 1 if self.parallel_block else 2) * d
        per_layer = attn + mlp + norms
        dense_delta = 0
        if self.is_moe and self.first_k_dense:
            dense_delta = self.first_k_dense * (
                3 * d * self.intermediate_size - mlp
            )
        return (
            embed + lm_head + self.num_layers * per_layer
            + dense_delta + d
        )

    def weight_bytes(self, bits: int = 16) -> int:
        """Bytes of the weights as stored: the parameters, and for a
        hybrid the zeros that fill its experts' width up to whole lane
        tiles of 128 (``models/hybrid.py pad_expert_width``)."""
        stored = self.param_count()
        if self.layer_kinds is not None:
            stored += (
                self.layers_of("E") * self.num_held_experts
                * 2 * self.hidden_size * (-self.moe_intermediate_size % 128)
            )
        return stored * bits // 8

    def kv_cache_bytes_per_token(self, bits: int = 16) -> int:
        """Bytes of cache per token position (all layers that keep rows
        a position: ``num_kv_layers``)."""
        per_layer = sum(h * w for h, w in self.kv_row_shapes)
        return self.num_kv_layers * per_layer * bits // 8

    def window_bytes_per_slot(self, max_len: int, bits: int = 16) -> int:
        """Bytes a slot of ``max_len`` positions keeps in the window
        store: ``min(sliding_window, max_len)`` rows of every sliding
        layer, whatever its length. 0 without ``window_rows``."""
        per_layer = sum(h * w for h, w in self.kv_row_shapes)
        rows = min(self.sliding_window, max_len)
        return self.num_window_layers * rows * per_layer * bits // 8

    def state_bytes_per_slot(self, bits: int = 16) -> int:
        """Bytes a slot keeps whatever its length (:attr:`state_shapes`):
        each such layer's recurrent state (float32, as the families'
        serving notes ask) and the rows its convolution keeps (``bits``
        wide). 0 for a model without such layers."""
        if self.state_shapes is None:
            return 0
        layers, state, conv = self.state_shapes
        return layers * (
            math.prod(state) * 4 + math.prod(conv) * bits // 8
        )

    def beside_bytes_per_slot(self, max_len: int, bits: int = 16) -> int:
        """Bytes a slot of ``max_len`` positions keeps beside the rows of
        ``kv_cache_bytes_per_token``, whatever its length: 0 for a model
        without :attr:`beside_rows`."""
        return self.state_bytes_per_slot(bits) + self.window_bytes_per_slot(
            max_len, bits
        )


def _period(kinds: tuple) -> tuple:
    """The shortest run of ``kinds`` that the whole repeats; ``kinds``
    itself where it repeats nothing, ``()`` of nothing."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return ()


# Which of a block's undecided positions a pass decides (the family's
# published ``block_diffusion_generate``): the first ones, the ones of
# largest confidence, or every one over a threshold and at least as many
# as a pass must.
DIFFUSION_RULES: Tuple[str, ...] = (
    "sequential", "low_confidence_static", "low_confidence_dynamic",
)


# The families ``config_from_hf`` reads, by a substring of the file's
# first ``architectures`` entry. A file that names an architecture of
# none of them is refused by name: served as a Llama-class stack (what
# any file with ``hidden_size`` and ``num_attention_heads`` used to get)
# it would answer with the wrong model's tokens. A file that names no
# architecture at all is read by its keys, as the presets' and the
# tests' small files are.
FAMILIES: Tuple[str, ...] = (
    "Llama", "Mistral", "Mixtral", "Qwen2", "Qwen3", "Gemma", "GptOss",
    "Deepseek", "NemotronH", "Cohere2Moe", "OlmoHybrid", "GraniteMoeHybrid",
    "SolarOpen2", "SDARMoe",
    # multimodal wrappers whose text stack is one of the above
    "Llava", "VLForConditionalGeneration",
)


def _nemotron_h_config(cfg: Dict[str, Any], name: str) -> ModelConfig:
    """The Nemotron-H hybrid (``model_type: nemotron_h``): one mixer a
    layer by ``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` experts,
    ``*`` attention; ``-``, a dense MLP layer of the family's older
    members, is not read). The router's keys are the DeepSeek-V3
    router's; attention takes no rotary embedding (no layer of the
    family's public port reads ``rope_theta``).

    One chip's share of the experts is ``n_routed_experts`` (how many
    are held here) beside ``experts_held: {"of": <the router's published
    width>, "first": <the first held id>}``. ``of``, not the
    ``published`` of the DeepSeek family's files, on purpose: a reader
    from before this family (it takes any file with ``hidden_size`` and
    ``num_attention_heads`` for an attention + gated-expert stack, 52
    layers of it here) then fails on the share's key and the instance
    ends in ``error`` at once, where it would otherwise wait for a
    placement no chip can give (PERF.md section 6, PR 46)."""
    pattern = cfg["hybrid_override_pattern"]
    if set(pattern) - {"M", "E", "*"}:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}: only M (Mamba-2), E "
            "(experts) and * (attention) layers are served"
        )
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}"
        )
    if cfg.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(
            f"mlp_hidden_act {cfg['mlp_hidden_act']!r}: the hybrid's "
            "two-matrix experts are served with relu2 only"
        )
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    share = cfg.get("experts_held") or {}
    held = int(cfg.get("n_routed_experts") or 0)
    m_heads = int(cfg["mamba_num_heads"])
    m_dim = int(cfg["mamba_head_dim"])
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=hidden,
        intermediate_size=cfg.get("intermediate_size", 0),
        num_layers=len(pattern),
        num_heads=heads,
        num_kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or hidden // heads,
        rope=False,
        rms_norm_eps=cfg.get("layer_norm_epsilon")
        or cfg.get("norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        max_position_embeddings=cfg.get("max_position_embeddings", 8192),
        num_experts=int(share["of"]) if share else held,
        experts_held=held if share else 0,
        first_held_expert=int(share.get("first", 0)),
        num_experts_per_tok=cfg.get("num_experts_per_tok", 0),
        moe_intermediate_size=int(cfg.get("moe_intermediate_size") or 0),
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        n_shared_experts=int(cfg.get("n_shared_experts") or 0),
        shared_expert_intermediate_size=int(
            cfg.get("moe_shared_expert_intermediate_size") or 0
        ),
        routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
        moe_scoring="sigmoid",
        n_group=int(cfg.get("n_group") or 1),
        topk_group=int(cfg.get("topk_group") or 1),
        moe_act="relu2",
        layer_kinds=tuple(pattern),
        mamba_num_heads=m_heads,
        mamba_head_dim=m_dim,
        ssm_state_size=int(cfg["ssm_state_size"]),
        mamba_n_groups=int(cfg.get("n_groups") or 1),
        conv_kernel=int(cfg.get("conv_kernel") or 4),
        ssm_chunk_size=int(cfg.get("chunk_size") or 128),
    ).validate()


def _cohere2_moe_config(cfg: Dict[str, Any], name: str) -> ModelConfig:
    """Command A+ (``model_type: cohere2_moe``): a parallel block behind
    one mean-centred LayerNorm; window and full attention layers by
    ``layer_types``, the window layers rotated in interleaved pairs
    (``rope_gptj``) and their rows kept at window size, the full layers
    without positional embedding; sigmoid scores, the ``num_experts_per_
    tok`` largest chosen without groups or bias and normalised;
    ``num_shared_experts`` shared experts of the routed experts' width,
    averaged; the embedding tied to the head under ``logit_scale``.

    One chip's share of the experts is ``num_experts`` (how many are
    held here) beside ``experts_held: {"of", "first"}``, as the
    Nemotron-H files state it and for its reason (a reader from before
    this family fails on the key at once)."""
    def refuse(key, want):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"{key} {cfg[key]!r}: a cohere2_moe stack is served with "
                f"{want!r} only"
            )

    refuse("position_embedding_type", "rope_gptj")
    refuse("expert_selection_fn", "sigmoid")
    refuse("shared_expert_combination_strategy", "average")
    refuse("use_parallel_block", True)
    refuse("use_gated_activation", True)
    refuse("use_qk_norm", False)
    refuse("attention_bias", False)
    refuse("rotary_pct", 1)
    refuse("hidden_act", "silu")
    if int(cfg.get("first_k_dense_replace") or 0):
        raise ValueError(
            "first_k_dense_replace: a dense prefix of a cohere2_moe stack "
            "(prefix_dense_*) is not read"
        )
    # a file cut in depth keeps the published list whole: the stack is
    # its first num_hidden_layers entries
    layer_types = (cfg.get("layer_types") or [])[:cfg["num_hidden_layers"]]
    if len(layer_types) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layer_types has {len(layer_types)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}"
        )
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    share = cfg.get("experts_held") or {}
    held = int(cfg["num_experts"])
    width = int(cfg["intermediate_size"])
    shared = int(cfg.get("num_shared_experts") or 0)
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=hidden,
        intermediate_size=width,
        num_layers=cfg["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or hidden // heads,
        rope_theta=float(
            (cfg.get("rope_parameters") or {}).get("rope_theta")
            or cfg.get("rope_theta", 10000.0)
        ),
        rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", True),
        max_position_embeddings=cfg.get("max_position_embeddings", 8192),
        sliding_window=int(cfg["sliding_window"]),
        layer_sliding=tuple(t == "sliding_attention" for t in layer_types),
        num_experts=int(share["of"]) if share else held,
        experts_held=held if share else 0,
        first_held_expert=int(share.get("first", 0)),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=width,
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        moe_scoring="sigmoid",
        n_shared_experts=shared,
        shared_expert_intermediate_size=shared * width,
        shared_expert_average=shared > 1,
        router_correction_bias=False,
        parallel_block=True,
        layer_norm=True,
        rope_interleaved=True,
        nope_full_layers=True,
        window_rows=True,
        logit_scale=float(cfg.get("logit_scale", 1.0)),
    ).validate()


def _olmo_hybrid_config(cfg: Dict[str, Any], name: str) -> ModelConfig:
    """Olmo-Hybrid (``model_type: olmo_hybrid``): every layer a mixer and
    then a gated MLP; the mixer by ``layer_types``, a gated-delta-rule
    linear-attention mixer (the ``linear_*`` keys; three short causal
    convolutions, ``beta`` doubled under ``linear_allow_neg_eigval``) or
    full causal attention with q and k normalised over the whole
    projection. The hub file has no key for two things, and what is
    assumed of each is a field here, so that a correction is one line
    (``perfbench/configs/olmo-hybrid-7b-int8/deployment.json``,
    ``assumed``): a full-attention layer norms each sublayer's output
    inside the residual, as the family's earlier models do, and a
    linear-attention layer each sublayer's input (``norm_after``);
    attention takes no rotary embedding where ``rope_parameters.
    rope_theta`` is null (``rope``)."""
    layer_types = tuple(cfg.get("layer_types") or ())
    if set(layer_types) - {"linear_attention", "full_attention"}:
        raise ValueError(
            f"layer_types {sorted(set(layer_types))}: only linear_attention "
            "and full_attention layers are served"
        )
    if len(layer_types) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layer_types has {len(layer_types)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}"
        )
    for key, want in (("hidden_act", "silu"), ("attention_bias", False)):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"{key} {cfg[key]!r}: an olmo_hybrid stack is served with "
                f"{want!r} only"
            )
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=hidden,
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or hidden // heads,
        rope=theta is not None,
        rope_theta=float(theta or 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        max_position_embeddings=cfg.get("max_position_embeddings", 8192),
        layer_types=layer_types,
        linear_num_key_heads=int(cfg["linear_num_key_heads"]),
        linear_num_value_heads=int(cfg["linear_num_value_heads"]),
        linear_key_head_dim=int(cfg["linear_key_head_dim"]),
        linear_value_head_dim=int(cfg["linear_value_head_dim"]),
        linear_conv_kernel_dim=int(cfg.get("linear_conv_kernel_dim") or 4),
        linear_allow_neg_eigval=bool(cfg.get("linear_allow_neg_eigval")),
        qk_norm_whole=True,
        norm_after=("full_attention",),
    ).validate()


def _granite_hybrid_config(cfg: Dict[str, Any], name: str) -> ModelConfig:
    """Granite 4.0-H (``model_type: granitemoehybrid``): every layer a
    mixer by ``layer_types`` (``mamba`` a Mamba-2 mixer, the
    ``mamba_*`` keys; ``attention`` causal GQA without positional
    embedding) and then a gated SiLU MLP (``shared_intermediate_size``
    wide), each sublayer's output times ``residual_multiplier``; the
    embeddings times ``embedding_multiplier``, the attention scores
    times ``attention_multiplier`` (not ``1 / sqrt(head_dim)``), the
    logits of the tied head divided by ``logits_scaling``. The family's
    larger files route experts beside the shared MLP
    (``num_local_experts > 0``): not read, refused by name."""
    if int(cfg.get("num_local_experts") or 0):
        raise ValueError(
            f"num_local_experts {cfg['num_local_experts']}: a "
            "granitemoehybrid stack is served with its shared MLP alone "
            "(num_local_experts 0); routed experts beside it are not read"
        )
    for key, want in (
        ("position_embedding_type", "nope"), ("hidden_act", "silu"),
        ("attention_bias", False), ("mamba_proj_bias", False),
        ("mamba_conv_bias", True), ("normalization_function", "rmsnorm"),
    ):
        if cfg.get(key, want) != want:
            raise ValueError(
                f"{key} {cfg[key]!r}: a granitemoehybrid stack is served "
                f"with {want!r} only"
            )
    kinds = {"mamba": "mamba", "attention": "full_attention"}
    layer_types = tuple(cfg.get("layer_types") or ())
    if set(layer_types) - set(kinds):
        raise ValueError(
            f"layer_types {sorted(set(layer_types))}: only mamba and "
            "attention layers are served"
        )
    if len(layer_types) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layer_types has {len(layer_types)} layers, "
            f"num_hidden_layers says {cfg['num_hidden_layers']}"
        )
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    m_heads, m_dim = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if m_heads * m_dim != int(cfg.get("mamba_expand", 2)) * hidden:
        raise ValueError(
            f"mamba_n_heads {m_heads} x mamba_d_head {m_dim} is not "
            f"mamba_expand x hidden_size"
        )
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=hidden,
        intermediate_size=int(
            cfg.get("shared_intermediate_size") or cfg["intermediate_size"]
        ),
        num_layers=cfg["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or hidden // heads,
        rope=False,
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", True),
        max_position_embeddings=cfg.get("max_position_embeddings", 8192),
        layer_types=tuple(kinds[t] for t in layer_types),
        mamba_num_heads=m_heads,
        mamba_head_dim=m_dim,
        ssm_state_size=int(cfg["mamba_d_state"]),
        mamba_n_groups=int(cfg.get("mamba_n_groups") or 1),
        conv_kernel=int(cfg.get("mamba_d_conv") or 4),
        ssm_chunk_size=int(cfg.get("mamba_chunk_size") or 256),
        embed_multiplier=float(cfg.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(cfg.get("residual_multiplier", 1.0)),
        logit_scale=1.0 / float(cfg.get("logits_scaling", 1.0)),
        query_pre_attn_scalar=(
            float(cfg["attention_multiplier"]) ** -2
            if cfg.get("attention_multiplier") else 0.0
        ),
    ).validate()


def _solar_open2_config(cfg: Dict[str, Any], name: str) -> ModelConfig:
    """Solar-Open2 (``model_type: solar_open2``): every layer a mixer and
    then routed experts under one shared expert (the DeepSeek-V3
    router's keys: sigmoid scores, a correction bias, the chosen scores
    normalised). The layers ``gqa_layers`` lists are causal GQA without
    positional embedding (``use_rope: false``) whose output passes a
    sigmoid gate (``use_gqa_gate``); every other layer is a KDA
    linear-attention mixer (``linear_attn_config``; Kimi Linear,
    arXiv:2510.26692): the gated delta rule with **one decay a head and
    key channel**, the decay's and the gate's projections through a
    bottleneck (``kda_use_full_proj: false``), the gate a sigmoid.

    What the file has no key for is assumed, each a field of
    :class:`ModelConfig` or a line here, and listed with its other
    reading in ``perfbench/configs/solar-open2-250b-int8-ep8-l12/
    deployment.json``: the bottleneck's rank is the linear heads'
    ``head_dim``; the shared expert is ``n_shared_experts *
    moe_intermediate_size`` wide (``intermediate_size`` belongs to
    leading dense layers, of which a served file has none); no norm on
    q and k.

    One chip's share of the experts is ``n_routed_experts`` (how many
    are held here) beside ``experts_held: {"of", "first"}``, as the
    Nemotron-H files state it and for its reason."""
    def refuse(key, want, group=cfg):
        if group.get(key, want) != want:
            raise ValueError(
                f"{key} {group[key]!r}: a solar_open2 stack is served with "
                f"{want!r} only"
            )

    refuse("kda_use_full_proj", False)
    refuse("first_k_dense_replace", 0)
    refuse("hidden_act", "silu")
    refuse("attention_bias", False)
    refuse("use_rope", False)
    linear = cfg.get("linear_attn_config") or {}
    heads_l = int(linear["num_heads"])
    refuse("num_kv_heads", None, linear)
    n = cfg["num_hidden_layers"]
    full = sorted(int(i) for i in cfg.get("gqa_layers") or ())
    if full and not 0 <= full[0] <= full[-1] < n:
        raise ValueError(
            f"gqa_layers {full}: num_hidden_layers says {n} layers"
        )
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    share = cfg.get("experts_held") or {}
    held = int(cfg["n_routed_experts"])
    shared = int(cfg.get("n_shared_experts") or 0)
    width = int(cfg["moe_intermediate_size"])
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=hidden,
        intermediate_size=cfg.get("intermediate_size", 0),
        num_layers=n,
        num_heads=heads,
        num_kv_heads=cfg.get("num_key_value_heads", heads),
        head_dim=cfg.get("head_dim") or hidden // heads,
        rope=False,
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        max_position_embeddings=cfg.get("max_position_embeddings", 8192),
        num_experts=int(share["of"]) if share else held,
        experts_held=held if share else 0,
        first_held_expert=int(share.get("first", 0)),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=width,
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        n_shared_experts=shared,
        shared_expert_intermediate_size=shared * width,
        routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
        moe_scoring="sigmoid",
        layer_types=tuple(
            "full_attention" if i in full else "linear_attention"
            for i in range(n)
        ),
        linear_num_key_heads=heads_l,
        linear_num_value_heads=heads_l,
        linear_key_head_dim=int(linear["head_dim"]),
        linear_value_head_dim=int(linear["head_dim"]),
        linear_conv_kernel_dim=int(linear.get("short_conv_kernel_size") or 4),
        linear_allow_neg_eigval=bool(cfg.get("kda_allow_neg_eigval")),
        linear_decay_a_channel=True,
        linear_low_rank=int(linear["head_dim"]),
        linear_gate_act="sigmoid",
        attn_output_gate=bool(cfg.get("use_gqa_gate")),
    ).validate()


def _sdar_moe_config(
    cfg: Dict[str, Any], name: str, generation: Optional[Dict[str, Any]]
) -> ModelConfig:
    """SDAR-MoE (``model_type: sdar_moe``): Qwen3-MoE's layers to the
    number, generated by diffusion over blocks. The file is read as a
    Qwen3-MoE file; what differs is no key of it but which keys a query
    sees and what a step is (:attr:`ModelConfig.diffusion_block`), and
    those numbers are a deployment's generation defaults: ``generation``,
    the ``generation_config.json`` beside the file where there is one
    (``block_length``, ``denoising_steps``, ``remasking_strategy``,
    ``confidence_threshold``, ``mask_token_id``), else the released Chat
    checkpoints' (blocks of 4 in 4 steps, ``low_confidence_dynamic`` at
    0.9, the family tokenizer's ``<|MASK|>``).

    Refused by key, since no layer here does it: a sliding window, dense
    layers among the experts' (``mlp_only_layers``, ``decoder_sparse_step``
    other than 1)."""
    for key, want in (
        ("use_sliding_window", False), ("mlp_only_layers", []),
        ("decoder_sparse_step", 1),
    ):
        if (cfg.get(key) or want) != want:
            raise ValueError(
                f"{key} {cfg[key]!r}: an sdar_moe stack is served with "
                f"{want!r} only"
            )
    gen = generation or {}
    base = config_from_hf(
        {**cfg, "architectures": ["Qwen3MoeForCausalLM"],
         "model_type": "qwen3_moe", "sliding_window": None}, name,
    )
    return dataclasses.replace(
        base,
        diffusion_block=int(gen.get("block_length", 4)),
        denoising_steps=int(gen.get("denoising_steps", 4)),
        remasking_strategy=str(
            gen.get("remasking_strategy", "low_confidence_dynamic")
        ),
        confidence_threshold=float(gen.get("confidence_threshold", 0.9)),
        mask_token_id=int(gen.get("mask_token_id", 151669)),
    ).validate()


def config_from_hf(
    cfg: Dict[str, Any], name: str = "custom",
    generation: Optional[Dict[str, Any]] = None,
) -> ModelConfig:
    """Build a ModelConfig from an HF ``config.json`` dict of one of
    :data:`FAMILIES` (the reference's selectors introspect the same
    keys, base_candidate_selector.py:56-165). An architecture of no
    family listed is refused by name. ``generation``: the directory's
    ``generation_config.json``, for the one family whose reader takes
    its numbers from it (:func:`_sdar_moe_config`)."""
    archs = cfg.get("architectures") or [""]
    arch = archs[0] if archs else ""
    if arch and not any(f in arch for f in FAMILIES):
        raise ValueError(
            f"architecture {arch!r} is of no family this engine reads "
            f"({', '.join(FAMILIES)}); it is not served as a Llama-class "
            "stack"
        )
    if "NemotronH" in arch or cfg.get("model_type") == "nemotron_h":
        return _nemotron_h_config(cfg, name)
    if "Cohere2Moe" in arch or cfg.get("model_type") == "cohere2_moe":
        return _cohere2_moe_config(cfg, name)
    if "OlmoHybrid" in arch or cfg.get("model_type") == "olmo_hybrid":
        return _olmo_hybrid_config(cfg, name)
    if (
        "GraniteMoeHybrid" in arch
        or cfg.get("model_type") == "granitemoehybrid"
    ):
        return _granite_hybrid_config(cfg, name)
    if "SolarOpen2" in arch or cfg.get("model_type") == "solar_open2":
        return _solar_open2_config(cfg, name)
    if "SDARMoe" in arch or cfg.get("model_type") == "sdar_moe":
        return _sdar_moe_config(cfg, name, generation)
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or hidden // heads
    num_experts = (
        cfg.get("num_local_experts")      # Mixtral
        or cfg.get("num_experts")         # Qwen2-MoE
        or cfg.get("n_routed_experts")    # DeepSeek-V2/V3
        or 0
    )
    # the DeepSeek-V2/V3 family; a model that ships its layers unchanged
    # under another name (A.X-K1) is served from a file that names the
    # family (perfbench/configs/ax-k1-int8-ep16-l12/deployment.json)
    deepseek = "Deepseek" in arch
    mla = deepseek and int(cfg.get("kv_lora_rank") or 0) > 0
    if mla:
        qk_nope = int(cfg.get("qk_nope_head_dim") or 0)
        qk_rope = int(cfg.get("qk_rope_head_dim") or 0)
        # the width of a decompressed query or key head
        head_dim = qk_nope + qk_rope
    # one chip's share of the experts: the file's expert count is what
    # is held here, and ``experts_held`` beside it states the router's
    # published width and the first held id
    share = cfg.get("experts_held") or {}
    experts_held = num_experts if share else 0
    if share:
        num_experts = int(share["published"])
    # Gemma2/Gemma3 text: (1+w) norms, scaled embeddings, sandwich
    # norms, gelu-tanh MLP, softcapping (gemma2), alternating
    # sliding/full layers, dual rope thetas (gemma3).  Gemma1
    # ("GemmaForCausalLM") shares the (1+w)-norm and sqrt(d)
    # embed-scale conventions but has no post-norms / softcap /
    # sliding layers — it must still take the gemma norm path or it
    # serves silently-wrong logits.
    gemma2plus = "Gemma2" in arch or "Gemma3" in arch
    gemma1 = arch == "GemmaForCausalLM"
    gemma = gemma2plus or gemma1
    # GPT-OSS: attention sinks, alternating sliding/full layers, biased
    # attention + router + experts, clamped-glu MoE, YaRN rope
    # (modeling_gpt_oss)
    gptoss = "GptOss" in arch
    layer_types = cfg.get("layer_types")
    layer_sliding = (
        tuple(t == "sliding_attention" for t in layer_types)
        if (gemma2plus or gptoss) and layer_types
        else None
    )
    if gemma2plus and layer_sliding is None:
        # original-release hub configs serialize no layer_types; derive
        # the pattern the way transformers does — gemma3:
        # sliding_window_pattern (every Nth layer is global), gemma2:
        # alternating starting sliding at layer 0
        L = cfg["num_hidden_layers"]
        pat = (
            int(cfg.get("sliding_window_pattern") or 6)
            if "Gemma3" in arch
            else 2
        )
        layer_sliding = tuple(bool((i + 1) % pat) for i in range(L))
    if gptoss and layer_sliding is None:
        # a stripped config without layer_types must NOT fall through
        # to the global-window branch (it would window the
        # full-attention layers too — silently wrong past 128 tokens);
        # GptOssConfig's own default is alternating starting sliding
        layer_sliding = tuple(
            i % 2 == 0 for i in range(cfg["num_hidden_layers"])
        )
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=hidden,
        intermediate_size=cfg.get("intermediate_size", 4 * hidden),
        num_layers=cfg["num_hidden_layers"],
        num_heads=heads,
        # MLA decompresses to one K/V head a query head
        num_kv_heads=(
            heads if mla
            else cfg.get("num_key_value_heads", heads)
        ),
        head_dim=head_dim,
        rope_theta=cfg.get("rope_theta", 10000.0),
        rope_scaling=cfg.get("rope_scaling"),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        qkv_bias=(
            ("Qwen2" in arch and not cfg.get("no_bias", False))
            or (gptoss and cfg.get("attention_bias", True))
        ),
        o_bias=gptoss and bool(cfg.get("attention_bias", True)),
        attn_sinks=gptoss,
        moe_act="gptoss" if gptoss else "silu",
        moe_bias=gptoss,
        # Qwen3 (dense + MoE) and Gemma3 replace attention bias with
        # per-head q/k RMSNorm
        qk_norm="Qwen3" in arch or "Gemma3" in arch,
        max_position_embeddings=cfg.get("max_position_embeddings", 8192),
        sliding_window=cfg.get("sliding_window") or 0,
        hidden_act=(
            "gelu_tanh"
            if cfg.get("hidden_activation") == "gelu_pytorch_tanh"
            or cfg.get("hidden_act") == "gelu_pytorch_tanh"
            # original gemma1 hub configs say "gelu" but the released
            # weights were trained with the tanh approximation
            or (gemma and cfg.get("hidden_act") in (None, "gelu"))
            else "silu"
        ),
        norm_delta_gain=gemma,
        embed_multiplier=math.sqrt(hidden) if gemma else 1.0,
        post_norms=gemma2plus,
        query_pre_attn_scalar=(
            float(cfg.get("query_pre_attn_scalar") or 0) if gemma2plus else 0.0
        ),
        attn_logit_softcap=float(cfg.get("attn_logit_softcapping") or 0),
        final_logit_softcap=float(cfg.get("final_logit_softcapping") or 0),
        layer_sliding=layer_sliding,
        rope_local_theta=float(cfg.get("rope_local_base_freq") or 0),
        num_experts=num_experts,
        num_experts_per_tok=cfg.get("num_experts_per_tok", 0),
        moe_intermediate_size=(
            cfg.get("moe_intermediate_size")
            or (cfg.get("intermediate_size", 0) if num_experts else 0)
        ),
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        q_lora_rank=int(cfg.get("q_lora_rank") or 0) if deepseek else 0,
        kv_lora_rank=int(cfg.get("kv_lora_rank") or 0) if deepseek else 0,
        qk_nope_head_dim=(
            int(cfg.get("qk_nope_head_dim") or 0) if deepseek else 0
        ),
        qk_rope_head_dim=(
            int(cfg.get("qk_rope_head_dim") or 0) if deepseek else 0
        ),
        v_head_dim=int(cfg.get("v_head_dim") or 0) if deepseek else 0,
        n_shared_experts=(
            int(cfg.get("n_shared_experts") or 0) if deepseek
            else (1 if cfg.get("shared_expert_intermediate_size") else 0)
        ),
        shared_expert_intermediate_size=(
            int(cfg.get("n_shared_experts") or 0)
            * int(cfg.get("moe_intermediate_size") or 0)
            if deepseek
            # Qwen2-MoE: explicit width key
            else int(cfg.get("shared_expert_intermediate_size") or 0)
        ),
        shared_expert_gated="Qwen2Moe" in arch,
        routed_scaling_factor=(
            float(cfg.get("routed_scaling_factor") or 1.0)
            if deepseek else 1.0
        ),
        first_k_dense=(
            int(cfg.get("first_k_dense_replace") or 0)
            if deepseek and num_experts else 0
        ),
        moe_scoring=(
            "sigmoid"
            if deepseek and cfg.get("scoring_func") == "sigmoid"
            else ("softmax_topk" if gptoss else "softmax")
        ),
        # any topk_method: the family's public port selects within the
        # kept groups whatever the key says
        n_group=int(cfg.get("n_group") or 1) if deepseek else 1,
        topk_group=int(cfg.get("topk_group") or 1) if deepseek else 1,
        experts_held=experts_held,
        first_held_expert=int(share.get("first", 0)),
    ).validate()


def load_hf_config(path: str, name: str = "") -> ModelConfig:
    """Read ``config.json`` from a local HF model directory."""
    with open(os.path.join(path, "config.json")) as f:
        cfg = json.load(f)
    generation = None
    beside = os.path.join(path, "generation_config.json")
    if cfg.get("model_type") == "sdar_moe" and os.path.exists(beside):
        # the hub's place for a model's generation defaults; only this
        # family's reader takes numbers from it
        with open(beside) as f:
            generation = json.load(f)
    return config_from_hf(
        cfg, name=name or os.path.basename(path.rstrip("/")),
        generation=generation,
    )


# ---------------------------------------------------------------------------
# Presets. Flagship = llama3-8b (BASELINE.md north-star model). Tiny configs
# are for hermetic CPU tests (mirrors the reference's fixture doctrine,
# SURVEY.md §4).
# ---------------------------------------------------------------------------
PRESETS: Dict[str, ModelConfig] = {
    "llama3-8b": ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=8192,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=8192,
    ),
    "qwen2.5-7b": ModelConfig(
        name="qwen2.5-7b",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        qkv_bias=True,
        tie_word_embeddings=False,
        max_position_embeddings=32768,
    ),
    # BASELINE anchor family: the reference's closest published 8B number
    # is Qwen3-8B (docs/performance-lab/qwen3-8b/910b.md:95-98).
    "qwen3-8b": ModelConfig(
        name="qwen3-8b",
        vocab_size=151936,
        hidden_size=4096,
        intermediate_size=12288,
        num_layers=36,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        qk_norm=True,
        max_position_embeddings=40960,
    ),
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b",
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=6144,
        num_layers=48,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        qk_norm=True,
        num_experts=128,
        num_experts_per_tok=8,
        moe_intermediate_size=768,
        norm_topk_prob=True,
        max_position_embeddings=40960,
    ),
    "gemma2-9b": ModelConfig(
        name="gemma2-9b",
        vocab_size=256000,
        hidden_size=3584,
        intermediate_size=14336,
        num_layers=42,
        num_heads=16,
        num_kv_heads=8,
        head_dim=256,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        hidden_act="gelu_tanh",
        norm_delta_gain=True,
        embed_multiplier=math.sqrt(3584),
        post_norms=True,
        query_pre_attn_scalar=256.0,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        sliding_window=4096,
        layer_sliding=tuple(i % 2 == 0 for i in range(42)),
        max_position_embeddings=8192,
    ),
    # GPT-OSS (openai/gpt-oss-20b — BASELINE.md headline anchor,
    # docs/performance-lab/gpt-oss-20b/a100.md): attention sinks,
    # alternating sliding/full layers, biased everything, clamped-glu
    # MoE, YaRN truncate=false. Hub dims from GptOssConfig.
    "gpt-oss-20b": ModelConfig(
        name="gpt-oss-20b",
        vocab_size=201088,
        hidden_size=2880,
        intermediate_size=2880,
        num_layers=24,
        num_heads=64,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=150000.0,
        rope_scaling={
            "rope_type": "yarn", "factor": 32.0,
            "beta_fast": 32.0, "beta_slow": 1.0,
            "truncate": False,
            "original_max_position_embeddings": 4096,
        },
        rms_norm_eps=1e-5,
        max_position_embeddings=131072,
        sliding_window=128,
        layer_sliding=tuple(i % 2 == 0 for i in range(24)),
        qkv_bias=True,
        o_bias=True,
        attn_sinks=True,
        num_experts=32,
        num_experts_per_tok=4,
        moe_intermediate_size=2880,
        moe_scoring="softmax_topk",
        moe_act="gptoss",
        moe_bias=True,
    ),
    "gpt-oss-120b": ModelConfig(
        name="gpt-oss-120b",
        vocab_size=201088,
        hidden_size=2880,
        intermediate_size=2880,
        num_layers=36,
        num_heads=64,
        num_kv_heads=8,
        head_dim=64,
        rope_theta=150000.0,
        rope_scaling={
            "rope_type": "yarn", "factor": 32.0,
            "beta_fast": 32.0, "beta_slow": 1.0,
            "truncate": False,
            "original_max_position_embeddings": 4096,
        },
        rms_norm_eps=1e-5,
        max_position_embeddings=131072,
        sliding_window=128,
        layer_sliding=tuple(i % 2 == 0 for i in range(36)),
        qkv_bias=True,
        o_bias=True,
        attn_sinks=True,
        num_experts=128,
        num_experts_per_tok=4,
        moe_intermediate_size=2880,
        moe_scoring="softmax_topk",
        moe_act="gptoss",
        moe_bias=True,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=14336,
        max_position_embeddings=32768,
    ),
    # DeepSeek-V2-Lite (deepseek-ai/DeepSeek-V2-Lite): MLA + DeepSeek
    # MoE, served over the latent cache (see the MLA notes on
    # ModelConfig)
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400,
        hidden_size=2048,
        intermediate_size=10944,
        num_layers=27,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,                 # qk_nope + qk_rope
        rope_theta=10000.0,
        rms_norm_eps=1e-6,            # hub config.json value
        # the shipped YaRN scaling (hub config.json rope_scaling)
        rope_scaling={
            "type": "yarn", "factor": 40,
            "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096,
        },
        max_position_embeddings=163840,
        num_experts=64,
        num_experts_per_tok=6,
        moe_intermediate_size=1408,
        norm_topk_prob=False,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_shared_experts=2,
        shared_expert_intermediate_size=2816,
        routed_scaling_factor=1.0,
        first_k_dense=1,
    ),
    # Hermetic-test configs (run everywhere, compile in seconds).
    "tiny": ModelConfig(
        name="tiny",
        vocab_size=264,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        max_position_embeddings=256,
    ),
    "tiny-qwen3": ModelConfig(
        name="tiny-qwen3",
        vocab_size=264,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        qk_norm=True,
        max_position_embeddings=256,
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe",
        vocab_size=264,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=96,
        max_position_embeddings=256,
    ),
}


def get_config(name: str) -> ModelConfig:
    if name in PRESETS:
        return PRESETS[name]
    raise KeyError(
        f"unknown model preset {name!r}; known: {sorted(PRESETS)}"
    )
