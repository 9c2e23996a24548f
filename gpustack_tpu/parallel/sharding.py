"""PartitionSpec policies for the transformer params/activations/KV cache.

The single source of truth is :class:`SpecLayout` — a frozen dataclass
naming the mesh axes and producing every PartitionSpec the serving/
training paths use (params, activations, KV cache, per-slot decode
state, host-read outputs). ``runner.py`` holds one ``SpecLayout`` per
replica so the multi-chip layout is one inspectable object
(``layout.describe()``) instead of inline specs scattered through the
engine.

Two modes:

- ``inference``: Megatron-style TP (heads + FFN width over ``tp``, experts
  over ``ep``), weights replicated over ``dp``/``sp``.
- ``train``: additionally FSDP-shards every large weight over ``dp`` on a
  non-TP dimension; under jit XLA all-gathers weights before use and
  reduce-scatters grads — ZeRO-3 semantics with zero hand-written
  collectives.

The specs are written against the param tree produced by
``models.transformer.init_params`` (stacked ``[L, ...]`` leaves; the layer
axis is never sharded — it is the scan axis).

The module-level helpers (``param_pspecs``/``cache_pspec``/…) are thin
wrappers over a default-axes ``SpecLayout``, kept for the existing call
sites.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from gpustack_tpu.parallel.mesh import AXIS_DP, AXIS_EP, AXIS_SP, AXIS_TP


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Declarative dp/sp/ep/tp axis assignment for one model replica.

    Every PartitionSpec the runner dispatches against derives from this
    object, so "how is this replica laid out across chips" has exactly
    one answer — renderable as a dict via :meth:`describe` (served on
    the engine's health surface).
    """

    dp_axis: str = AXIS_DP
    sp_axis: str = AXIS_SP
    ep_axis: str = AXIS_EP
    tp_axis: str = AXIS_TP
    # long-context serving: the KV cache's sequence dim shards over sp
    # for the whole generation (ring-attention prefill / merged decode)
    long_context: bool = False
    # training: dp doubles as the FSDP axis for large weights
    train: bool = False

    @property
    def fsdp_axis(self) -> Optional[str]:
        """The axis large weights FSDP-shard over (None at inference —
        weights replicate across dp)."""
        return self.dp_axis if self.train else None

    # ---- params ---------------------------------------------------------

    def layer_rules(self) -> Dict[str, P]:
        fsdp, tp, ep = self.fsdp_axis, self.tp_axis, self.ep_axis
        return {
            "attn_norm": P(None, None),
            "mlp_norm": P(None, None),
            "wq": P(None, fsdp, tp),
            "wk": P(None, fsdp, tp),
            "wv": P(None, fsdp, tp),
            "wo": P(None, tp, fsdp),
            "bq": P(None, tp),
            "bk": P(None, tp),
            "bv": P(None, tp),
            # per-head-dim q/k norms (Qwen3/Gemma3) are tiny: replicate
            "q_norm": P(None, None),
            "k_norm": P(None, None),
            # gemma sandwich norms: replicated like the other norm gains
            "post_attn_norm": P(None, None),
            "post_mlp_norm": P(None, None),
            "w_gate": P(None, fsdp, tp),
            "w_up": P(None, fsdp, tp),
            "w_down": P(None, tp, fsdp),
            "router": P(None, fsdp, None),
            "we_gate": P(None, ep, fsdp, tp),
            "we_up": P(None, ep, fsdp, tp),
            "we_down": P(None, ep, tp, fsdp),
            # DeepSeek MLA: down-projections are small (rank-sized) —
            # replicate; up-projections shard their head-concat dim over tp
            "wq_a": P(None, fsdp, None),
            "q_a_norm": P(None, None),
            "wq_b": P(None, None, tp),
            "wkv_a": P(None, fsdp, None),
            "kv_a_norm": P(None, None),
            "wk_b": P(None, None, tp),
            "wv_b": P(None, None, tp),
            # DeepSeek shared experts: dense-MLP-shaped, same sharding
            "ws_gate": P(None, fsdp, tp),
            "ws_up": P(None, fsdp, tp),
            "ws_down": P(None, tp, fsdp),
            "shared_gate": P(None, None, None),
            "router_bias": P(None, None),
            # GPT-OSS: o-proj bias is hidden-wide (replicate with the
            # norms); sink logits are per-head tiny; expert biases shard
            # with their expert matrices (E over ep, F over tp)
            "bo": P(None, None),
            "sinks": P(None, None),
            "we_gate_b": P(None, ep, tp),
            "we_up_b": P(None, ep, tp),
            "we_down_b": P(None, ep, None),
        }

    def embed(self) -> P:
        return P(self.tp_axis, self.fsdp_axis)

    def lm_head(self) -> P:
        return P(self.fsdp_axis, self.tp_axis)

    def params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """PartitionSpec tree matching the param tree structure."""
        rules = self.layer_rules()
        specs: Dict[str, Any] = {
            "embed": self.embed(),
            "final_norm": P(None),
        }
        if "layers" in params:
            specs["layers"] = {k: rules[k] for k in params["layers"]}
        if "dense_layers" in params:
            # DeepSeek first_k_dense prefix stack (models/transformer.py)
            specs["dense_layers"] = {
                k: rules[k] for k in params["dense_layers"]
            }
        for stack in (
            "ssm_layers", "moe_layers", "attn_layers", "delta_layers"
        ):
            # the hybrid's three stacks (models/hybrid.py) and a stack
            # with ``layer_types``' two kinds of mixer, served on one
            # device: every leaf whole on it
            if stack in params:
                specs[stack] = {k: P() for k in params[stack]}
        if "lm_head" in params:
            specs["lm_head"] = self.lm_head()
        return specs

    # ---- activations / serving state ------------------------------------

    def activations(self, seq_sharded: bool = False) -> P:
        """[B, T, ...] activations: batch over dp, optionally sequence
        over sp."""
        return P(self.dp_axis, self.sp_axis if seq_sharded else None)

    def cache(self, latent: bool = False) -> P:
        """KV cache [L, B, S, H_kv, hd]: rows over dp, heads over tp;
        the sequence dim shards over sp in long-context mode (context
        parallelism as a first-class placement dimension — SURVEY.md
        §5). A ``latent`` cache (MLA) has one head for all query heads:
        nothing to divide over tp, every tp shard holds it whole."""
        return P(
            None, self.dp_axis,
            self.sp_axis if self.long_context else None,
            None if latent else self.tp_axis, None,
        )

    def slot_state(self) -> P:
        """Per-slot decode vectors (last_tokens/positions/active/
        sampling): tiny — replicated on every chip."""
        return P(None)

    def replicated(self) -> P:
        """Host-read outputs (sampled tokens, logprobs): forced fully
        replicated so multi-host fetches never span non-addressable
        devices."""
        return P()

    def describe(self) -> Dict[str, Any]:
        """The layout as one inspectable dict (engine health surface)."""
        return {
            "axes": {
                "dp": self.dp_axis, "sp": self.sp_axis,
                "ep": self.ep_axis, "tp": self.tp_axis,
            },
            "train": self.train,
            "long_context": self.long_context,
            "cache": str(self.cache()),
            "slot_state": str(self.slot_state()),
            "activations": str(self.activations(self.long_context)),
            "embed": str(self.embed()),
            "host_read": str(self.replicated()),
        }


def _layer_rules(train: bool) -> Dict[str, P]:
    return SpecLayout(train=train).layer_rules()


def param_pspecs(params: Dict[str, Any], train: bool = False) -> Dict[str, Any]:
    """PartitionSpec tree matching the param tree structure."""
    return SpecLayout(train=train).params(params)


def activation_pspec(seq_sharded: bool = False) -> P:
    """[B, T, ...] activations: batch over dp, optionally sequence over sp."""
    return SpecLayout().activations(seq_sharded)


def cache_pspec(long_context: bool = False) -> P:
    """KV cache [L, B, S, H_kv, hd] spec (see SpecLayout.cache)."""
    return SpecLayout(long_context=long_context).cache()


def logical_pspecs(
    params: Dict[str, Any],
    mesh: Mesh,
    train: bool = False,
) -> Dict[str, Any]:
    """NamedSharding tree for the params on ``mesh``."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_pspecs(params, train=train),
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(
    params: Dict[str, Any],
    mesh: Mesh,
    train: bool = False,
) -> Dict[str, Any]:
    """Place a (host-resident) param tree onto the mesh."""
    shardings = logical_pspecs(params, mesh, train=train)
    return jax.tree.map(jax.device_put, params, shardings)
