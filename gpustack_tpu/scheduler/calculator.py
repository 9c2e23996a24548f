"""HBM resource estimation: model spec → chips + mesh plan + bytes.

Replaces the reference's gguf-parser pipeline (reference
gpustack/scheduler/calculator.py shells out to a Go binary for layer-wise
VRAM estimates): on TPU the claim is weights + KV cache + activation
headroom against HBM per chip, and the output is a mesh plan whose product
is chips-per-replica.

Weight/KV math comes from ModelConfig (exact parameter counts, attention-
type-aware KV sizing — the reference's selector parses the same
hyperparameters, base_candidate_selector.py:56-165). When a local
checkpoint directory is present, the native ``model-meta`` tool (C++,
native/) supplies exact safetensors tensor sizes instead.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Optional

from gpustack_tpu.models.config import (
    ModelConfig,
    PRESETS,
    config_from_hf,
)
from gpustack_tpu.parallel.mesh import MeshPlan, plan_mesh
from gpustack_tpu.schemas import ComputedResourceClaim, Model

logger = logging.getLogger(__name__)

# Fraction of per-chip HBM the engine may plan against (the rest covers
# activations, XLA scratch, and fragmentation) — analogue of vLLM's
# gpu-memory-utilization handled by the reference selector.
HBM_UTILIZATION = 0.9


class EvaluationError(Exception):
    """Model cannot be evaluated (bad source, unknown architecture...)."""


@dataclasses.dataclass
class ModelEvaluation:
    config: ModelConfig
    weight_bytes: int
    kv_cache_bytes: int
    overhead_bytes: int

    @property
    def total_bytes(self) -> int:
        return self.weight_bytes + self.kv_cache_bytes + self.overhead_bytes


def resolve_raw_config(model: Model) -> Optional[dict]:
    """Raw HF-style ``config.json`` dict for the model's source, or None
    when the source has no such file (presets; diffusers layouts, whose
    ``model_index.json`` is handled by ``resolve_model_config``).

    Network sources are disk-cached (hf_hub cache / the ModelScope
    config cache), so callers may use this freely on every reconcile.
    """
    if model.preset:
        return None
    if model.local_path:
        if os.path.exists(
            os.path.join(model.local_path, "model_index.json")
        ):
            return None
        import json as _json

        try:
            with open(
                os.path.join(model.local_path, "config.json")
            ) as f:
                return _json.load(f)
        except (OSError, ValueError) as e:
            raise EvaluationError(
                f"cannot read config from {model.local_path}: {e}"
            )
    if model.huggingface_repo_id:
        # Fetch just config.json (tiny; hf_hub caches it, so offline
        # re-evaluation works once cached) — the reference does the same
        # HF-config probing server-side (scheduler/evaluator.py HF rate
        # limiter).
        import json as _json

        try:
            from huggingface_hub import hf_hub_download

            path = hf_hub_download(
                model.huggingface_repo_id, "config.json"
            )
            with open(path) as f:
                return _json.load(f)
        except Exception as e:
            raise EvaluationError(
                f"cannot fetch config for "
                f"{model.huggingface_repo_id!r}: {e}"
            )
    if model.model_scope_model_id:
        return _modelscope_config_cached(model.model_scope_model_id)
    raise EvaluationError(
        "model has no source (preset/local_path/hf/modelscope)"
    )


def resolve_model_config(model: Model, raw: Optional[dict] = None):
    """Model spec → engine config. ``raw`` lets callers that already
    fetched the raw config dict (model_registry.detect_categories) skip
    a second source resolution."""
    from gpustack_tpu.models.diffusion import (
        DIFFUSION_PRESETS,
        config_from_diffusers,
    )
    from gpustack_tpu.models.whisper import (
        WHISPER_PRESETS,
        config_from_hf_whisper,
    )

    from gpustack_tpu.models.tts import TTS_PRESETS
    from gpustack_tpu.models.vlm import VLM_PRESETS, get_vlm_config

    if model.preset:
        if model.preset in WHISPER_PRESETS:
            return WHISPER_PRESETS[model.preset]
        if model.preset in TTS_PRESETS:
            return TTS_PRESETS[model.preset]
        if model.preset in VLM_PRESETS:
            # placement math runs on the language half (the tower is a
            # rounding error next to the LLM weights + KV cache)
            return get_vlm_config(model.preset).language
        if model.preset in DIFFUSION_PRESETS:
            return DIFFUSION_PRESETS[model.preset]
        if model.preset not in PRESETS:
            raise EvaluationError(f"unknown preset {model.preset!r}")
        return PRESETS[model.preset]
    if raw is None:
        raw = resolve_raw_config(model)
    if raw is None:
        from gpustack_tpu.engine.gguf import config_from_gguf, gguf_file_in

        gguf_path = gguf_file_in(model.local_path or "")
        if gguf_path:
            try:
                return config_from_gguf(gguf_path, name=model.name)
            except ValueError as e:
                raise EvaluationError(str(e))
        # diffusers-format layout = image pipeline
        return config_from_diffusers(model.local_path, name=model.name)
    name = (
        model.huggingface_repo_id
        or model.model_scope_model_id
        or model.name
        or os.path.basename(str(model.local_path).rstrip("/"))
    )
    try:
        if raw.get("model_type") == "whisper":
            return config_from_hf_whisper(raw, name=model.name or name)
        if raw.get("model_type") in ("tts", "fastspeech"):
            # in-repo TTS checkpoint format: config.json names a preset
            # (same contract as build_audio_engine_from_args)
            preset = raw.get("preset", "tts-base")
            if preset not in TTS_PRESETS:
                raise EvaluationError(f"unknown TTS preset {preset!r}")
            return TTS_PRESETS[preset]
        return config_from_hf(raw, name=name)
    except (KeyError, ValueError) as e:
        raise EvaluationError(
            f"unsupported model config for {name!r}: {e}"
        )


def _modelscope_config_cached(model_id: str) -> dict:
    """config.json for a ModelScope model, disk-cached like the HF
    branch (hf_hub_download caches): repeat evaluations don't re-hit the
    network, and offline re-evaluation keeps working once cached."""
    import json as _json
    import re as _re

    safe = _re.sub(r"[^A-Za-z0-9_.-]", "--", model_id)
    cache_dir = os.path.join(
        os.path.expanduser("~"), ".cache", "gpustack_tpu", "ms-configs"
    )
    cache = os.path.join(cache_dir, safe + ".json")
    if os.path.exists(cache):
        try:
            with open(cache) as f:
                return _json.load(f)
        except (OSError, ValueError):
            pass
    from gpustack_tpu.worker.downloaders import modelscope_fetch_config

    try:
        raw = modelscope_fetch_config(model_id)
    except Exception as e:
        raise EvaluationError(
            f"cannot fetch config for {model_id!r}: {e}"
        )
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(cache + ".tmp", "w") as f:
            _json.dump(raw, f)
        os.replace(cache + ".tmp", cache)
    except OSError:
        pass
    return raw


from gpustack_tpu.utils.profiling import timed

# KV slots a PREFILL-role replica plans for: it computes prompt KV and
# hands it off rather than decoding a full continuous batch, so a
# couple of in-flight prefills bound its resident KV. This is what
# makes context length a real placement dimension per role — a 32k-
# context model's decode replicas claim the full ``max_slots`` KV
# while its prefill replicas fit on fewer chips.
PREFILL_ROLE_KV_SLOTS = 2


@timed(threshold_s=5.0, name="scheduler.evaluate_model")
def evaluate_model(model: Model, role: str = "") -> ModelEvaluation:
    """HBM claim for one replica. ``role`` (disaggregated serving) is
    a KV-sizing dimension: prefill-role replicas hold at most
    ``PREFILL_ROLE_KV_SLOTS`` sequences of KV; decode/colocated
    replicas hold ``max_slots``."""
    cfg = resolve_model_config(model)
    weight_bits = 8 if model.quantization == "int8" else 16
    weight_bytes = cfg.weight_bytes(weight_bits)
    if model.local_path:
        # exact accounting from the native model-meta tool (checkpoint
        # tensors on disk beat config-derived estimates)
        from gpustack_tpu.utils.native import run_model_meta

        meta = run_model_meta(model.local_path)
        if meta and meta.get("total_bytes"):
            disk_bytes = int(meta["total_bytes"])
            if model.quantization == "int8":
                # engine int8 quantization only shrinks 16/32-bit float
                # tensors; already-quantized checkpoint bytes (GGUF Q*,
                # int8 safetensors) load as-is
                by_dtype = meta.get("bytes_by_dtype") or {}
                wide = sum(
                    v for k, v in by_dtype.items()
                    if k in ("F16", "BF16", "F32", "F64")
                )
                narrow = disk_bytes - wide
                disk_bytes = narrow + wide // 2 + wide // 256
            weight_bytes = disk_bytes
    # KV buffers follow the model's compute dtype: KVCache.create
    # allocates bf16 only for dtype == "bfloat16" and fp32 for anything
    # else, so mirror that exact rule or fp32 deployments undercount 2x
    kv_bits = 16 if getattr(cfg, "dtype", "bfloat16") == "bfloat16" else 32
    kv_slots = model.max_slots
    if role == "prefill":
        kv_slots = min(model.max_slots, PREFILL_ROLE_KV_SLOTS)
    kv_bytes = (
        cfg.kv_cache_bytes_per_token(kv_bits)
        * model.max_seq_len
        * kv_slots
    )
    # what a slot keeps beside the rows of kv_cache_bytes_per_token's
    # layers, whatever its length (a recurrent state; min(window,
    # context) rows of every sliding layer whose rows are kept at window
    # size); the audio configurations have no such method
    beside_bytes = getattr(cfg, "beside_bytes_per_slot", None)
    if beside_bytes is not None:
        kv_bytes += beside_bytes(model.max_seq_len, kv_bits) * kv_slots
    # activation + runtime overhead: prefill attention scratch dominates;
    # scale with seq len, floor at 256 MiB (audio configs use d_model)
    hidden = getattr(cfg, "hidden_size", 0) or cfg.d_model
    overhead = max(
        256 * 2**20,
        int(2 * model.max_seq_len * hidden * 4 * 8),
    )
    return ModelEvaluation(
        config=cfg,
        weight_bytes=weight_bytes,
        kv_cache_bytes=kv_bytes,
        overhead_bytes=overhead,
    )


def fleet_chip_budget(workers, distributable: bool):
    """(max_chips, allowed_counts) for a filtered fleet.

    ``allowed_counts`` = per-worker ICI-tileable sub-slice sizes
    (policies/topology) plus, for distributable models, power-of-two
    whole-host multiples across a slice (plan_mesh only factors
    power-of-two device counts, so a 3-host 24-chip placement is not
    claimable even though the hosts exist). Shared by the scheduler and
    the /evaluate API so the preview claim always matches what placement
    would actually do.
    """
    from gpustack_tpu.policies.topology import tileable_counts

    max_single = max(w.total_chips for w in workers)
    max_chips = max_single
    allowed: set = set()
    for w in workers:
        sl = w.status.slice
        allowed |= tileable_counts(
            sl.topology if sl else "", w.total_chips
        )
    if distributable:
        domains: dict = {}
        for w in workers:
            sl = w.status.slice
            if sl and sl.ici_domain:
                domains[sl.ici_domain] = (
                    domains.get(sl.ici_domain, 0) + w.total_chips
                )
        if domains:
            max_chips = max(max_chips, max(domains.values()))
        for w in workers:
            sl = w.status.slice
            if sl and sl.ici_domain and w.total_chips:
                n = w.total_chips * 2
                while n <= max_chips:
                    allowed.add(n)
                    n *= 2
    return max_chips, allowed


def chips_for_claim(
    evaluation: ModelEvaluation,
    hbm_per_chip: int,
    max_chips: int,
    long_context: bool = False,
    explicit_plan: str = "",
    explicit_chips: int = 0,
    allowed_counts: Optional[set] = None,
) -> Optional[ComputedResourceClaim]:
    """Pick chips-per-replica (power of two) and a mesh plan that fits.

    Returns None when the model cannot fit on ``max_chips`` chips.
    Mirrors the reference's candidate ladder (manual → 1 GPU → multi-GPU →
    multi-worker, vllm_resource_fit_selector.py:315-341) but in chip space:
    the smallest power-of-two chip count whose per-chip share fits HBM.

    ``allowed_counts`` (from policies/topology.tileable_counts over the
    eligible fleet) restricts the ladder to chip counts that actually
    tile some worker's ICI mesh — a 2-chip claim on a 2x4 v5e host is
    unplaceable and must be bumped to 4, not discovered to be
    unschedulable later.
    """
    usable = int(hbm_per_chip * HBM_UTILIZATION)
    if usable <= 0:
        return None
    cfg = evaluation.config

    if explicit_plan:
        plan = MeshPlan.parse(explicit_plan)
        chips = plan.chips
        per_chip = evaluation.total_bytes // chips
        if chips <= max_chips and per_chip <= usable:
            return ComputedResourceClaim(
                chips=chips,
                mesh_plan=str(plan),
                hbm_bytes_per_chip=per_chip + _per_chip_overhead(evaluation, chips),
                weight_bytes=evaluation.weight_bytes,
                kv_cache_bytes=evaluation.kv_cache_bytes,
            )
        return None

    start = explicit_chips or 1
    chips = max(1, start)
    if getattr(cfg, "beside_rows", None):
        # a model that keeps something a slot beside its rows is served
        # on one device (engine/runner.py): more chips than one hold
        # nothing of it
        max_chips = min(max_chips, 1)
    while chips <= max_chips:
        if (
            allowed_counts is not None
            and chips not in allowed_counts
            and not explicit_chips
        ):
            chips *= 2
            continue
        # weights and KV shard across chips; overhead replicates
        per_chip = (
            (evaluation.weight_bytes + evaluation.kv_cache_bytes) // chips
            + evaluation.overhead_bytes
        )
        if per_chip <= usable:
            plan = plan_mesh(
                chips,
                num_kv_heads=cfg.num_kv_heads,
                num_experts=cfg.num_experts,
                long_context=long_context,
            )
            return ComputedResourceClaim(
                chips=chips,
                mesh_plan=str(plan),
                hbm_bytes_per_chip=per_chip,
                weight_bytes=evaluation.weight_bytes,
                kv_cache_bytes=evaluation.kv_cache_bytes,
            )
        if explicit_chips:
            return None  # user pinned the count; it doesn't fit
        chips *= 2
    return None


def _per_chip_overhead(evaluation: ModelEvaluation, chips: int) -> int:
    return evaluation.overhead_bytes
