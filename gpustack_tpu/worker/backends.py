"""Engine backends: turn a scheduled instance into a launchable command.

Reference analogue: worker/backends/* subclassing InferenceServer
(base.py:150) — image/env/args resolution per engine. On TPU the launch
unit is a local process (the engine owns the chips via libtpu), so a
backend resolves an **argv + env**, not a container spec:

- ``tpu-native``: the in-repo engine (gpustack_tpu.engine.api_server) with
  mesh plan / quantization / context args derived from the placement.
- ``custom``: any command template from the InferenceBackend catalog
  (reference worker/backends/custom.py analogue).
"""

from __future__ import annotations

import logging
import os
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

from gpustack_tpu.schemas import Model, ModelInstance
from gpustack_tpu.schemas.inference_backends import (
    BackendVersionConfig,
    InferenceBackend,
)
from gpustack_tpu.utils.compile_cache import ENV_VAR as COMPILE_CACHE_ENV

logger = logging.getLogger(__name__)


def build_command(
    model: Model,
    instance: ModelInstance,
    port: int,
    backend: Optional[InferenceBackend],
    force_platform: str = "",
    process_index: int = 0,
    chip_indexes: Optional[List[int]] = None,
    cluster_secret: str = "",
) -> Tuple[List[str], Dict[str, str]]:
    """Resolve (argv, extra_env) for this instance.

    ``process_index``/``chip_indexes`` select the leader (0, instance
    chips) or a subordinate host's follower process of a multi-host
    replica. ``cluster_secret`` (the cluster registration token — shared
    by every worker, unknown to API users and outsiders) keys the
    derived multi-host command-channel auth token.
    """
    if model.backend in ("", "tpu-native"):
        return _tpu_native_command(
            model, instance, port, force_platform, process_index,
            chip_indexes, cluster_secret,
        )
    if backend is None:
        raise ValueError(f"unknown backend {model.backend!r}")
    vcfg = resolve_version_config(model, backend)
    if vcfg is None:
        raise ValueError(
            f"backend {model.backend!r} has no launch configuration"
        )
    return _render(vcfg, model, instance, port)


def resolve_version_config(
    model: Model, backend: Optional[InferenceBackend]
) -> Optional[BackendVersionConfig]:
    """The launch configuration build_command would use (None for the
    in-repo engine)."""
    if model.backend in ("", "tpu-native") or backend is None:
        return None
    version = model.backend_version or backend.default_version
    return next(
        (v for v in backend.versions if v.version == version), None
    ) or (backend.versions[0] if backend.versions else None)


def health_path_for(
    model: Model, backend: Optional[InferenceBackend]
) -> str:
    """Readiness endpoint for this instance's engine: external backends
    declare theirs (vLLM serves /health, not /healthz) in the catalog
    row; the in-repo engines all serve /healthz."""
    vcfg = resolve_version_config(model, backend)
    return (vcfg.health_path if vcfg else "") or "/healthz"


def _is_audio_model(model: Model) -> bool:
    """Key off the RESOLVED architecture, matching the scheduler's
    detection (calculator.resolve_model_config) — a local-path whisper
    checkpoint without a user-supplied 'audio' category must still launch
    the audio engine, not crash-loop under the LLM server."""
    from gpustack_tpu.models.tts import TTS_PRESETS
    from gpustack_tpu.models.whisper import WHISPER_PRESETS

    if (
        "audio" in model.categories
        or model.preset in WHISPER_PRESETS
        or model.preset in TTS_PRESETS
    ):
        return True
    if model.local_path:
        import json as _json

        try:
            with open(
                os.path.join(model.local_path, "config.json")
            ) as f:
                return _json.load(f).get("model_type") in (
                    "whisper", "tts", "fastspeech"
                )
        except (OSError, ValueError):
            return False
    return False


def _is_image_model(model: Model) -> bool:
    """Diffusion checkpoints are diffusers-format directories with a
    model_index.json (no top-level config.json), so detection keys off
    that layout — matching the scheduler's resolution
    (calculator.resolve_model_config)."""
    from gpustack_tpu.models.diffusion import DIFFUSION_PRESETS

    if "image" in model.categories or model.preset in DIFFUSION_PRESETS:
        return True
    if model.local_path:
        return os.path.exists(
            os.path.join(model.local_path, "model_index.json")
        )
    return False


def _tpu_native_command(
    model: Model,
    instance: ModelInstance,
    port: int,
    force_platform: str,
    process_index: int = 0,
    chip_indexes: Optional[List[int]] = None,
    cluster_secret: str = "",
) -> Tuple[List[str], Dict[str, str]]:
    if _is_audio_model(model):
        module = "gpustack_tpu.engine.audio_server"
    elif _is_image_model(model):
        module = "gpustack_tpu.engine.image_server"
    else:
        module = "gpustack_tpu.engine.api_server"
    argv = [
        sys.executable, "-m", module,
        # loopback only: the engine HTTP port carries no auth; all ingress
        # goes through the worker's authenticated reverse proxy
        # (worker/server.py instance_proxy)
        "--host", "127.0.0.1",
        "--port", str(port),
        "--served-name", model.name,
        "--max-seq-len", str(model.max_seq_len),
        "--max-slots", str(model.max_slots),
    ]
    if model.preset:
        argv += ["--preset", model.preset]
    elif model.local_path:
        # hf sources are resolved to a cache dir by the ModelFileManager
        # before command build (serve_manager rewrites local_path)
        argv += ["--model-dir", model.local_path]
    else:
        raise ValueError(
            "model has no resolved weight source (preset or local dir)"
        )
    claim = instance.computed_resource_claim
    if claim and claim.mesh_plan:
        argv += ["--mesh-plan", claim.mesh_plan]
    if model.quantization:
        argv += ["--quantization", model.quantization]
    for adapter in model.lora_adapters:
        argv += ["--lora", adapter]
    multi_host = bool(instance.coordinator_address)
    if model.prefill_chunk:
        # multi-host too: the chunk schedule replays op-for-op on
        # follower hosts via the chunk_start/chunk_continue/chunk_commit
        # broadcast vocabulary (engine/multihost.py) — long prompts on
        # the placements that need chunking most (70B-class multi-host)
        # no longer lose it
        argv += ["--prefill-chunk", str(model.prefill_chunk)]
    if model.engine_pipeline_depth:
        # per-model dispatch-ahead depth; negative = serial mode (0).
        # Unset (0) lets the engine read the config/env default.
        argv += [
            "--pipeline-depth", str(max(0, model.engine_pipeline_depth))
        ]
    if model.host_kv_cache_mb and not multi_host:
        # single-host only: on multi-host meshes the prefill K/V spans
        # non-addressable devices and cannot be pulled to one host's RAM
        argv += ["--host-kv-cache-mb", str(model.host_kv_cache_mb)]
        if model.kv_block_tokens:
            argv += ["--kv-block-tokens", str(model.kv_block_tokens)]
        if model.kv_cache_int8:
            argv += ["--kv-cache-int8"]
        if getattr(model, "kv_spill_mb", 0):
            # disk spill tier rides the host cache; a stable per-
            # instance directory keeps the tier warm across restarts
            argv += ["--kv-spill-mb", str(model.kv_spill_mb)]
            argv += [
                "--kv-spill-dir",
                os.path.join(
                    tempfile.gettempdir(),
                    f"gpustack-kv-spill-{instance.name}",
                ),
            ]
    if instance.role:
        # disaggregated prefill/decode role tag (ModelSpec
        # prefill_replicas/decode_replicas → controllers role deficit).
        # Passed even without a host KV cache so health/debug surfaces
        # show the tag — but warn: roleless KV means no handoff.
        if not model.host_kv_cache_mb or multi_host:
            logger.warning(
                "model %s: instance %s is role-tagged %r but has no "
                "host KV cache%s — KV handoff between roles is "
                "disabled", model.name, instance.name, instance.role,
                " (multi-host)" if multi_host else "",
            )
        argv += ["--kv-role", instance.role]
    if multi_host and model.speculative:
        logger.warning(
            "model %s: speculative decoding is single-host only; "
            "serving the multi-host replica without it", model.name,
        )
    elif model.speculative:
        if model.speculative == "draft" and not model.draft_source:
            # fail fast at command build — an engine that dies at startup
            # would crash-loop under restart_on_error with the cause
            # buried in instance logs
            raise ValueError(
                "speculative='draft' requires draft_source "
                "(preset name or local checkpoint dir)"
            )
        argv += [
            "--speculative", model.speculative,
            "--spec-tokens", str(model.spec_tokens),
        ]
        if model.draft_source:
            argv += ["--draft-source", model.draft_source]
    argv += model.backend_parameters

    env: Dict[str, str] = dict(model.env)
    # one compile cache for the worker and every engine it starts
    # (utils/compile_cache.py): the engine inherits the worker's
    # setting, a model cannot point its replicas elsewhere
    env.pop(COMPILE_CACHE_ENV, None)
    my_chips = (
        chip_indexes if chip_indexes is not None else instance.chip_indexes
    )
    if not force_platform:
        # a chip that cannot be opened is an error at start — an
        # instance in `error` with the cause in its log — never a CPU
        # run. --force-platform cpu is the one way to the CPU.
        env["JAX_PLATFORMS"] = "tpu"
        if my_chips and not instance.coordinator_address:
            env.update(chip_env(my_chips))
    else:
        env["GPUSTACK_TPU_PLATFORM"] = force_platform
        if force_platform == "cpu":
            # hermetic runs: the CPU backend must expose as many virtual
            # devices as this process's chip assignment so the mesh plan
            # tiles (mirrors tests/conftest.py)
            import re as _re

            claim = instance.computed_resource_claim
            n_local = len(my_chips) or (claim.chips if claim else 1)
            flags = _re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                env.get("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")),
            )
            env["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={n_local}"
            ).strip()
    if instance.coordinator_address:
        # multi-host: jax.distributed rendezvous (replaces the reference's
        # Ray bootstrap, worker/backends/vllm.py:258-328). The engine
        # consumes these in api_server.build_engine_from_args. The
        # leader→follower command channel (engine/multihost.py) rides
        # coordinator_port + 1 — fenced as a pair by the scheduler.
        host, _, cport = instance.coordinator_address.rpartition(":")
        env["GPUSTACK_TPU_COORDINATOR"] = instance.coordinator_address
        env["GPUSTACK_TPU_CMD_ADDRESS"] = f"{host}:{int(cport) + 1}"
        # command-channel auth (engine/multihost.py channel_token):
        # every worker of the placement derives the same value locally —
        # no extra secret distribution — and the derivation is KEYED by
        # the cluster registration token, which API users and outsiders
        # never see, so the token is not computable from public instance
        # metadata (instance ids are small integers, the channel port is
        # coordinator+1 — both guessable on their own)
        import hashlib as _hashlib

        env.setdefault(
            "GPUSTACK_TPU_CMD_TOKEN",
            _hashlib.sha256(
                f"{cluster_secret}:{instance.id}:"
                f"{instance.coordinator_address}".encode()
            ).hexdigest()[:32],
        )
        env["GPUSTACK_TPU_NUM_PROCESSES"] = str(
            1 + len(instance.subordinate_workers)
        )
        env.setdefault("GPUSTACK_TPU_PROCESS_ID", str(process_index))
    return argv, env


# libtpu's chip grid for a process that owns n chips of one host
# (x,y,z): what jax's own multi-process TPU tests pass for these counts.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_env(chip_indexes: Sequence[int]) -> Dict[str, str]:
    """libtpu environment that restricts one process to its chips of
    this host and makes those chips a slice of their own, so several
    engine processes can share a host, each on disjoint chips.

    Established on a four-chip v5e host with libtpu 0.0.34 (PR 23): with
    these three variables four one-chip processes, a two-chip and a
    four-chip process all open exactly their chips, at once, with no
    per-process ports or addresses and without lifting libtpu's
    one-process lock. Each process numbers its own devices from 0."""
    bounds = _CHIP_BOUNDS.get(len(chip_indexes))
    if bounds is None:
        raise ValueError(
            f"no libtpu chip grid for {len(chip_indexes)} chips "
            f"(supported: {sorted(_CHIP_BOUNDS)})"
        )
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in chip_indexes),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def _render(
    vcfg: BackendVersionConfig,
    model: Model,
    instance: ModelInstance,
    port: int,
) -> Tuple[List[str], Dict[str, str]]:
    claim = instance.computed_resource_claim
    subst = {
        "python": sys.executable,
        "port": str(port),
        "served_name": model.name,
        "model_dir": model.local_path or "",
        "preset": model.preset or "",
        "mesh_plan": claim.mesh_plan if claim else "",
        "max_seq_len": str(model.max_seq_len),
        "max_slots": str(model.max_slots),
    }

    def sub(s: str) -> str:
        for k, v in subst.items():
            s = s.replace("{" + k + "}", v)
        return s

    argv = [sub(a) for a in vcfg.command] + model.backend_parameters
    env = dict(vcfg.env)
    env.update(model.env)
    return argv, env
