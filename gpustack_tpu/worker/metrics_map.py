"""Engine-metric normalization: per-engine names → ``gpustack_tpu:*``.

Reference parity: RuntimeMetricsAggregator + assets/metrics_config/
metrics_config.yaml (runtime_metrics_aggregator.py:48) — every engine's
native metric names map onto one normalized namespace so dashboards and
alerts survive backend swaps. In-repo engines are covered exactly;
vLLM/SGLang names cover ``custom`` backends running those servers.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, Optional, Tuple

NORMALIZED_PREFIX = "gpustack_tpu:"

METRIC_MAP: Dict[str, str] = {
    # in-repo LLM engine (engine/api_server.py)
    "gpustack_engine_slots_used": "gpustack_tpu:requests_running",
    "gpustack_engine_slots_total": "gpustack_tpu:slots_total",
    "gpustack_engine_waiting": "gpustack_tpu:requests_waiting",
    "gpustack_engine_tokens_generated_total":
        "gpustack_tpu:generation_tokens_total",
    "gpustack_engine_ttft_seconds": "gpustack_tpu:ttft_seconds",
    "gpustack_engine_tpot_seconds": "gpustack_tpu:tpot_seconds",
    "gpustack_engine_e2e_seconds": "gpustack_tpu:e2e_request_seconds",
    # host-RAM block KV cache on the in-repo engine (kv_host_cache.py)
    "gpustack_kv_cache_hits": "gpustack_tpu:kv_cache_hits",
    "gpustack_kv_cache_misses": "gpustack_tpu:kv_cache_misses",
    "gpustack_kv_cache_prefix_tokens_reused":
        "gpustack_tpu:kv_cache_prefix_tokens_reused",
    "gpustack_kv_cache_bytes": "gpustack_tpu:kv_cache_host_bytes",
    # disaggregated KV handoff (engine/kv_transfer.py)
    "gpustack_kv_handoff_bytes_total":
        "gpustack_tpu:kv_handoff_bytes_total",
    "gpustack_kv_handoff_blocks_total":
        "gpustack_tpu:kv_handoff_blocks_total",
    "gpustack_kv_handoff_failures_total":
        "gpustack_tpu:kv_handoff_failures_total",
    "gpustack_kv_handoff_seconds": "gpustack_tpu:kv_handoff_seconds",
    # disk spill tier + fleet prefetch (engine/kv_spill.py, the fleet
    # KV fabric — docs/KV_CACHE.md)
    "gpustack_kv_spill_bytes_total":
        "gpustack_tpu:kv_spill_bytes_total",
    "gpustack_kv_spill_blocks_total":
        "gpustack_tpu:kv_spill_blocks_total",
    "gpustack_kv_spill_resident_bytes":
        "gpustack_tpu:kv_spill_resident_bytes",
    "gpustack_kv_spill_corrupt_total":
        "gpustack_tpu:kv_spill_corrupt_total",
    "gpustack_kv_spill_evictions_total":
        "gpustack_tpu:kv_spill_evictions_total",
    "gpustack_kv_spill_faultbacks_total":
        "gpustack_tpu:kv_spill_faultbacks_total",
    "gpustack_kv_prefetch_total": "gpustack_tpu:kv_prefetch_total",
    # engine flight recorder (observability/flight.py): per-step
    # scheduler telemetry — the fleet rollup's saturation signals
    "gpustack_engine_step_seconds": "gpustack_tpu:engine_step_seconds",
    "gpustack_engine_dispatched_tokens_total":
        "gpustack_tpu:dispatched_tokens_total",
    "gpustack_engine_prompt_tokens_total":
        "gpustack_tpu:prompt_tokens_total",
    "gpustack_engine_occupancy_ratio": "gpustack_tpu:occupancy_ratio",
    "gpustack_engine_queue_oldest_wait_seconds":
        "gpustack_tpu:queue_oldest_wait_seconds",
    "gpustack_engine_queue_depth": "gpustack_tpu:queue_depth",
    "gpustack_engine_spec_proposed_total":
        "gpustack_tpu:spec_proposed_total",
    "gpustack_engine_spec_accepted_total":
        "gpustack_tpu:spec_accepted_total",
    "gpustack_engine_kv_blocks_used": "gpustack_tpu:kv_blocks_used",
    "gpustack_engine_host_overlap_ratio":
        "gpustack_tpu:host_overlap_ratio",
    "gpustack_engine_idle_wait_seconds_total":
        "gpustack_tpu:idle_wait_seconds_total",
    "gpustack_engine_rollback_tokens_total":
        "gpustack_tpu:rollback_tokens_total",
    "gpustack_engine_flight_overhead_ratio":
        "gpustack_tpu:flight_overhead_ratio",
    # proxy-side usage metering (routes/openai_proxy.py): mapped so a
    # custom OpenAI-gateway backend emitting the same family lands in
    # the normalized namespace alongside the engine token counters
    "gpustack_model_usage_tokens_total":
        "gpustack_tpu:model_usage_tokens_total",
    # in-repo audio engine (engine/audio_server.py)
    "gpustack_tpu_audio_requests_total": "gpustack_tpu:audio_requests_total",
    "gpustack_tpu_audio_seconds_total": "gpustack_tpu:audio_seconds_total",
    # vLLM-style engines behind the custom backend (reference
    # metrics_config.yaml vllm section)
    "vllm:num_requests_running": "gpustack_tpu:requests_running",
    "vllm:num_requests_waiting": "gpustack_tpu:requests_waiting",
    "vllm:prompt_tokens_total": "gpustack_tpu:prompt_tokens_total",
    "vllm:generation_tokens_total": "gpustack_tpu:generation_tokens_total",
    "vllm:gpu_cache_usage_perc": "gpustack_tpu:kv_cache_usage_ratio",
    "vllm:time_to_first_token_seconds": "gpustack_tpu:ttft_seconds",
    "vllm:time_per_output_token_seconds": "gpustack_tpu:tpot_seconds",
    # SGLang names (reference metrics_config.yaml sglang section)
    "sglang:num_running_reqs": "gpustack_tpu:requests_running",
    "sglang:num_queue_reqs": "gpustack_tpu:requests_waiting",
    "sglang:prompt_tokens_total": "gpustack_tpu:prompt_tokens_total",
    "sglang:generation_tokens_total":
        "gpustack_tpu:generation_tokens_total",
    "sglang:token_usage": "gpustack_tpu:kv_cache_usage_ratio",
}

# Declared vocabulary of the normalized namespace (name -> prometheus
# kind). Keep LITERAL: the metrics-drift analyzer reads this dict from
# the AST (like METRIC_FAMILIES in observability/metrics.py) and
# enforces that every METRIC_MAP value above is a member — a
# ``gpustack_tpu:*`` typo in the map fails `make analyze` instead of
# silently minting a series no dashboard has ever heard of.
# ``gpustack_tpu:scrape_age_seconds`` is worker-emitted (not mapped):
# the staleness gauge for each instance's scraped engine body.
NORMALIZED_FAMILIES: Dict[str, str] = {
    "gpustack_tpu:requests_running": "gauge",
    "gpustack_tpu:slots_total": "gauge",
    "gpustack_tpu:requests_waiting": "gauge",
    "gpustack_tpu:generation_tokens_total": "counter",
    "gpustack_tpu:prompt_tokens_total": "counter",
    "gpustack_tpu:ttft_seconds": "histogram",
    "gpustack_tpu:tpot_seconds": "histogram",
    "gpustack_tpu:e2e_request_seconds": "histogram",
    "gpustack_tpu:kv_cache_hits": "counter",
    "gpustack_tpu:kv_cache_misses": "counter",
    "gpustack_tpu:kv_cache_prefix_tokens_reused": "counter",
    "gpustack_tpu:kv_cache_host_bytes": "gauge",
    "gpustack_tpu:kv_cache_usage_ratio": "gauge",
    "gpustack_tpu:kv_handoff_bytes_total": "counter",
    "gpustack_tpu:kv_handoff_blocks_total": "counter",
    "gpustack_tpu:kv_handoff_failures_total": "counter",
    "gpustack_tpu:kv_handoff_seconds": "histogram",
    "gpustack_tpu:kv_spill_bytes_total": "counter",
    "gpustack_tpu:kv_spill_blocks_total": "counter",
    "gpustack_tpu:kv_spill_resident_bytes": "gauge",
    "gpustack_tpu:kv_spill_corrupt_total": "counter",
    "gpustack_tpu:kv_spill_evictions_total": "counter",
    "gpustack_tpu:kv_spill_faultbacks_total": "counter",
    "gpustack_tpu:kv_prefetch_total": "counter",
    "gpustack_tpu:audio_requests_total": "counter",
    "gpustack_tpu:audio_seconds_total": "counter",
    "gpustack_tpu:engine_step_seconds": "histogram",
    "gpustack_tpu:dispatched_tokens_total": "counter",
    "gpustack_tpu:occupancy_ratio": "gauge",
    "gpustack_tpu:queue_oldest_wait_seconds": "gauge",
    "gpustack_tpu:queue_depth": "gauge",
    "gpustack_tpu:spec_proposed_total": "counter",
    "gpustack_tpu:spec_accepted_total": "counter",
    "gpustack_tpu:kv_blocks_used": "gauge",
    "gpustack_tpu:flight_overhead_ratio": "gauge",
    "gpustack_tpu:host_overlap_ratio": "gauge",
    "gpustack_tpu:idle_wait_seconds_total": "counter",
    "gpustack_tpu:rollback_tokens_total": "counter",
    "gpustack_tpu:scrape_age_seconds": "gauge",
    "gpustack_tpu:model_usage_tokens_total": "counter",
}

_LINE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r"\s+(?P<value>\S+)"
)


def parse_metric_line(
    line: str,
) -> Optional[Tuple[str, Dict[str, str], str]]:
    """'name{a="b"} 1.5' -> (name, {a: b}, '1.5'); None for non-samples."""
    line = line.strip()
    if not line or line.startswith("#"):
        return None
    m = _LINE.match(line)
    if not m:
        return None
    labels: Dict[str, str] = {}
    raw = m.group("labels")
    if raw:
        for part in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', raw):
            labels[part[0]] = part[1]
    return m.group("name"), labels, m.group("value")


def _fmt(name: str, labels: Dict[str, str], value: str) -> str:
    if labels:
        inner = ",".join(
            f'{k}="{v}"' for k, v in sorted(labels.items())
        )
        return f"{name}{{{inner}}} {value}"
    return f"{name} {value}"


def normalize_engine_metrics(
    body: str, extra_labels: Dict[str, str]
) -> Iterator[str]:
    """Engine /metrics text -> normalized sample lines (mapped names
    only), with ``extra_labels`` (instance_id, model) merged in."""
    for line in body.splitlines():
        parsed = parse_metric_line(line)
        if parsed is None:
            continue
        name, labels, value = parsed
        mapped = METRIC_MAP.get(name)
        if mapped is None:
            # histograms sample as <name>_bucket/_sum/_count — map the
            # base name and carry the suffix over
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix):
                    base = METRIC_MAP.get(name[: -len(suffix)])
                    if base is not None:
                        mapped = base + suffix
                    break
        if mapped is None:
            continue
        labels.update(extra_labels)
        yield _fmt(mapped, labels, value)


def raw_engine_metrics(
    body: str, extra_labels: Dict[str, str]
) -> Iterator[str]:
    """Raw passthrough with labels merged (reference /metrics/raw)."""
    for line in body.splitlines():
        parsed = parse_metric_line(line)
        if parsed is None:
            continue
        name, labels, value = parsed
        labels.update(extra_labels)
        yield _fmt(name, labels, value)
