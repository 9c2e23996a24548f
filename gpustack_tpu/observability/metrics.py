"""Prometheus text-format histograms and counters, dependency-free.

The existing exporters (server/exporter.py, worker/server.py) are
gauge/counter-only string builders; attributing latency needs real
histograms with correct wire format: ``# TYPE`` before the first
sample, cumulative ``_bucket`` counts ending in ``+Inf`` ==
``_count``, and label values escaped per the exposition format
(backslash, double-quote, newline).

``METRIC_FAMILIES`` below is the declared vocabulary for everything
this module can emit — the metrics-drift analyzer parses the literal
dict (like METRIC_MAP in worker/metrics_map.py) so a histogram family
rename that orphans a dashboard or doc reference fails CI, and so
``_bucket``/``_sum``/``_count`` stay series of ONE declared family
instead of three drifting metrics.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Declared metric families (name -> prometheus kind). Keep LITERAL:
# the metrics-drift rule reads the AST, it does not import this module.
METRIC_FAMILIES = {
    # per-phase request latency through the server's proxy path
    "gpustack_request_duration_seconds": "histogram",
    # per-phase relay latency through the worker's reverse proxy
    "gpustack_worker_request_duration_seconds": "histogram",
    # instance lifecycle: dwell time per state (lifecycle.py tap)
    "gpustack_instance_state_seconds": "histogram",
    # utils/profiling.CallStats surfaced on /metrics (slow-call tracing)
    "gpustack_slow_call_count": "counter",
    "gpustack_slow_call_seconds_total": "counter",
    "gpustack_slow_call_max_seconds": "gauge",
    # host-RAM block KV cache (engine/kv_host_cache.py), emitted by the
    # engine exporter (engine/api_server.py) and normalized onto the
    # gpustack_tpu: namespace by the worker (worker/metrics_map.py)
    "gpustack_kv_cache_hits": "counter",
    "gpustack_kv_cache_misses": "counter",
    "gpustack_kv_cache_prefix_tokens_reused": "counter",
    "gpustack_kv_cache_bytes": "gauge",
    # disaggregated KV handoff (engine/kv_transfer.py): wire bytes and
    # blocks per direction (label direction=in|out), pull failures, and
    # end-to-end pull latency — emitted by the engine exporter,
    # normalized onto gpustack_tpu: by the worker
    "gpustack_kv_handoff_bytes_total": "counter",
    "gpustack_kv_handoff_blocks_total": "counter",
    "gpustack_kv_handoff_failures_total": "counter",
    "gpustack_kv_handoff_seconds": "histogram",
    # disk spill tier under the host cache (engine/kv_spill.py): bytes
    # and blocks per direction (direction=out spilled to disk, in
    # faulted back), the resident spill footprint, corrupt/truncated
    # files degraded to misses, disk-budget evictions, and blocks
    # re-attached to the trie by fault-back — engine exporter, worker-
    # normalized like the families above
    "gpustack_kv_spill_bytes_total": "counter",
    "gpustack_kv_spill_blocks_total": "counter",
    "gpustack_kv_spill_resident_bytes": "gauge",
    "gpustack_kv_spill_corrupt_total": "counter",
    "gpustack_kv_spill_evictions_total": "counter",
    "gpustack_kv_spill_faultbacks_total": "counter",
    # background fleet prefetch pulls landed by this engine
    # (POST /kv/pull; label result=ok|failed)
    "gpustack_kv_prefetch_total": "counter",
    # engine flight recorder (observability/flight.py): per-step
    # scheduler telemetry, emitted by the engine exporter and
    # normalized by the worker (worker/metrics_map.py)
    "gpustack_engine_step_seconds": "histogram",
    "gpustack_engine_dispatched_tokens_total": "counter",
    "gpustack_engine_prompt_tokens_total": "counter",
    # prompt tokens by the expert dispatch their prefill program was
    # traced with (label dispatch=grouped|dense); absent for a dense model
    "gpustack_engine_moe_prompt_tokens_total": "counter",
    # a replica that holds a share of its model's experts
    # (ModelConfig.experts_held): the router's (token, expert) pairs of
    # its prefill programs, bucket padding included, by whether the
    # pair's expert is held here (label held=yes|no); absent otherwise
    "gpustack_engine_moe_pairs_total": "counter",
    # cached positions over the decode steps (label kind=live|allocated):
    # what the steps' live slots attended, by the scheduler's count, and
    # the slots x max_len a step that the cache allocates; live over
    # allocated is the share of the cache a decode step has to read
    "gpustack_engine_decode_kv_positions_total": "counter",
    # a model with experts, over the decode steps fetched (label
    # kind=read|held): the held experts whose weights a step read,
    # summed over its layers with experts (the decode program's own
    # count), and held x layers a step; read over held is the share of
    # the experts' weights a decode step reads (100 % under dense
    # dispatch); absent for a model without experts
    "gpustack_engine_moe_decode_experts_total": "counter",
    # the device's KV cache: its bytes, and the bytes one position of
    # one layer takes (1,152 for an MLA latent of 512 + 64 in bf16)
    "gpustack_engine_kv_cache_bytes": "gauge",
    "gpustack_engine_kv_cache_bytes_per_token": "gauge",
    # what the slots keep on the device, by kind (label
    # kind=kv|state|window): rows a position, the recurrent state of a
    # model with state-space layers, and the sliding layers' rows of a
    # stack that keeps them at window size (each 0 for any other)
    "gpustack_engine_cache_bytes": "gauge",
    # a stack with a window store: cached rows its layers attended, by
    # kind of layer (label layer=sliding|full), over slots, positions
    # and layers; absent for any other model
    "gpustack_engine_attn_rows_total": "counter",
    "gpustack_engine_diffusion_passes_total": "counter",
    "gpustack_engine_diffusion_blocks_total": "counter",
    "gpustack_engine_diffusion_tokens_decided_total": "counter",
    # a model that keeps a recurrent state a slot: tokens through the
    # layers that keep it, by the program that took them (label
    # kind=prefill|decode: the chunked scan over a prompt, the one-step
    # update of a live slot) and the kind of mixer (label
    # mixer=ssm|delta|kda: Mamba-2, gated delta rule with a decay a
    # head, the same rule with a decay a key channel); absent for any
    # other model
    "gpustack_engine_ssm_tokens_total": "counter",
    "gpustack_engine_occupancy_ratio": "gauge",
    "gpustack_engine_queue_oldest_wait_seconds": "gauge",
    "gpustack_engine_queue_depth": "gauge",
    "gpustack_engine_spec_proposed_total": "counter",
    "gpustack_engine_spec_accepted_total": "counter",
    "gpustack_engine_kv_blocks_used": "gauge",
    "gpustack_engine_flight_overhead_ratio": "gauge",
    # overlapped engine (ISSUE 12): host work overlapped with device
    # compute, idle spin saved by the cv wakeup, and dispatch-ahead
    # tokens rolled back after a lagged fetch
    "gpustack_engine_host_overlap_ratio": "gauge",
    "gpustack_engine_idle_wait_seconds_total": "counter",
    "gpustack_engine_rollback_tokens_total": "counter",
    # programs lowered / compiled (persistent-cache misses) in the engine
    # process since its start, the weights' programs included, and the
    # seconds its threads stood in tracing and lowering, compiling or
    # loading from the cache (jax.monitoring, ISSUE 26, 42): a window
    # that meets a shape for the first time shows here even when the
    # persistent cache has the program
    "gpustack_engine_programs_traced_total": "counter",
    "gpustack_engine_programs_compiled_total": "counter",
    "gpustack_engine_compile_seconds_total": "counter",
    # an engine process's start (observability/startup.py): seconds of
    # each phase (import, backend, config, weights, engine, listen), and
    # from the process's creation to the first /healthz 200
    # (phase="ready") and to the first token (phase="first_token")
    "gpustack_engine_start_seconds": "gauge",
    # proxy-side usage metering (routes/openai_proxy.py _record_usage):
    # per-model token throughput on /metrics instead of DB-only, plus a
    # loss counter so silently-swallowed usage writes become visible
    "gpustack_model_usage_tokens_total": "counter",
    "gpustack_usage_records_dropped_total": "counter",
    # per-model SLO engine (observability/slo.py, fed by
    # server/sloeval.py): long-window compliance, two-window burn
    # rates, and the alert state machine (0 ok / 1 warning / 2 firing /
    # 3 resolved)
    "gpustack_slo_compliance_ratio": "gauge",
    "gpustack_slo_burn_rate": "gauge",
    "gpustack_slo_alert_state": "gauge",
    # zero-downtime rollouts (server/rollout.py): numeric state of a
    # model's newest rollout (0 completed / 1 surging / 2 observing /
    # 3 promoting / 4 rolling_back / 5 rolled_back / 6 failed) and a
    # labeled event counter (started / batch_promoted / completed /
    # gate_failed / rolled_back / …)
    "gpustack_rollout_state": "gauge",
    "gpustack_rollout_events_total": "counter",
    # SLO-driven autoscaler (server/autoscaler.py): the replica target
    # it last wrote, a 0/1 stale-signal freeze flag per model, the
    # measured cold-start estimate (SCHEDULED→RUNNING dwell p95 from
    # lifecycle timelines), and a labeled decision counter
    # (up / down / to_zero / wake / freeze / bounds)
    "gpustack_autoscale_replicas_target": "gauge",
    "gpustack_autoscale_frozen": "gauge",
    "gpustack_autoscale_cold_start_seconds": "gauge",
    "gpustack_autoscale_events_total": "counter",
    # tenant QoS (server/tenancy.py): per-tenant admission outcomes
    # (outcome=admitted|<shed reason>), live in-flight, and budget-
    # charged tokens — labels bounded to the first N tracked tenants
    # (sticky) plus a monotonic tenant="_other" rollup so millions of
    # users can't blow the scrape
    "gpustack_tenant_requests_total": "counter",
    "gpustack_tenant_inflight": "gauge",
    "gpustack_tenant_tokens_total": "counter",
    # control-plane write combiner (server/write_combiner.py):
    # position on the overload-degradation ladder (>= 1.0 = degraded,
    # liveness-only flushes), heartbeat/status writes coalesced away
    # before ever reaching the DB, writes actually landed per batched
    # flush, and status documents deferred past a flush by pressure —
    # the knobs that keep DB write rate sub-linear in workers
    "gpustack_control_write_pressure": "gauge",
    "gpustack_control_coalesced_writes_total": "counter",
    "gpustack_control_flushed_writes_total": "counter",
    "gpustack_control_deferred_writes_total": "counter",
    # control-plane HA (server/coordinator.py + orm/fencing.py):
    # whether THIS server holds the lease, the fencing epoch of the
    # current lease, leadership transitions this process observed
    # (acquired + lost), and writes rejected by the epoch fence — a
    # nonzero fenced count is a deposed leader caught mid-write, i.e.
    # the fence doing its job
    "gpustack_ha_is_leader": "gauge",
    "gpustack_ha_epoch": "gauge",
    "gpustack_ha_leader_transitions_total": "counter",
    "gpustack_ha_fenced_writes_total": "counter",
}

# request-latency buckets: 1ms .. 10min covers auth (sub-ms) through a
# slow non-streaming generation
DURATION_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)

# state-dwell buckets: instances legitimately sit minutes in
# DOWNLOADING/STARTING and hours in RUNNING
DWELL_BUCKETS = (
    0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
    600.0, 1800.0, 3600.0, 14400.0,
)

_INF = float("inf")


def escape_label_value(value: str) -> str:
    """Exposition-format label escaping: ``\\`` then ``"`` then LF."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def format_labels(labels: Sequence[Tuple[str, str]]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{escape_label_value(v)}"' for k, v in labels
    )
    return "{" + inner + "}"


# concurrency contract (checked by `python -m gpustack_tpu.analysis`,
# rule guarded-by): series maps and registry tables are written from
# bench/executor threads and scraped from HTTP handlers — always under
# the owning object's `_mu` (the registry map under its module lock).
GUARDED_BY = {
    "_series": "_mu",
    "_hists": "_mu",
    "_counters": "_mu",
    "_REGISTRIES": "_REGISTRIES_MU",
}


class Histogram:
    """One histogram family with optional labels.

    ``observe`` is thread-safe (bench and executor threads record into
    it); ``render`` emits the full family — ``# TYPE`` first, one
    cumulative bucket series per label set, ``+Inf`` always present and
    equal to ``_count``.
    """

    # backstop against label-cardinality explosions: past this many
    # distinct label sets, new ones fold into a sentinel series so a
    # misbehaving caller can bloat neither memory nor the scrape
    MAX_SERIES = 1024
    OVERFLOW_LABEL = "_other"

    def __init__(
        self,
        name: str,
        buckets: Sequence[float] = DURATION_BUCKETS,
        label_names: Sequence[str] = (),
    ):
        self.name = name
        self.buckets: Tuple[float, ...] = tuple(sorted(buckets))
        self.label_names = tuple(label_names)
        self._mu = threading.Lock()
        # label values tuple -> (bucket counts list, sum, count)
        self._series: Dict[
            Tuple[str, ...], List
        ] = {}

    def observe(self, value: float, **labels: str) -> None:
        key = tuple(
            str(labels.get(name, "")) for name in self.label_names
        )
        with self._mu:
            series = self._series.get(key)
            if series is None and len(self._series) >= self.MAX_SERIES:
                key = tuple(
                    self.OVERFLOW_LABEL for _ in self.label_names
                )
                series = self._series.get(key)
            if series is None:
                series = [[0] * (len(self.buckets) + 1), 0.0, 0]
                self._series[key] = series
            counts, _, _ = series
            placed = False
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    counts[i] += 1
                    placed = True
                    break
            if not placed:
                counts[-1] += 1          # +Inf bucket
            series[1] += value
            series[2] += 1

    def snapshot(
        self,
    ) -> Dict[Tuple[str, ...], Tuple[List[Tuple[float, int]], float, int]]:
        """label values -> (cumulative (upper_bound, count) pairs
        including +Inf, sum, count)."""
        out = {}
        with self._mu:
            items = [
                (k, (list(v[0]), v[1], v[2]))
                for k, v in self._series.items()
            ]
        for key, (counts, total, count) in items:
            cum, acc = [], 0
            for ub, c in zip(self.buckets + (_INF,), counts):
                acc += c
                cum.append((ub, acc))
            out[key] = (cum, total, count)
        return out

    def quantile(self, q: float, **labels: str) -> Optional[float]:
        """Estimated quantile via linear interpolation within the
        bucket (the same estimate PromQL's histogram_quantile makes).
        None when the (labeled) series has no observations."""
        key = tuple(
            str(labels.get(name, "")) for name in self.label_names
        )
        snap = self.snapshot().get(key)
        if snap is None or snap[2] == 0:
            return None
        cum, _total, count = snap
        rank = q * count
        prev_ub, prev_cum = 0.0, 0
        for ub, c in cum:
            if c >= rank:
                if ub == _INF:
                    return prev_ub
                if c == prev_cum:
                    return ub
                frac = (rank - prev_cum) / (c - prev_cum)
                return prev_ub + (ub - prev_ub) * frac
            prev_ub, prev_cum = ub, c
        return prev_ub

    def render(self) -> List[str]:
        lines = [f"# TYPE {self.name} histogram"]
        for key, (cum, total, count) in sorted(
            self.snapshot().items()
        ):
            base_labels = list(zip(self.label_names, key))
            for ub, c in cum:
                le = "+Inf" if ub == _INF else repr(ub)
                lines.append(
                    f"{self.name}_bucket"
                    f"{format_labels(base_labels + [('le', le)])} {c}"
                )
            lines.append(
                f"{self.name}_sum{format_labels(base_labels)} "
                f"{total:.6f}"
            )
            lines.append(
                f"{self.name}_count{format_labels(base_labels)} {count}"
            )
        return lines


class Counter:
    """One labeled counter family (same thread-safety and overflow
    backstop contract as :class:`Histogram`)."""

    MAX_SERIES = 1024
    OVERFLOW_LABEL = "_other"

    def __init__(self, name: str, label_names: Sequence[str] = ()):
        self.name = name
        self.label_names = tuple(label_names)
        self._mu = threading.Lock()
        self._series: Dict[Tuple[str, ...], float] = {}

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        if amount < 0:
            return                    # counters only go up
        key = tuple(
            str(labels.get(name, "")) for name in self.label_names
        )
        with self._mu:
            if (
                key not in self._series
                and len(self._series) >= self.MAX_SERIES
            ):
                key = tuple(
                    self.OVERFLOW_LABEL for _ in self.label_names
                )
            self._series[key] = self._series.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        key = tuple(
            str(labels.get(name, "")) for name in self.label_names
        )
        with self._mu:
            return self._series.get(key, 0.0)

    def render(self) -> List[str]:
        with self._mu:
            items = sorted(self._series.items())
        if not items:
            return []
        lines = [f"# TYPE {self.name} counter"]
        for key, value in items:
            labels = format_labels(list(zip(self.label_names, key)))
            if value == int(value):
                lines.append(f"{self.name}{labels} {int(value)}")
            else:
                lines.append(f"{self.name}{labels} {value:.6f}")
        return lines


class MetricsRegistry:
    """Named histograms + counters for one component (server /
    worker): creation is idempotent so call sites can resolve by name
    without import-time ordering concerns."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._hists: Dict[str, Histogram] = {}
        self._counters: Dict[str, Counter] = {}

    def histogram(
        self,
        name: str,
        buckets: Sequence[float] = DURATION_BUCKETS,
        label_names: Sequence[str] = (),
    ) -> Histogram:
        with self._mu:
            h = self._hists.get(name)
            if h is None:
                h = Histogram(
                    name, buckets=buckets, label_names=label_names
                )
                self._hists[name] = h
            return h

    def counter(
        self, name: str, label_names: Sequence[str] = ()
    ) -> Counter:
        with self._mu:
            c = self._counters.get(name)
            if c is None:
                c = Counter(name, label_names=label_names)
                self._counters[name] = c
            return c

    def render_lines(self) -> List[str]:
        with self._mu:
            hists = sorted(self._hists.items())
            counters = sorted(self._counters.items())
        lines: List[str] = []
        for _, h in hists:
            lines.extend(h.render())
        for _, c in counters:
            lines.extend(c.render())
        return lines


_REGISTRIES: Dict[str, MetricsRegistry] = {}
_REGISTRIES_MU = threading.Lock()


def get_registry(component: str) -> MetricsRegistry:
    """Process-global registry per component. Server and worker keep
    separate registries because in embedded-worker mode both live in
    one process but scrape on different ports — each /metrics must
    serve only its own families."""
    with _REGISTRIES_MU:
        reg = _REGISTRIES.get(component)
        if reg is None:
            reg = MetricsRegistry()
            _REGISTRIES[component] = reg
        return reg


def slow_call_lines(stats=None) -> List[str]:
    """Render utils/profiling.CallStats as gpustack_slow_call_* series
    (count/total/max per decorated call site)."""
    if stats is None:
        from gpustack_tpu.utils.profiling import STATS as stats  # noqa: N813

    snap = stats.snapshot()
    if not snap:
        return []

    def type_line(family: str) -> str:
        # TYPE text derives from the declared vocabulary — exactly one
        # declaration site for the metrics-drift analyzer to read
        return f"# TYPE {family} {METRIC_FAMILIES[family]}"

    lines = [type_line("gpustack_slow_call_count")]
    for name in sorted(snap):
        labels = format_labels([("name", name)])
        lines.append(
            f"gpustack_slow_call_count{labels} "
            f"{int(snap[name]['count'])}"
        )
    lines.append(type_line("gpustack_slow_call_seconds_total"))
    for name in sorted(snap):
        labels = format_labels([("name", name)])
        lines.append(
            f"gpustack_slow_call_seconds_total{labels} "
            f"{snap[name]['total_s']:.6f}"
        )
    lines.append(type_line("gpustack_slow_call_max_seconds"))
    for name in sorted(snap):
        labels = format_labels([("name", name)])
        lines.append(
            f"gpustack_slow_call_max_seconds{labels} "
            f"{snap[name]['max_s']:.6f}"
        )
    return lines
