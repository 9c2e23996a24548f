"""Continuous-batching engine: request queue → slots → streamed tokens.

This is the TPU replacement for the engine containers the reference
launches (reference gpustack/worker/backends/vllm.py role): an in-process
orchestrator around :class:`~gpustack_tpu.engine.runner.ModelRunner`.

Overlapped scheduling (one dispatch thread that never waits on the
device, ``pipeline_depth`` steps of work in flight — the
``--async-scheduling`` role the reference Performance Lab credits its
biggest serving wins to):

1. admit: while a slot is free and requests wait → prefill (bucketed) +
   insert. The first sampled token is fed on-device (a device scalar
   into ``insert``), so admission dispatches N+1's prefill while N's
   sample is still in flight.
2. decode: one ``decode_step`` advances all active slots; sampled tokens
   are fetched ``pipeline_depth`` steps behind dispatch. When a lagged
   fetch reveals a slot finished, the speculatively dispatched steps
   for it are rolled back host-side (dropped + counted) and the slot is
   re-tenanted cleanly.
3. retire: EOS / max_tokens / capacity → free slot; detokenization and
   SSE stream writes ride a dedicated worker thread so tokenizer calls
   and client queues never stall dispatch.

``pipeline_depth=0`` is the serial reference mode (fetch + inline
detok every step) — greedy outputs are bit-identical across modes; the
parity suite (tests/engine/test_overlap.py) enforces it.

The reference's per-instance health probe contract (serve_manager health
checks) maps to :meth:`LLMEngine.health`.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import logging
import os
import queue
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from gpustack_tpu.engine.runner import DecodeState, ModelRunner
from gpustack_tpu.engine.tokenizer import load_tokenizer
from gpustack_tpu.models.config import ModelConfig
from gpustack_tpu.observability import capture as _capture
from gpustack_tpu.observability import flight as _flight
from gpustack_tpu.observability import startup as _startup

logger = logging.getLogger(__name__)

# default decode-fetch pipeline depth: decode steps in flight before the
# host inspects tokens (ModelSpec.engine_pipeline_depth / Config
# engine_pipeline_depth override it per deployment; 0 = serial mode)
_FETCH_LAG = 2

# sync-in-dispatch contract (analysis/rules/sync_dispatch.py): these
# functions form the scheduler dispatch path and must never block on the
# device — the analyzer flags np.asarray / .item() /
# jax.block_until_ready / jax.device_get inside them (nested def bodies
# excluded: they run on worker threads). Host syncs belong in the
# designated fetch/drain helpers (_process_fetch, _drain_pending,
# _draft_propose, _upload_prefix, _resolve_staged_prefix) or off-thread.
DISPATCH_SYNC_FREE = (
    "_loop", "step", "_step", "_admit", "_start_request", "_finalize_start",
    "_new_slot_info", "_plan_chunk_job", "_advance_chunk",
    "_decode_once", "_dispatch_decode", "_note_spec_dispatch", "_spec_safe",
    "_deliver", "_emit_text", "_push", "_finish", "_flight_record",
    "_submit_kv_copy", "_store_finished_sequence", "_build_proposals",
    "_entry_ready", "_drain_ready", "_advance_one_shot",
    "_flush_detok",
)

# guarded-by contract (analysis/rules/guarded_by.py): lock-guarded
# shared state, plus the scheduler thread's single-owner state. An
# owner list means "only these methods — all of which run on the
# scheduler thread — may touch the attribute"; a lock there would be
# pure overhead on the dispatch path. Cross-thread observational reads
# (health gauges) carry explicit `# analysis: ignore[guarded-by]`.
_SCHEDULER_METHODS = (
    "step", "_step", "_loop", "_admit", "_advance_chunk",
    "_advance_one_shot", "_build_proposals", "_decode_once",
    "_dispatch_decode", "_draft_propose",
    "_fail_all_requests", "_finalize_start", "_finalize_start_sync",
    "_finish", "_flight_record", "_process_fetch", "_drain_pending",
    "_drain_ready", "_start_request", "_deliver", "_flush_detok",
    "_store_finished_sequence", "_upload_prefix",
    "_resolve_staged_prefix", "_plan_chunk_job", "_new_slot_info",
    "_emit_text", "_push", "_note_spec_dispatch", "_spec_safe",
    "_entry_ready", "_submit_kv_copy", "_draw", "_insert",
    "_flush_freed",
)

GUARDED_BY = {
    "_overlap_s": "_overlap_mu",
    "_profile": "_profile_mu",
    "_capturing": "_profile_mu",
    "_captures": "_profile_mu",
    "_last_capture": "_profile_mu",
    "_KVStager._inflight": "_mu",
    "_slots": _SCHEDULER_METHODS,
    "_free": _SCHEDULER_METHODS,
    "_pending": _SCHEDULER_METHODS,
    "_chunk_jobs": _SCHEDULER_METHODS,
    "_detok_batch": _SCHEDULER_METHODS,
    "_overlap_seen": _SCHEDULER_METHODS,
    "_state": _SCHEDULER_METHODS,
    "_draws": _SCHEDULER_METHODS,
    "_freed": _SCHEDULER_METHODS,
}

# a busy step longer than this logs one WARNING line with its mode and
# its phases: a stall of seconds (PERF.md, the token-loss mode) then
# names where its time went in the engine's own log
_SLOW_STEP_S = 1.0

# thread-boundary contract (analysis/rules/thread_boundary.py): the
# scheduler's working state must never be reached from `async def`
# bodies — the HTTP layer talks to the engine through submit()/health()
# and the thread-safe queues only.
THREAD_OWNED = (
    "_slots", "_free", "_pending", "_chunk_jobs", "_detok_batch",
    "_state",
)


class LatencyHistogram:
    """Fixed-bucket Prometheus-style histogram (counts are cumulative
    per bucket at render time, kept simple here as per-bucket tallies).

    The reference normalizes vLLM's ttft/tpot histograms into its
    dashboard pipeline (metrics_config.yaml); the in-repo engine emits
    the same shapes natively."""

    def __init__(self, buckets):
        self.buckets = tuple(buckets)       # upper bounds, seconds
        self.counts = [0] * (len(self.buckets) + 1)   # +Inf tail
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        # bucket BEFORE count: snapshot() reads count first, so a racing
        # scrape can under-report count but never show count > +Inf
        # bucket (which would corrupt histogram_quantile)
        self.total += value
        for i, ub in enumerate(self.buckets):
            if value <= ub:
                self.counts[i] += 1
                break
        else:
            self.counts[-1] += 1
        self.count += 1

    def snapshot(self):
        """[(le, cumulative_count)], sum, count — count read first (see
        observe) and clamped to the +Inf bucket so the exposition always
        satisfies count <= bucket{le=\"+Inf\"}."""
        count = self.count
        cum, out = 0, []
        for ub, c in zip(self.buckets, self.counts):
            cum += c
            out.append((ub, cum))
        inf = cum + self.counts[-1]
        out.append((float("inf"), inf))
        return out, self.total, min(count, inf)


TTFT_BUCKETS_S = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
TPOT_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5)
E2E_BUCKETS_S = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


def _ngram_propose(ctx: List[int], k: int, n: int = 2) -> List[int]:
    """Propose up to k continuation tokens: find the latest earlier
    occurrence of the context's final n-gram and replay what followed
    (the reference exposes the same idea as vLLM's ngram speculative
    mode via engine args, vllm.py:531). O(len) reference version — the
    engine hot loop uses the incremental :class:`_NgramIndex`."""
    if k <= 0 or len(ctx) < n + 1:
        return []
    key = tuple(ctx[-n:])
    for i in range(len(ctx) - n - 1, -1, -1):
        if tuple(ctx[i : i + n]) == key:
            return list(ctx[i + n : i + n + k])
    return []


class _NgramIndex:
    """Incremental 2-gram index: O(1) proposal lookup per decode step.

    ``prev[g]`` is the end-index of the latest occurrence of 2-gram ``g``
    *before* its most recent one — exactly what the proposer needs, since
    the most recent occurrence of the context's final 2-gram is always the
    context tail itself.
    """

    def __init__(self, ctx: List[int], n: int = 2):
        self.n = n
        self.ctx = list(ctx)
        self.cur: Dict[tuple, int] = {}
        self.prev: Dict[tuple, int] = {}
        for end in range(n, len(self.ctx) + 1):
            self._register(tuple(self.ctx[end - n : end]), end)

    def _register(self, gram: tuple, end: int) -> None:
        if gram in self.cur:
            self.prev[gram] = self.cur[gram]
        self.cur[gram] = end

    def append(self, token: int) -> None:
        self.ctx.append(token)
        if len(self.ctx) >= self.n:
            self._register(tuple(self.ctx[-self.n:]), len(self.ctx))

    def propose(self, k: int) -> List[int]:
        if k <= 0 or len(self.ctx) < self.n + 1:
            return []
        end = self.prev.get(tuple(self.ctx[-self.n:]))
        if end is None:
            return []
        return self.ctx[end : end + k]


@dataclasses.dataclass
class GenRequest:
    """One generation request (already tokenized)."""

    prompt_ids: List[int]
    max_tokens: int = 128
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None             # OpenAI 'seed': deterministic replay
    logit_bias: Optional[Dict[int, float]] = None   # token id -> bias
    stop_ids: Tuple[int, ...] = ()
    stop_texts: Tuple[str, ...] = ()       # OpenAI 'stop' strings
    logprobs: bool = False                 # collect per-token logprobs
    top_logprobs: int = 0                  # alternatives per position (<= 20)
    json_mode: bool = False                # stop after one complete JSON value
    # VLM: (embeds [T, D] f32, mask [T] bool) overriding placeholder rows
    embeds_override: Optional[Tuple[Any, Any]] = None
    stream: Optional[queue.Queue] = None   # receives (token_id, text_piece)
    request_id: str = ""
    # the id of the hop trace this request arrived under (the API server
    # sets it from the trace its middleware opened; "" for a request made
    # in-process): the flight record's per-request entries carry it, so
    # they join the server's and the worker's hops of the same request
    trace_id: str = ""

    # filled by the engine
    output_ids: List[int] = dataclasses.field(default_factory=list)
    output_text: str = ""                  # stop-truncated decoded text
    # aligned with output_ids when logprobs: per-token logprob and
    # [(token_id, logprob)] alternatives
    output_logprobs: List[float] = dataclasses.field(default_factory=list)
    output_top_logprobs: List[List[Tuple[int, float]]] = dataclasses.field(
        default_factory=list
    )
    finish_reason: str = ""
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    # client gone (SSE disconnect, proxy timeout): the engine stops
    # generating for this request at its next delivery instead of
    # burning the slot to max_tokens (advisor r4)
    aborted: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )
    submitted_at: float = 0.0
    first_token_at: float = 0.0
    finished_at: float = 0.0
    # host KV cache accounting for this request: prefix tokens whose
    # prefill was skipped, and the host→device upload seconds spent
    # re-materializing them (surfaced as the trace's kv_upload phase)
    prefix_tokens_reused: int = 0
    kv_upload_s: float = 0.0

    def abort(self) -> None:
        self.aborted.set()

    @property
    def ttft_ms(self) -> float:
        return (self.first_token_at - self.submitted_at) * 1e3


@dataclasses.dataclass
class _ChunkJob:
    """An in-progress chunked prefill occupying a slot (not yet decoding)."""

    req: "GenRequest"
    ids: List[int]
    done: int = 0            # tokens prefilled so far
    last: Any = None         # last-position logits of the latest chunk
    k: Any = None            # accumulated KV [L, bucket, H, hd]
    v: Any = None
    # staged prefix upload in flight on the kv-copy executor (double
    # buffering): resolves to (k, v, prefix_len) or None on eviction —
    # the job cold-starts then. While pending, decode for running slots
    # proceeds; that concurrency is the overlap win.
    pending_kv: Any = None
    # deferred ONE-SHOT prefill (non-chunked prefix hit): the single
    # "chunk" is the entire suffix, run the step after the staged
    # upload lands — the job shape that un-blocks the scheduler from
    # the old inline gather+upload (PR 11 residual)
    one_shot: bool = False


@dataclasses.dataclass
class _SlotInfo:
    request: GenRequest
    ngram: Optional["_NgramIndex"] = None
    # draft mode: delivered tokens not yet ingested into the draft cache
    pending_draft: List[int] = dataclasses.field(default_factory=list)
    # Incremental detokenization state: undecoded token ids are buffered
    # until they decode cleanly (no dangling multibyte sequence), then the
    # text accumulates here — the tokenizer only ever decodes the small
    # buffer, keeping streaming O(tokens) instead of O(tokens^2).
    buffer_ids: List[int] = dataclasses.field(default_factory=list)
    text: str = ""            # decoded text (post stop-truncation)
    emitted: int = 0          # chars of ``text`` already streamed
    # JSON mode: incremental end-of-value scanner + chars already scanned
    json_scan: Optional[Any] = None
    json_scanned: int = 0
    # True: the scheduler detokenizes inline (serial mode, or the
    # request's termination depends on decoded text — stop strings /
    # JSON mode). False: buffer_ids/text/emitted are owned by the detok
    # worker after handoff; the scheduler only appends token ids.
    sync_detok: bool = True


class _DetokWorker:
    """Dedicated detokenization + stream-write thread (overlap mode).

    The scheduler hands accepted token ids through a bounded queue and,
    for offloaded requests, never touches the slot's detok state
    (``buffer_ids``/``text``/``emitted``) again — this thread owns the
    tokenizer calls and SSE queue puts, so neither stalls device
    dispatch. Queue items are COALESCED: one ``("batch", [(info,
    toks), ...])`` entry per drained fetch covering every slot that
    produced tokens (was: one entry per slot per fetch — a full batch
    paid ``max_slots`` queue round-trips per step). A ``("finish",
    info)`` item flushes the tail, publishes ``output_text`` and sets
    the request's ``done`` event; the single FIFO queue is the
    ordering contract (the scheduler flushes the pending batch before
    queueing any finish, so all of a request's tokens precede its
    finish). Busy seconds feed the engine's host-overlap accounting
    (the flight recorder's ``host_overlap_ratio``)."""

    _STOP = object()

    def __init__(self, engine: "LLMEngine", maxsize: int = 4096):
        self._engine = engine
        # bounded: a stalled consumer backpressures dispatch instead of
        # pinning unbounded text host-side
        self._q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self._thread: Optional[threading.Thread] = None

    def _ensure_thread(self) -> None:
        # lazy: only engines that actually offload pay for a thread.
        # Scheduler-thread-only callers, so no start race.
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="llm-detok", daemon=True
            )
            self._thread.start()

    def put_batch(
        self, items: List[Tuple["_SlotInfo", List[int]]]
    ) -> None:
        """One coalesced entry for one drained fetch's accepted tokens
        across every offloaded slot."""
        self._ensure_thread()
        self._q.put(("batch", items))

    def finish(self, info: "_SlotInfo") -> None:
        self._ensure_thread()
        self._q.put(("finish", info))

    def stop(self, timeout: float = 10.0) -> None:
        if self._thread is None:
            return
        self._q.put(self._STOP)
        self._thread.join(timeout=timeout)

    def _run(self) -> None:
        eng = self._engine
        while True:
            item = self._q.get()
            if item is self._STOP:
                return
            kind, payload = item
            t0 = time.perf_counter()
            try:
                if kind == "finish":
                    self._finish_one(payload)
                else:
                    for info, toks in payload:
                        self._tokens_one(info, toks)
            finally:
                eng._note_overlap(time.perf_counter() - t0)

    def _tokens_one(self, info: "_SlotInfo", toks: List[int]) -> None:
        try:
            info.buffer_ids.extend(toks)
            self._engine._emit_text(info, final=False)
        except Exception:
            # a tokenizer fault must fail ONE request loudly — never
            # the rest of its batch, nor any waiter queued behind it
            logger.exception("detok worker item failed")
            self._fail_request(info)

    def _finish_one(self, info: "_SlotInfo") -> None:
        try:
            # finish: flush the multibyte tail, publish, wake the
            # waiter (finish_reason was set by the scheduler before
            # the handoff)
            req = info.request
            self._engine._emit_text(info, final=True)
            req.output_text = info.text
            if req.stream is not None:
                req.stream.put(None)
            req.done.set()
        except Exception:
            logger.exception("detok worker finish failed")
            self._fail_request(info)

    @staticmethod
    def _fail_request(info: "_SlotInfo") -> None:
        req = info.request
        if not req.done.is_set():
            req.finish_reason = req.finish_reason or "error"
            # publish whatever text HAD decoded — a fault in the final
            # flush must not turn a finished request into an
            # empty-looking success
            req.output_text = info.text
            if req.stream is not None:
                req.stream.put(None)
            req.done.set()


class _KVStager:
    """Two-slot staging buffer for host→device prefix-KV uploads AND
    wire imports on the kv-copy executor: at most ``depth`` jobs in
    flight, so the next chunk job's prefix copies (or a handed-off
    block run lands) while the current chunk or the running slots'
    decode computes, without unbounded host pinning. Thread-safe:
    the scheduler thread stages prefix uploads while api_server
    executor threads stage KV-transfer imports."""

    def __init__(self, executor, depth: int = 2):
        self._ex = executor
        self._inflight: "collections.deque" = collections.deque()
        self._mu = threading.Lock()
        self.depth = depth

    def submit(self, fn):
        with self._mu:
            while self._inflight and self._inflight[0].done():
                self._inflight.popleft()
            while len(self._inflight) >= self.depth:
                # backpressure: the two-slot bound is the memory
                # contract (held under the lock — the bound is global,
                # not per-submitter)
                concurrent.futures.wait([self._inflight.popleft()])
            try:
                fut = self._ex.submit(fn)
            except RuntimeError:
                # executor shut down (engine stopping / tests draining
                # the copy pool): run inline — a resolved future keeps
                # the caller's contract
                fut = concurrent.futures.Future()
                try:
                    fut.set_result(fn())
                except Exception as e:
                    fut.set_exception(e)
            self._inflight.append(fut)
            return fut


def _refuse_what_moves_a_slot(
    cfg, speculative, host_kv_cache_mb, kv_spill_mb, kv_role, prefill_chunk
) -> None:
    """A model that keeps something a slot beside the rows it keeps a
    position (``cfg.beside_rows``: a recurrent state, a ring of window
    rows) cannot have a slot cut, stored, moved, gone on from or rolled
    back by its positions: the tokens served after would come from a
    slot that is not the sequence's. The first such mechanism that was
    asked for is refused here, at engine start, by name (a mesh of
    several devices: ``ModelRunner``)."""
    beside = cfg.beside_rows
    if beside is None:
        return
    asked = [
        (speculative, f"speculative={speculative!r}: a verify step cannot "
         f"roll back {beside.lost} past a rejected draft"),
        (host_kv_cache_mb > 0, "host_kv_cache_mb: the prefix cache "
         "(engine/kv_host_cache.py) keeps blocks of rows a token span: "
         f"{beside.span}"),
        (kv_spill_mb > 0, "kv_spill_mb: the spill tier (engine/kv_spill.py) "
         f"stores blocks of rows a token span: {beside.span}"),
        (kv_role, f"kv_role={kv_role!r}: a KV handoff (engine/"
         f"kv_transfer.py) moves blocks of rows a token span: {beside.span}"),
        (prefill_chunk > 0, "prefill_chunk: a chunk goes on from cached "
         f"rows (prefill_with_prefix): {beside.span}"),
    ]
    for on, why in asked:
        if on:
            raise ValueError(
                f"{cfg.name} {beside.keeps} and cannot be served with {why}"
            )


class LLMEngine:
    """Single-replica continuous-batching LLM engine."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        *,
        tokenizer=None,
        model_dir: Optional[str] = None,
        max_slots: int = 8,
        max_seq_len: int = 1024,
        plan=None,
        mesh=None,
        seed: int = 0,
        speculative: str = "",       # ""|"ngram"|"draft" (forces greedy)
        spec_tokens: int = 4,        # proposals verified per spec step
        draft_cfg=None,              # draft model config (speculative=draft)
        draft_params=None,
        host_kv_cache_mb: int = 0,   # >0: host-RAM block KV cache
        kv_block_tokens: int = 0,    # block granularity (0 = default 256)
        kv_cache_int8: bool = False,  # int8 host tier (per-block scales)
        prefill_chunk: int = 0,      # >0: chunked prefill (tokens/chunk)
        pipeline_depth: int = _FETCH_LAG,  # 0 = serial reference mode
        kv_role: str = "",           # ""|"prefill"|"decode" (disagg tag)
        kv_spill_mb: int = 0,        # >0: disk spill tier under host RAM
        kv_spill_dir: str = "",      # spill directory ("" = derived tmp)
    ):
        self.cfg = cfg
        _refuse_what_moves_a_slot(
            cfg, speculative, host_kv_cache_mb, kv_spill_mb, kv_role,
            prefill_chunk,
        )
        self.tokenizer = tokenizer or load_tokenizer(model_dir)
        self.runner = ModelRunner(
            cfg, params, plan=plan, mesh=mesh,
            max_slots=max_slots, max_seq_len=max_seq_len,
        )
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self._state: DecodeState = self.runner.new_state()
        cache = self._state.cache
        self._kv_cache_bytes_per_token = cfg.kv_cache_bytes_per_token(
            8 * cache.k.dtype.itemsize
        ) // max(1, cfg.num_kv_layers)
        # the three kinds of per-slot memory, as the cache counts them:
        # rows a position; a recurrent state (0 and no dtype for a model
        # without one); the window store of a stack that keeps its
        # sliding layers' rows at window size (0 bytes and 0 rows a ring
        # for any other; kv_bytes is then the full layers' rows alone)
        memory = cache.memory()
        self._kv_cache_bytes = memory["kv_bytes"]
        self._state_bytes = memory["state_bytes"]
        self._state_dtype = memory["state_dtype"]
        self._window_bytes = memory["window_bytes"]
        self._window_rows = memory["window_rows"]
        self._slots: Dict[int, _SlotInfo] = {}
        self._free = list(range(max_slots))
        self._waiting: "queue.Queue[GenRequest]" = queue.Queue()
        # Between two serving programs the scheduler's thread hands the
        # device nothing (ROADMAP A11): what a program needs from the
        # host goes in as NumPy arguments of the call itself. So a draw
        # (an admission's first token, a decode step) gets the words
        # (seed, count of draws) and its program wraps them as its key
        # (runner.draw_words), where a split here was two programs; and
        # a finished slot waits in ``_freed`` for the next decode
        # program to switch it off, where a scatter here was one.
        self._seed = int(seed) & 0xFFFFFFFF
        self._draws = 0
        self._freed: set = set()
        self._pending: List[Tuple[Any, Dict[int, int]]] = []
        # Dispatch-ahead pipeline (docs/ENGINE_PIPELINE.md): sampled
        # tokens are fetched this many steps behind dispatch, so the
        # device always has work queued while the host inspects older
        # results. 0 = serial reference mode (fetch + inline detok every
        # step) — greedy-identical to overlapped mode, used for parity.
        # Clamped: depth only buys overlap up to the device queue, and
        # every extra step is wasted compute after a slot finishes.
        self.pipeline_depth = max(0, min(int(pipeline_depth), 16))
        self.overlap = self.pipeline_depth > 0
        self._running = False
        self._fatal = ""            # set when the scheduling loop dies
        self._thread: Optional[threading.Thread] = None
        # idle wakeup: submit() signals under this condition, replacing
        # the old 2 ms poll loop (idle-spin saved is exported via the
        # flight recorder's idle_wait counter)
        self._wake = threading.Condition()
        # detokenization + SSE stream writes off the dispatch path;
        # accepted tokens accumulate here and flush as ONE coalesced
        # queue entry per drained fetch (not one per slot)
        self._detok = _DetokWorker(self)
        self._detok_batch: List[Tuple[_SlotInfo, List[int]]] = []
        # host work overlapped with device compute (detok worker + kv
        # staging/copy executor busy seconds), drained per step into the
        # flight record's host_overlap field
        self._overlap_mu = threading.Lock()
        self._overlap_s = 0.0
        self._overlap_seen = 0.0
        self._id_counter = itertools.count()
        self._step_count = 0
        self._tokens_generated = 0
        # Flight recorder: one record per scheduler step, always on
        # (observability/flight.py — the self-measured overhead ratio
        # is exported and tier-1 asserts it stays <1% of step time).
        # Its compile counters are the process's (jax.monitoring's
        # listeners are process-wide): every engine of a process reads
        # the one log, which an engine server opens before its weights.
        self.flight = _flight.FlightRecorder(
            max_slots, programs=_startup.process_programs()
        )
        # called once, with its time.time(), when this engine hands on
        # the first token of its life (the engine server's start span)
        self.on_first_token: Optional[Callable[[float], None]] = None
        # per-step accumulators reset at the top of step(); written only
        # by the scheduler thread
        self._phases = _flight.StepPhases()
        self._step_admitted: List[Tuple[str, float]] = []
        self._step_first: List[Tuple[str, float]] = []
        self._step_mode = ""
        self._step_real = 0          # tokens genuinely dispatched
        self._step_padded = 0        # tokens the padded dispatch computed
        self._step_out = 0           # tokens delivered to requests
        self._step_prompt = 0        # prompt tokens entering prefill
        # prompt tokens entering prefill, by expert dispatch (MoE only)
        self._step_moe_dispatch: Dict[str, int] = {}
        # the form of attention the step's prefill ran (MLA only)
        self._step_attn: Optional[str] = None
        # cached positions the step's decode attends / the cache allocates
        self._step_kv_live = 0
        self._step_kv_allocated = 0
        # held experts the fetched decode steps read / held x layers
        # (flight ``moe_read_pct``)
        self._step_moe_read = 0
        self._step_moe_held = 0
        self._moe_held_a_step = cfg.num_held_experts * cfg.num_moe_layers
        # a hybrid: slots whose state the step's decode step moved, and
        # prompt tokens the step sent through the chunked scan
        self._step_state_slots = 0
        self._step_ssm_tokens = 0
        # a stack with a window store: rows the step's sliding layers
        # and its full layers attended, over slots, positions and layers
        self._step_window_rows = 0
        self._step_full_rows = 0
        self._step_spec_proposed = 0
        self._step_spec_accepted = 0
        # on-demand profiler capture (capture_profile): the capturing
        # thread starts and stops the jax.profiler trace; the scheduler
        # only counts the armed capture's steps down
        self._profile_mu = threading.Lock()
        self._profile: Optional[Dict[str, Any]] = None
        self._capturing = False
        # captures begun, and (which capture, what /healthz keeps of its
        # trace's summary): the newest traced capture's
        self._captures = 0
        self._last_capture: Tuple[int, Optional[Dict[str, Any]]] = (0, None)
        # the step_num of the sched.step span the scheduler is in
        self._span_step = 0
        self.ttft_hist = LatencyHistogram(TTFT_BUCKETS_S)
        self.tpot_hist = LatencyHistogram(TPOT_BUCKETS_S)
        self.e2e_hist = LatencyHistogram(E2E_BUCKETS_S)
        # Chunked prefill (vLLM's enable-chunked-prefill role): prompts
        # longer than the chunk are prefilled chunk-by-chunk with a
        # decode step interleaved between chunks, so one long prompt
        # can't stall token cadence for every running slot. Chunks ride
        # the prefix-continuation jit path (prefill_with_prefix), so
        # each chunk's cost is one bucketed forward, never O(S^2) over
        # the whole prompt at once.
        self.prefill_chunk = 0
        if prefill_chunk > 0:
            # snap to a real bucket so chunk steps hit stable jit keys
            # (rounding UP — the effective chunk may exceed the request);
            # clamp to the top bucket: a chunk >= every possible prompt
            # makes chunking a no-op instead of a startup crash
            top = self.runner.prefill_buckets[-1]
            self.prefill_chunk = self.runner.bucket_for(
                min(prefill_chunk, top)
            )
        self._chunk_jobs: Dict[int, _ChunkJob] = {}
        self.speculative = speculative
        self.spec_tokens = max(2, spec_tokens)
        self._spec_hits = 0
        self._spec_steps = 0
        self._spec_proposed = 0   # slots x (spec_tokens-1) across steps
        # Draft-model speculation (EAGLE-class role; reference surfaces
        # EAGLE3/MTP/ngram as vLLM args, worker/backends/vllm.py:531): a
        # small proposer model runs its own slot-aligned DecodeState;
        # delivered tokens are block-ingested into its cache (catch-up),
        # it proposes spec_tokens-1 greedy continuations, and the target
        # verifies — output is bit-identical to plain greedy decode.
        self.host_kv_cache = None
        self._kv_copy_pool = None
        self._kv_stage = None
        self.kv_conv = None
        # disaggregated-serving role tag (ModelSpec prefill_replicas /
        # decode_replicas → backends --kv-role): advisory — the engine
        # serves whatever arrives; the proxy's routing and the KV
        # handoff surface (api_server /kv/export, /kv/import) are what
        # make the roles mean something
        self.kv_role = kv_role
        # KV-transfer accounting (engine/kv_transfer.py): handoff
        # bytes/blocks/failures/latency, rendered by the engine exporter
        from gpustack_tpu.engine.kv_transfer import HandoffStats

        self.kv_handoff = HandoffStats()
        if host_kv_cache_mb > 0:
            from gpustack_tpu.engine.kv_host_cache import (
                DEFAULT_BLOCK_TOKENS,
                HostKVCache,
            )

            self.host_kv_cache = HostKVCache(
                host_kv_cache_mb * 2**20,
                # <= 0 (unset, or a bad spec value — ModelSpec has no
                # range validation) falls back to the default instead
                # of crash-looping the engine process at startup
                block_tokens=(
                    kv_block_tokens if kv_block_tokens > 0
                    else DEFAULT_BLOCK_TOKENS
                ),
                int8=kv_cache_int8,
            )
            if kv_spill_mb > 0:
                from gpustack_tpu.engine.kv_spill import DiskKVSpill

                spill_dir = kv_spill_dir or os.path.join(
                    tempfile.gettempdir(),
                    f"gpustack-kv-spill-{os.getpid()}",
                )
                self.host_kv_cache.spill = DiskKVSpill(
                    spill_dir, kv_spill_mb * 2**20
                )
            # conversation index feeding the cluster KV directory:
            # the API layer records (message-chain hashes, token ids)
            # at chat finish; /kv/summary snapshots block residency
            from gpustack_tpu.engine.kv_fabric import ConvIndex

            self.kv_conv = ConvIndex()
            # device→host KV copies run off-thread: a synchronous PCIe
            # pull of a whole bucket's KV would stall the scheduler
            # thread (and every decoding slot) on each prefill miss
            self._kv_copy_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="kv-copy"
            )
            # double-buffered host→device prefix uploads ride the same
            # executor behind a two-slot stager (chunked prefill seeds)
            self._kv_stage = _KVStager(self._kv_copy_pool)
        self.draft_runner = None
        self._draft_state = None
        if speculative == "draft":
            if draft_cfg is None or draft_params is None:
                raise ValueError(
                    "speculative='draft' needs draft_cfg/draft_params"
                )
            self.draft_runner = ModelRunner(
                draft_cfg, draft_params,
                max_slots=max_slots, max_seq_len=max_seq_len,
            )
            self._draft_state = self.draft_runner.new_state()

    # ---- public API -----------------------------------------------------

    def submit(self, req: GenRequest) -> GenRequest:
        if self._fatal:
            raise ValueError(f"engine is down: {self._fatal}")
        if not req.request_id:
            req.request_id = f"req-{next(self._id_counter)}"
        req.submitted_at = time.time()
        if self.speculative:
            # Speculative verification is greedy and produces no sampled
            # distribution — REJECT incompatible requests instead of
            # silently changing their sampling semantics (round-3 trap:
            # temperature was zeroed with no signal to the API user).
            if req.temperature > 0:
                raise ValueError(
                    "this deployment runs speculative decoding, which is "
                    "greedy-only; set temperature=0 (or deploy without "
                    "--speculative) to use sampling"
                )
            if req.logprobs:
                raise ValueError(
                    "logprobs are unavailable under speculative decoding "
                    "(verification produces no per-token distribution)"
                )
            if req.embeds_override is not None:
                raise ValueError(
                    "image inputs are unavailable under speculative "
                    "decoding (the draft model has no vision tower)"
                )
            if req.logit_bias:
                raise ValueError(
                    "logit_bias is unavailable under speculative "
                    "decoding (verification argmaxes raw logits; the "
                    "bias would silently stop applying after the "
                    "first token)"
                )
        if req.logit_bias:
            from gpustack_tpu.engine.sampling import MAX_BIAS

            if len(req.logit_bias) > MAX_BIAS:
                raise ValueError(
                    f"logit_bias supports at most {MAX_BIAS} entries "
                    f"(got {len(req.logit_bias)})"
                )
            bad = [
                t for t in req.logit_bias
                if not 0 <= int(t) < self.cfg.vocab_size
            ]
            if bad:
                raise ValueError(
                    f"logit_bias token ids out of range: {bad[:5]}"
                )
        if len(req.prompt_ids) >= self.max_seq_len:
            raise ValueError(
                f"prompt of {len(req.prompt_ids)} tokens >= max_seq_len "
                f"{self.max_seq_len}"
            )
        # enqueue + notify under one lock so a submit can never slip
        # between the scheduler's emptiness check and its cv wait (the
        # classic lost wakeup)
        with self._wake:
            self._waiting.put(req)
            self._wake.notify_all()
        return req

    def generate(self, req: GenRequest, timeout: float = 300.0) -> GenRequest:
        """Blocking helper: submit and wait for completion."""
        self.submit(req)
        if not req.done.wait(timeout):
            raise TimeoutError(f"request {req.request_id} timed out")
        return req

    def start(self) -> None:
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, name="llm-engine", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        with self._wake:
            self._wake.notify_all()
        if self._thread:
            self._thread.join(timeout=30)
        # drain the detok queue so every finished request's text/done
        # landed before the engine object is abandoned
        self._detok.stop()

    def embed(self, batch_prompt_ids: List[List[int]]) -> List[List[float]]:
        """Mean-pooled, l2-normalized embeddings — one batched forward for
        the whole request. Runs directly on the runner (jax dispatch is
        thread-safe); sequence and batch dims are bucketed so jit
        specializations stay bounded."""
        for ids in batch_prompt_ids:
            if len(ids) >= self.max_seq_len:
                raise ValueError(
                    f"input of {len(ids)} tokens >= max_seq_len "
                    f"{self.max_seq_len}"
                )
        bucket = self.runner.bucket_for(
            max(1, max(len(i) for i in batch_prompt_ids))
        )
        padded = [
            list(ids) + [0] * (bucket - len(ids))
            for ids in batch_prompt_ids
        ]
        lens = [len(ids) for ids in batch_prompt_ids]
        vecs = self.runner.embed(padded, lens)
        import numpy as _np

        return _np.asarray(vecs).tolist()

    def device_info(self) -> Dict[str, Any]:
        """What this replica runs on, from the devices of the runner's
        mesh — so a replica that landed on the CPU says so. ``memory``
        is per device, where the backend reports it (the CPU does not)."""
        devices = list(self.runner.mesh.devices.flat)
        memory = []
        for d in devices:
            local = d.process_index == jax.process_index()
            stats = d.memory_stats() if local else None
            if stats:
                memory.append({
                    "id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                })
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
            "ids": [d.id for d in devices],
            "memory": memory,
        }

    def health(self) -> Dict[str, Any]:
        return {
            "status": "error" if self._fatal else "ok",
            "error": self._fatal,
            "model": self.cfg.name,
            "device": self.device_info(),
            "slots_total": self.max_slots,
            # racy-tolerated gauge: HTTP thread reads the scheduler's
            # slot list length; worst case one admit stale
            "slots_used": self.max_slots - len(self._free),  # analysis: ignore[guarded-by]
            "waiting": self._waiting.qsize(),
            "steps": self._step_count,
            "tokens_generated": self._tokens_generated,
            "prompt_tokens": self.flight.prompt_tokens_total,
            # the device's KV cache: all of it, and what one position of
            # one layer takes (ModelConfig.kv_row_shapes)
            "kv_cache_bytes": self._kv_cache_bytes,
            "kv_cache_bytes_per_token": self._kv_cache_bytes_per_token,
            # both kinds of per-slot memory: rows a position, and a
            # hybrid's recurrent state (0 and None for any other model)
            "cache": {
                "kv_bytes": self._kv_cache_bytes,
                "state_bytes": self._state_bytes,
                "state_dtype": self._state_dtype,
                # the sliding layers' rows, kept at window size (0 for a
                # model without such a store); kv_bytes is then the
                # full layers' rows
                "window_bytes": self._window_bytes,
            },
            # the recurrent state's share of what the slots hold (a
            # slot's state over its state, rows and window rows), per
            # cent; None for a model without such a state
            "state_share_pct": (
                round(100.0 * self._state_bytes / (
                    self._state_bytes + self._kv_cache_bytes
                    + self._window_bytes
                ), 2) if self._state_bytes else None
            ),
            # one chip's share of the routed experts (cfg.experts_held):
            # how many this replica holds, of how many the router scores,
            # from which id on; None where every expert is held
            "experts_held": (
                {
                    "held": self.cfg.num_held_experts,
                    "of": self.cfg.num_experts,
                    "first": self.cfg.first_held_expert,
                } if self.cfg.experts_held else None
            ),
            # how a layer that keeps a recurrent state (cfg.state_mixer:
            # "ssm" Mamba-2, "delta" gated delta rule, "kda" the same
            # rule with a decay a key channel) runs its scan
            # over a prompt and moves its state in a decode step
            # (runner.ssm_update: "kernel" the stacked state in place,
            # live slots only); None for a model without such layers
            "state_mixer": self.cfg.state_mixer,
            "ssm_scan": self.runner.ssm_scan,
            "ssm_update": self.runner.ssm_update,
            # under a share of the experts: the prefill programs' router
            # pairs by whether their expert is held here (else None)
            "moe_pairs": self.runner.moe_pairs(),
            # how every sample() finds its candidates (sampling.
            # top_candidates): "chunked: 64 of 1187 chunks of 128" or
            # "whole row of <vocabulary>"
            "sample_candidates": self.runner.sample_candidates,
            # how a decode step attends over the cache (transformer.
            # decode_attention_impl): "kernel" reads the live slots'
            # rows where they lie, "xla" every slot's slab
            "decode_attention": self.runner.decode_attention,
            # how a decode step enumerates its experts' products
            # (transformer.moe_dispatch): "touched" reads the experts
            # the live rows chose, "dense" every held one; None for a
            # model without experts
            "decode_moe_dispatch": self.runner.decode_moe_dispatch,
            "flight_overhead_ratio": round(
                self.flight.overhead_ratio(), 6
            ),
            # overlapped pipeline (docs/ENGINE_PIPELINE.md)
            "pipeline_depth": self.pipeline_depth,
            "overlap": self.overlap,
            "host_overlap_ratio": round(
                self.flight.host_overlap_ratio(), 6
            ),
            "pipeline_rollback_tokens": (
                self.flight.rollback_tokens_total
            ),
            "idle_wait_s": round(self.flight.idle_wait_s_total, 3),
            # lowerings and compiles in this process (jax.monitoring):
            # a warm engine under traffic it has seen adds none
            "programs_traced_total": self.flight.programs_traced_total,
            "programs_compiled_total": self.flight.programs_compiled_total,
            "compile_seconds_total": round(
                self.flight.compile_seconds_total, 3
            ),
            # the newest traced capture's idle time by host span
            # (capture_profile; observability/capture.py digest): None
            # until one has been summarised
            "last_capture": self._last_capture_digest(),
            # the replica's multi-chip layout as one inspectable object
            # (parallel/sharding.SpecLayout)
            "layout": self.runner.layout.describe(),
            "speculative": self.speculative,
            "spec_steps": self._spec_steps,
            "spec_extra_tokens": self._spec_hits,
            # accepted proposals / proposals made (1.0 = every proposal
            # of every slot accepted)
            "spec_acceptance_rate": round(
                self._spec_hits / max(1, self._spec_proposed), 4
            ),
            "draft_model": (
                self.draft_runner.cfg.name if self.draft_runner else ""
            ),
            "kv_cache_hits": (
                self.host_kv_cache.hits if self.host_kv_cache else 0
            ),
            "kv_cache_misses": (
                self.host_kv_cache.misses if self.host_kv_cache else 0
            ),
            "kv_cache_prefix_hits": (
                self.host_kv_cache.prefix_hits
                if self.host_kv_cache else 0
            ),
            "kv_cache_prefix_tokens_reused": (
                self.host_kv_cache.prefix_tokens_reused
                if self.host_kv_cache else 0
            ),
            "kv_cache_blocks": (
                self.host_kv_cache.entries if self.host_kv_cache else 0
            ),
            "kv_cache_host_bytes": (
                self.host_kv_cache.bytes_used if self.host_kv_cache else 0
            ),
            # disaggregated serving (docs/KV_CACHE.md "KV handoff"):
            # role tag + wire-transfer accounting
            "kv_role": self.kv_role,
            "kv_handoff": self.kv_handoff.snapshot(),
            # fleet KV fabric (docs/KV_CACHE.md "Fleet KV fabric"):
            # disk spill tier counters + fault-backs + the bounded
            # conversation index feeding the cluster directory
            "kv_spill": (
                self.host_kv_cache.spill.snapshot()
                if self.host_kv_cache and self.host_kv_cache.spill
                else {}
            ),
            "kv_faultbacks": (
                self.host_kv_cache.faultbacks
                if self.host_kv_cache else 0
            ),
            "kv_conversations": (
                len(self.kv_conv) if self.kv_conv else 0
            ),
        }

    # ---- scheduling loop ------------------------------------------------

    def _loop(self) -> None:
        while self._running:
            try:
                busy = self.step()
            except Exception as e:
                # A dead scheduling thread must be LOUD and terminal, not
                # a silent hang: fail every in-flight and queued request
                # and flip health so the serve manager's probe tears the
                # instance down (e.g. a multi-host follower that never
                # connected — engine/multihost.py raises after its
                # connect window).
                logger.exception("engine scheduling loop died")
                self._fatal = f"engine loop died: {e}"
                self._fail_all_requests(str(e))
                return
            if not busy:
                # Idle: park on the wakeup condition instead of the old
                # 2 ms poll. submit() notifies under the same lock; the
                # bounded timeout is a backstop for wake sources that
                # don't notify (aborts on queued requests). Waited
                # seconds are exported as the spin this saves.
                with self._wake:
                    if self._running and self._waiting.empty():
                        t0 = time.perf_counter()
                        self._wake.wait(timeout=0.05)
                        self.flight.note_idle_wait(
                            time.perf_counter() - t0
                        )

    def _notify_wake(self) -> None:
        with self._wake:
            self._wake.notify_all()

    def _note_overlap(self, seconds: float) -> None:
        """Worker threads report host work done concurrently with the
        scheduler here; _flight_record drains the delta per step."""
        with self._overlap_mu:
            self._overlap_s += seconds

    def _flush_detok(self) -> None:
        """Hand the accumulated (info, tokens) pairs to the detok
        worker as ONE queue entry — called once per drained fetch (and
        before any finish item, so the FIFO ordering contract holds)."""
        if self._detok_batch:
            batch, self._detok_batch = self._detok_batch, []
            self._detok.put_batch(batch)

    def _fail_all_requests(self, message: str) -> None:
        self._flush_detok()
        for info in list(self._slots.values()):
            req = info.request
            req.finish_reason = "error"
            if info.sync_detok:
                req.output_text = info.text
                if req.stream is not None:
                    req.stream.put(None)
                req.done.set()
            else:
                # the detok worker owns this request's text/stream/done;
                # queue ordering delivers any buffered tokens first
                self._detok.finish(info)
        self._slots.clear()
        # mid-chunked-prefill requests live in _chunk_jobs, not _slots —
        # they must fail just as loudly (their clients are blocked on
        # done too)
        for job in self._chunk_jobs.values():
            req = job.req
            req.finish_reason = "error"
            if req.stream is not None:
                req.stream.put(None)
            req.done.set()
        self._chunk_jobs.clear()
        while not self._waiting.empty():
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            req.finish_reason = "error"
            if req.stream is not None:
                req.stream.put(None)
            req.done.set()

    def step(self) -> bool:
        """One scheduling iteration. Returns False when fully idle."""
        # unlocked probe: False is the steady state
        if not self._capturing:  # analysis: ignore[guarded-by]
            self._phases.annotate = None
            return self._step()
        # a capture is open, from before the profiler starts until after
        # it has stopped (the steps that run while it does either are in
        # the trace too): the step and its phases also go into the
        # profiler's trace as host spans, on the clock of the device's
        # operations. Outside a profiler session an annotation does
        # nothing.
        self._phases.annotate = jax.profiler.TraceAnnotation
        self._span_step = self._step_count
        with jax.profiler.StepTraceAnnotation(
            "sched.step", step_num=self._span_step
        ):
            return self._step()

    def _step(self) -> bool:
        phases = self._phases
        phases.reset()
        t0 = time.perf_counter()
        cpu0 = time.thread_time()
        self._step_mode = ""
        self._step_real = self._step_padded = 0
        self._step_out = self._step_prompt = 0
        self._step_moe_dispatch = {}
        self._step_attn = None
        self._step_kv_live = self._step_kv_allocated = 0
        self._step_state_slots = self._step_ssm_tokens = 0
        self._step_window_rows = self._step_full_rows = 0
        self._step_moe_read = self._step_moe_held = 0
        self._step_spec_proposed = self._step_spec_accepted = 0
        self._step_admitted = []
        self._step_first = []
        # Eager-ready drain BEFORE admission: fetch whatever the device
        # already finished (non-blocking readiness probe), so a slot
        # whose request ended re-tenants THIS step instead of
        # pipeline_depth steps later. The depth is a cap on in-flight
        # work (the only place the host may block), never a mandatory
        # delay — on a fast link results drain one step after dispatch,
        # on a slow link up to `depth` dispatches proceed unfetched.
        with phases.drain:
            self._drain_ready()
        with phases.admit:
            admitted = self._admit()
        # at most one prefill chunk per step: decode cadence for running
        # slots is bounded by one chunk's latency, not a whole prompt's
        with phases.chunk:
            progressed = self._advance_chunk()
        if self._slots:
            self._decode_once()
            self._flight_record(t0, cpu0)
            return True
        if admitted or progressed or self._chunk_jobs:
            self._flight_record(t0, cpu0)
            return True
        # Nothing active: drain any lagging fetches so finished requests
        # complete deterministically.
        with phases.drain:
            self._drain_pending()
        if self._step_out or self._step_spec_accepted or self._step_first:
            # tokens delivered by the drain would otherwise vanish when
            # the next step resets the accumulators — record them so
            # flight tokens_out/spec_accepted match tokens_generated
            self._flight_record(t0, cpu0)
        return not self._waiting.empty()

    def _note_prefill(self, tokens: int, bucket: int) -> None:
        """``tokens`` prompt tokens enter a prefill program of
        ``bucket`` rows in this step."""
        self._step_real += tokens
        self._step_prompt += tokens
        self._step_padded += bucket
        self._step_attn = self.runner.attn_label(bucket)
        if self._state_bytes:
            self._step_ssm_tokens += tokens
        if self._window_bytes:
            # position i attends min(i + 1, window) keys in a sliding
            # layer and i + 1 in a full one
            w = min(tokens, self._window_rows)
            self._note_attended(
                w * (w + 1) // 2 + (tokens - w) * w,
                tokens * (tokens + 1) // 2,
            )
        dispatch = self.runner.moe_dispatch_for(bucket)
        if dispatch is not None:
            by_dispatch = self._step_moe_dispatch
            by_dispatch[dispatch] = by_dispatch.get(dispatch, 0) + tokens

    def _note_attended(self, sliding: int, full: int) -> None:
        """Rows a sliding layer and a full layer attended in this step,
        over slots and positions: counted for every layer of the kind."""
        cfg = self.cfg
        self._step_window_rows += sliding * cfg.num_window_layers
        self._step_full_rows += full * cfg.num_kv_layers

    def _flight_record(self, t0: float, cpu0: float) -> None:
        """Seal the flight record of the step that began at ``t0`` on
        the wall clock and ``cpu0`` on this thread's own (and advance an
        in-flight profiler capture). Scheduler-thread only."""
        # this thread's own CPU time in the step, read inside the wall
        # interval: what is left of dur_s beside it and the wait phase is
        # time the thread wanted to run and did not
        cpu_s = time.thread_time() - cpu0
        dur_s = time.perf_counter() - t0
        oldest = 0.0
        try:
            # peeking the queue head without its mutex is safe here:
            # worst case a racing admit swaps the head and the gauge is
            # one submit stale — observability, not control flow
            oldest = time.time() - self._waiting.queue[0].submitted_at
        except (IndexError, AttributeError):
            pass
        kv = self.host_kv_cache
        with self._overlap_mu:
            overlap_total = self._overlap_s
        overlap_delta = overlap_total - self._overlap_seen
        self._overlap_seen = overlap_total
        mode = self._step_mode or "decode"
        phases_s = self._phases.seconds
        programs = self.flight.record(
            dur_s=dur_s,
            cpu_s=cpu_s,
            host_overlap_s=max(0.0, overlap_delta),
            phases_s=phases_s,
            admitted=self._step_admitted,
            first_tokens=self._step_first,
            mode=mode,
            slots_used=self.max_slots - len(self._free),
            waiting=self._waiting.qsize(),
            oldest_wait_s=max(0.0, oldest),
            tokens_real=self._step_real,
            tokens_padded=self._step_padded,
            tokens_out=self._step_out,
            prompt_tokens=self._step_prompt,
            spec_proposed=self._step_spec_proposed,
            spec_accepted=self._step_spec_accepted,
            kv_blocks=kv.entries if kv is not None else 0,
            kv_reused_total=(
                kv.prefix_tokens_reused if kv is not None else 0
            ),
            moe_dispatch=self._step_moe_dispatch,
            # a step without a prefill went over the cache
            attn=self._step_attn or self.runner.attn_label(),
            kv_live=self._step_kv_live,
            kv_allocated=self._step_kv_allocated,
            moe_read=self._step_moe_read,
            moe_held=self._step_moe_held,
            ssm=(
                (self._step_state_slots, self._step_ssm_tokens,
                 self.cfg.state_mixer)
                if self._state_bytes else None
            ),
            attn_rows=(
                (self._step_window_rows, self._step_full_rows)
                if self._window_bytes else None
            ),
        )
        if dur_s > _SLOW_STEP_S:
            logger.warning(
                "slow scheduler step: %.0f ms (cpu %.0f ms), mode %s, %s%s",
                dur_s * 1e3, cpu_s * 1e3, mode, ", ".join(
                    f"{name} {sec * 1e3:.0f} ms"
                    for name, sec in zip(_flight.PHASES, phases_s)
                ),
                "; programs " + ", ".join(
                    f"{name} (lower {lower_ms:.0f} ms, "
                    f"{'load' if cached else 'compile'} {load_ms:.0f} ms)"
                    for name, lower_ms, load_ms, cached in programs
                ) if programs else "",
            )
        # unlocked fast-path probe: None is the steady state, and a
        # stale non-None just pays one _profile_step() lock round-trip
        if self._profile is not None:  # analysis: ignore[guarded-by]
            self._profile_step()

    # ---- on-demand profiler capture -----------------------------------

    def capture_profile(
        self, steps: int, out_dir: str = "", timeout_s: float = 30.0
    ) -> Dict[str, Any]:
        """Wrap the next ``steps`` busy scheduler steps in a
        ``jax.profiler`` trace written under ``out_dir`` (an empty
        ``out_dir`` captures the steps' flight records only) and return
        the captured step summary.

        The calling thread starts the trace, arms the countdown, waits,
        and stops the trace: the scheduler only counts steps and never
        holds ``_profile_mu`` across a profiler call, so collecting the
        trace does not stand in its way. Steps that run while the trace
        is being stopped are in the trace too, past the ones asked for.

        A capture with a trace is then summarised (``idle``:
        ``observability/capture.py``, what the host was doing while the
        chip stood idle), by a child process and on the calling thread,
        with the capture already released; ``/healthz`` keeps the newest
        one's digest (``last_capture``). A child that fails leaves
        ``{"error": ...}`` in both and the capture's answer whole.

        Blocks up to ``timeout_s`` for the steps to elapse; an idle
        engine returns whatever was captured by the deadline. One
        capture at a time — a concurrent request gets a ValueError
        (profiler state is process-global)."""
        cap: Dict[str, Any] = {
            "remaining": max(1, min(int(steps), 10_000)),
            "requested": max(1, min(int(steps), 10_000)),
            "records": [],
            # the step_num of a sched.step span -> its step's record
            "record_of": {},
            "done": threading.Event(),
        }
        with self._profile_mu:
            if self._capturing:
                raise ValueError(
                    "a profile capture is already in progress"
                )
            self._capturing = True
            self._captures += 1
            seq = self._captures
        profiler, error = "flight-only", ""
        try:
            if out_dir:
                try:
                    jax.profiler.start_trace(out_dir)
                    profiler = "jax"
                except Exception as e:  # reported; the steps still count
                    error = f"start_trace failed: {e}"
            with self._profile_mu:
                self._profile = cap
            cap["done"].wait(timeout_s)
            with self._profile_mu:
                # the idle-timeout path: the countdown never reached zero
                self._profile = None
                records = list(cap["records"])
                record_of = dict(cap["record_of"])
            if profiler == "jax":
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    error = f"stop_trace failed: {e}"
                    profiler = "flight-only"
        finally:
            with self._profile_mu:
                self._capturing = False
        result = {
            "requested": cap["requested"],
            "steps_captured": len(records),
            "profiler": profiler,
            "artifact": out_dir if profiler == "jax" else "",
            "error": error,
            "records": records,
            "aggregate": _flight.aggregate_records(
                records, self.max_slots,
                overhead_ratio=self.flight.overhead_ratio(),
            ) if records else {},
        }
        if profiler == "jax":
            idle = _capture.summarize_in_child(out_dir)
            for gap in idle.get("gaps") or ():
                # which of ``records`` is the step the gap fell in
                gap["record"] = record_of.get(gap["step_num"])
            result["idle"] = idle
            with self._profile_mu:
                if seq > self._last_capture[0]:
                    self._last_capture = (seq, _capture.digest(idle))
        return result

    def _last_capture_digest(self) -> Optional[Dict[str, Any]]:
        with self._profile_mu:
            return self._last_capture[1]

    def _profile_step(self) -> None:
        """Advance the armed capture by one recorded step (scheduler
        thread; the lock only guards the handoff with the capturing
        thread, never a profiler call or device work)."""
        with self._profile_mu:
            cap = self._profile
            if cap is None:
                return
            snap = self.flight.snapshot(limit=1)
            if snap:
                cap["records"].append(snap[-1])
                # (a step that began before the capture did has no span)
                if self._phases.annotate is not None:
                    cap["record_of"].setdefault(
                        self._span_step, len(cap["records"]) - 1
                    )
            cap["remaining"] -= 1
            if cap["remaining"] <= 0:
                self._profile = None
                cap["done"].set()

    def _plan_chunk_job(
        self, req: GenRequest, ids, matched: int = 0
    ) -> "Optional[_ChunkJob]":
        """Chunk schedule for a long prompt, seeded from the host KV
        cache's matched block run (``matched``, probed once by the
        caller) when one fits. Returns None when any continuation would
        overflow the top bucket (possible with non-power-of-two
        max_seq_len shapes) — the caller then falls back to one-shot
        prefill, which always fits."""
        top = self.runner.prefill_buckets[-1]

        def fits(start: int) -> bool:
            # every continuation writes its suffix block at
            # [start, start + sb); dynamic_update_slice CLAMPS
            # out-of-range starts, so overflow = silent corruption —
            # same bounds contract as the one-shot prefix path
            while start < len(ids):
                n = min(self.prefill_chunk, len(ids) - start)
                sb = self.runner.bucket_for(n)
                if start and start + sb > top:
                    return False
                start += n
            return True

        kv_cache = self.host_kv_cache
        if kv_cache is not None and matched > 0:
            # block granularity means the bounds guard can trim the
            # matched run block-by-block instead of rejecting it
            # outright — a partially usable prefix still saves its
            # blocks' prefill FLOPs. Trim BEFORE gathering so no KV
            # bytes are assembled for blocks the guard discards.
            plen = matched
            while plen > 0 and not fits(plen):
                plen -= kv_cache.block_tokens
            if plen > 0 and self._kv_stage is not None and fits(0):
                # double-buffered staging: the gather (host memcpy) and
                # upload (host→device) run on the kv-copy executor while
                # this and later steps decode the running slots; the
                # chunk job rendezvouses when it is actually reached.
                # fits(0) guards the eviction fallback: a run that
                # vanishes between match and gather cold-starts the job.
                fut = self._kv_stage.submit(
                    self._stage_prefix_fn(req, ids, plen, kv_cache)
                )
                return _ChunkJob(req=req, ids=list(ids), pending_kv=fut)
            got = (
                self._gather_and_upload(req, ids, plen, kv_cache)
                if plen > 0 else None
            )
            if got is not None:
                k, v, _ = got
                return _ChunkJob(
                    req=req, ids=list(ids), done=plen, k=k, v=v,
                )
        if fits(0):
            return _ChunkJob(req=req, ids=list(ids))
        return None

    def _gather_and_upload(self, req, ids, plen: int, kv_cache):
        """Gather a matched block run from host RAM and upload it at
        bucket width. Returns ``(k, v, plen)``, or None when the run
        evicted between match and gather. Hit counters and the request's
        attribution are recorded here, success-only — the ONE
        implementation behind both the staged (executor) and cold
        (inline fallback) prefix paths, so their accounting can't
        drift."""
        got = kv_cache.gather_prefix(list(ids), plen)
        if got is None:
            return None
        pk, pv = got
        kv_cache.prefix_hits += 1
        kv_cache.prefix_tokens_reused += plen
        req.prefix_tokens_reused = plen
        t0 = time.time()
        k, v = self._upload_prefix(pk, pv, plen)
        req.kv_upload_s = time.time() - t0
        return k, v, plen

    def _stage_prefix_fn(self, req, ids, plen: int, kv_cache):
        """Build the kv-copy-executor job for a chunked prefix seed
        (``_upload_prefix`` blocks off-thread — that wait IS the
        overlap being bought)."""
        ids_t = tuple(ids)

        def stage():
            t0 = time.perf_counter()
            try:
                return self._gather_and_upload(
                    req, list(ids_t), plen, kv_cache
                )
            finally:
                self._note_overlap(time.perf_counter() - t0)
                self._notify_wake()
        return stage

    def _resolve_staged_prefix(self, job: "_ChunkJob") -> None:
        """Rendezvous with a staged gather+upload — the designated wait
        point (may block when the job is reached before the upload
        lands, i.e. when there was no decode work to overlap with). A
        failed or evicted stage cold-starts the job."""
        fut, job.pending_kv = job.pending_kv, None
        try:
            with self._phases.wait:
                got = fut.result()
        except Exception as e:
            logger.warning(
                "prefix staging failed; cold chunked prefill: %s", e
            )
            got = None
        if got is not None:
            job.k, job.v, job.done = got

    def _upload_prefix(self, pk, pv, use_len: int):
        """Upload a matched prefix run padded to its BUCKET width, not
        its exact block-multiple length: prefill_with_prefix jit-keys on
        (Pb, Tsb, total_bucket), so exact widths would compile one fresh
        executable per distinct matched length — bucket padding keeps the
        key set as bounded as v1's bucket-stored arrays. Pad rows sit at
        positions >= use_len: overwritten by the suffix's own writes or
        invisible through the causal mask (the prefix-prefill invariant).
        Blocks until resident so the caller's kv_upload timing is
        honest (prefill would stall on the transfer anyway)."""
        import jax.numpy as jnp

        pw = self.runner.bucket_for(use_len)
        if pk.shape[1] >= pw:
            k_host, v_host = pk[:, :pw], pv[:, :pw]
        else:
            pad = ((0, 0), (0, pw - pk.shape[1]), (0, 0), (0, 0))
            k_host = np.pad(pk, pad)
            v_host = np.pad(pv, pad)
        k = jnp.asarray(k_host)
        v = jnp.asarray(v_host)
        jax.block_until_ready((k, v))
        return k, v

    def _advance_chunk(self) -> bool:
        """Run ONE chunk of the oldest runnable in-progress chunked
        prefill. A job whose staged prefix upload is still in flight is
        passed over while any decode work exists — that concurrency is
        the double-buffer win; with nothing else to run, the oldest
        upload is awaited instead."""
        if not self._chunk_jobs:
            return False
        slot = job = None
        for s, j in self._chunk_jobs.items():
            if j.pending_kv is None or j.pending_kv.done():
                slot, job = s, j
                break
        if job is None:
            if self._slots:
                return False   # decode while the upload lands
            slot = next(iter(self._chunk_jobs))
            job = self._chunk_jobs[slot]
        if job.req.aborted.is_set():
            # abandon the remaining chunks; the slot never activated
            del self._chunk_jobs[slot]
            self._free.append(slot)
            abort_op = getattr(self.runner, "chunk_abort", None)
            if abort_op is not None and job.done > 0 and not job.one_shot:
                # multi-host: followers drop their chunk register too,
                # or the aborted prompt's partial K/V stays pinned in
                # device memory until the next chunked job (one-shot
                # jobs never touched a chunk register)
                abort_op()
            self._finish_aborted(job.req)
            return True
        if job.pending_kv is not None:
            self._resolve_staged_prefix(job)
        if job.one_shot:
            self._advance_one_shot(slot, job)
            return True
        start = job.done
        chunk = job.ids[start : start + self.prefill_chunk]
        self._step_mode = self._step_mode or "prefill_chunk"
        self._note_prefill(len(chunk), self.runner.bucket_for(len(chunk)))
        # chunk-specific runner entry points exist on the multi-host
        # BroadcastingRunner (separate follower register + no device
        # arrays on the wire); the single-host runner serves both roles
        # with its plain methods
        r = self.runner
        if start == 0:
            b = r.bucket_for(len(chunk))
            padded = list(chunk) + [0] * (b - len(chunk))
            fn = getattr(r, "prefill_chunk", None) or r.prefill
            job.last, job.k, job.v = fn(padded, len(chunk))
        else:
            sb = r.bucket_for(len(chunk))
            total_bucket = r.bucket_for(start + sb)
            padded = list(chunk) + [0] * (sb - len(chunk))
            fn = (
                getattr(r, "prefill_continue_chunk", None)
                or r.prefill_with_prefix
            )
            job.last, job.k, job.v = fn(
                job.k, job.v, start, padded, len(chunk), total_bucket
            )
        job.done += len(chunk)
        if job.done >= len(job.ids):
            del self._chunk_jobs[slot]
            ids = job.ids
            # block insert trims to full blocks <= len(ids); the copy
            # worker trims the (continuation-padded) arrays to match
            self._submit_kv_copy(ids, job.k, job.v, len(ids))
            commit = getattr(self.runner, "chunk_commit", None)
            if commit is not None:
                # multi-host: followers promote their chunk register so
                # the sample_first/insert pair replays the right arrays
                commit()
            self._finalize_start(slot, job.req, job.last, job.k, job.v)
        return True

    def _advance_one_shot(self, slot: int, job: "_ChunkJob") -> None:
        """Complete a deferred one-shot prefill: the staged prefix (if
        it landed — an evicted or failed stage leaves ``done == 0`` and
        the job cold-starts) plus ONE bucketed forward over the entire
        suffix, then slot activation. Greedy-identical to the old
        inline path; only the scheduler-blocking gather+upload moved
        onto the stager."""
        req, ids = job.req, job.ids
        r = self.runner
        self._step_mode = self._step_mode or "prefill"
        if job.done > 0:
            suffix = ids[job.done:]
            sb = r.bucket_for(len(suffix))
            total_bucket = r.bucket_for(job.done + sb)
            self._note_prefill(len(suffix), sb)
            padded = list(suffix) + [0] * (sb - len(suffix))
            last_logits, k, v = r.prefill_with_prefix(
                job.k, job.v, job.done, padded, len(suffix),
                total_bucket,
            )
        else:
            bucket = r.bucket_for(max(1, len(ids)))
            self._note_prefill(len(ids), bucket)
            padded = list(ids) + [0] * (bucket - len(ids))
            last_logits, k, v = r.prefill(padded, len(ids))
        del self._chunk_jobs[slot]
        self._submit_kv_copy(ids, k, v, len(ids))
        self._finalize_start(slot, req, last_logits, k, v)

    # admit as many waiting requests as there are free slots
    def _admit(self) -> bool:
        admitted = False
        while self._free and not self._waiting.empty():
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                break
            if req.aborted.is_set():
                # client gone while queued: never spend a prefill on it
                self._finish_aborted(req)
                continue
            self._step_admitted.append(
                (req.trace_id, time.time() - req.submitted_at)
            )
            slot = self._free.pop(0)
            self._start_request(slot, req)
            admitted = True
        return admitted

    def _finish_aborted(self, req: GenRequest) -> None:
        """Terminal bookkeeping for a request aborted before it owned a
        slot (queued, or mid-chunked-prefill)."""
        req.finish_reason = "abort"
        req.finished_at = time.time()
        if req.stream is not None:
            req.stream.put(None)
        req.done.set()

    def _start_request(self, slot: int, req: GenRequest) -> None:
        ids = req.prompt_ids
        bucket = self.runner.bucket_for(max(1, len(ids)))
        padded = list(ids) + [0] * (bucket - len(ids))
        if req.embeds_override is not None:
            # VLM prompt: placeholder ids alias across different images,
            # so the token-keyed host KV cache and chunked prefill don't
            # apply — one fused prefill with the embedding override
            self._step_mode = self._step_mode or "prefill"
            self._note_prefill(len(ids), bucket)
            embeds, mask = self._padded_embeds(req, bucket, len(ids))
            last_logits, k, v = self.runner.prefill_with_embeds(
                padded, len(ids), embeds, mask
            )
            self._finalize_start(slot, req, last_logits, k, v)
            return
        # ONE prefix probe per request (counts one hit or miss), shared
        # by the chunked and one-shot paths. Local read: the copy worker
        # may null host_kv_cache concurrently.
        kv_cache = self.host_kv_cache
        matched = (
            kv_cache.match_prefix_len(ids) if kv_cache is not None else 0
        )
        if (
            self.prefill_chunk
            and len(ids) > self.prefill_chunk
            and (job := self._plan_chunk_job(req, ids, matched)) is not None
        ):
            # long prompt: prefill in chunks, one per scheduler step
            # (the step loop interleaves decode between chunks; the job
            # planner seeds from the host cache's matched block run)
            self._step_mode = self._step_mode or "prefill_chunk"
            self._chunk_jobs[slot] = job
            return
        use_len = matched
        if use_len > 0:
            top = self.runner.prefill_buckets[-1]
            # cache bounds contract: the suffix BLOCK (bucketed) must
            # fit above the prefix within a REAL bucket —
            # dynamic_update_slice clamps out-of-range writes and would
            # silently corrupt the tail. Block granularity lets the
            # guard trim the matched run one block at a time instead of
            # rejecting the whole match; trimming happens BEFORE any KV
            # bytes are assembled.
            while use_len > 0:
                sb = self.runner.bucket_for(len(ids) - use_len)
                if use_len + sb <= top:
                    break
                use_len -= kv_cache.block_tokens
        if use_len > 0 and self._kv_stage is not None:
            # Deferred one-shot prefill: the gather+upload used to run
            # INLINE here, blocking the scheduler (and every decoding
            # slot) on the host→device copy. It now rides the same
            # two-slot stager as the chunked path — the slot holds a
            # one-shot job whose single "chunk" is the entire suffix,
            # and decode proceeds while the upload lands.
            fut = self._kv_stage.submit(
                self._stage_prefix_fn(req, ids, use_len, kv_cache)
            )
            self._chunk_jobs[slot] = _ChunkJob(
                req=req, ids=list(ids), pending_kv=fut, one_shot=True,
            )
            return
        prefix = (
            kv_cache.gather_prefix(ids, use_len) if use_len > 0 else None
        )
        if prefix is not None:
            pk, pv = prefix
            # prefix reuse: upload the cached block run, prefill only
            # the suffix from that offset. Counted here, not in the
            # lookup — a match the bounds guard rejected (or that
            # evicted before the gather) saved nothing.
            kv_cache.prefix_hits += 1
            kv_cache.prefix_tokens_reused += use_len
            req.prefix_tokens_reused = use_len
            suffix = ids[use_len:]
            sb = self.runner.bucket_for(len(suffix))
            total_bucket = self.runner.bucket_for(use_len + sb)
            self._step_mode = self._step_mode or "prefill"
            self._note_prefill(len(suffix), sb)
            t0 = time.time()
            with self._phases.wait:
                pk_dev, pv_dev = self._upload_prefix(pk, pv, use_len)
            req.kv_upload_s = time.time() - t0
            suffix_padded = list(suffix) + [0] * (sb - len(suffix))
            last_logits, k, v = self.runner.prefill_with_prefix(
                pk_dev, pv_dev, use_len, suffix_padded, len(suffix),
                total_bucket,
            )
            mixer = ()
        else:
            self._step_mode = self._step_mode or "prefill"
            self._note_prefill(len(ids), bucket)
            # with what the slot keeps beside its rows, if anything
            last_logits, k, v, *mixer = self.runner.prefill(padded, len(ids))
        if kv_cache is not None:
            self._submit_kv_copy(ids, k, v, len(ids))
        self._finalize_start(slot, req, last_logits, k, v, *mixer)

    @staticmethod
    def _padded_embeds(req: GenRequest, bucket: int, n_ids: int):
        """Bucket-pad a VLM request's override embeddings (host-side np
        prep — kept out of the declared dispatch functions)."""
        embeds, mask = req.embeds_override
        pad_rows = bucket - n_ids
        embeds = np.pad(
            np.asarray(embeds, np.float32), ((0, pad_rows), (0, 0))
        )
        mask = np.pad(np.asarray(mask, bool), (0, pad_rows))
        return embeds, mask

    def _submit_kv_copy(self, seq, k_dev, v_dev, total: int) -> None:
        """Queue an async device→host copy + block insert of ``seq``'s
        KV. The device arrays may be wider than ``total`` (bucket or
        prefix-continuation padding); they are trimmed host-side in the
        copy worker. Shared by the prefill-time and finish-time stores
        so the disable-on-error path exists exactly once."""
        kv_cache = self.host_kv_cache
        if kv_cache is None or self._kv_copy_pool is None:
            return

        def copy_to_host(
            seq=tuple(seq), k_=k_dev, v_=v_dev,
            kv_cache=kv_cache, total=total,
        ):
            try:
                kv_cache.insert_sequence(
                    seq,
                    np.asarray(k_)[:, :total],
                    np.asarray(v_)[:, :total],
                )
            except RuntimeError as e:
                # non-addressable shards (defensive: backends gates
                # multi-host off already)
                logger.warning("disabling host KV cache: %s", e)
                self.host_kv_cache = None

        try:
            self._kv_copy_pool.submit(copy_to_host)
        except RuntimeError:
            # pool shut down (engine stopping) — skip the store; the
            # cache is an optimization, never required for correctness
            pass

    def kv_import_prepared(self, tokens, prepared):
        """Land a handed-off block run (already wire-decoded and
        converted to the cache's tier) through the ``_KVStager`` so the
        scheduler — and therefore every decoding slot — never stalls on
        the transfer. Returns a ``concurrent.futures.Future`` resolving
        to the number of blocks attached (0 when the cache is off)."""
        kv_cache = self.host_kv_cache

        def land():
            if kv_cache is None:
                return 0
            t0 = time.perf_counter()
            try:
                n = kv_cache.import_blocks(tokens, prepared)
                self.kv_handoff.blocks_in += n
                return n
            finally:
                self._note_overlap(time.perf_counter() - t0)

        if self._kv_stage is not None:
            return self._kv_stage.submit(land)
        fut = concurrent.futures.Future()
        try:
            fut.set_result(land())
        except Exception as e:  # pragma: no cover - cache insert bug
            fut.set_exception(e)
        return fut

    def _store_finished_sequence(self, slot: int, req: GenRequest) -> None:
        """Cache the FULL finished sequence (prompt + generated tokens)
        so turn N+1 of a conversation prefix-hits the blocks turn N
        decoded — the multi-turn/agent-loop win block granularity
        exists for. Rides the same kv-copy executor as the prefill
        store. Single-host only by construction: worker/backends.py
        never passes ``host_kv_cache_mb`` to multi-host replicas, so
        the decode-state rows sliced here are always addressable."""
        kv_cache = self.host_kv_cache
        if kv_cache is None or self._kv_copy_pool is None:
            return
        if req.embeds_override is not None:
            # VLM prompt: placeholder ids alias across different images,
            # so image-conditioned KV must never enter the token-keyed
            # cache (same exclusion as the prefill-time paths)
            return
        # Drop the trailing output token: a sampled token's KV is only
        # written on device when it is *fed* on a later step, which may
        # not have happened for the final one by finish time. Every
        # earlier token was fed (its successor was sampled from it).
        seq = list(req.prompt_ids) + list(req.output_ids[:-1])
        bt = kv_cache.block_tokens
        if len(seq) // bt <= len(req.prompt_ids) // bt:
            # no full block beyond what the prefill-time store already
            # indexed — skip the device pull entirely
            return
        total = len(seq)
        # slice at a bucketed width so the dispatched slice executables
        # stay bounded; trim to the true length host-side in the worker
        width = self.runner.bucket_for(total)
        k_dev, v_dev = self.runner.slot_kv(self._state, slot, width)
        self._submit_kv_copy(seq, k_dev, v_dev, total)

    def _new_slot_info(self, req: GenRequest) -> _SlotInfo:
        info = _SlotInfo(request=req)
        # Stop strings and JSON-mode termination decide WHICH tokens
        # count from decoded text, so their detok must stay inline on
        # the scheduler (decision before the next delivery) — plain
        # requests stream through the detok worker in overlap mode.
        info.sync_detok = (
            not self.overlap
            or bool(req.stop_texts)
            or req.json_mode
        )
        if req.json_mode:
            from gpustack_tpu.engine.openai_tools import JsonScanner

            info.json_scan = JsonScanner()
        if self.speculative == "ngram":
            info.ngram = _NgramIndex(req.prompt_ids)
        return info

    def _finalize_start(
        self, slot: int, req: GenRequest, last_logits, k, v, mixer=None
    ) -> None:
        """Insert a finished prefill into the decode state and feed the
        first sampled token (shared by the one-shot, cached and chunked
        prefill paths).

        Overlap mode: the sampled token never touches the host here —
        ``insert`` consumes it as a device scalar, and the host learns
        it through the fetch pipeline like any decode token, so
        admission N+1 dispatches while N's prefill+sample is still in
        flight on device. Speculative modes (the proposers need exact
        host state) and logprobs requests (per-token arrays wanted
        immediately) take the synchronous path.
        """
        ids = req.prompt_ids
        # First generated token through the runner's device sampler
        # (multi-host followers replay the same call). Seeded rows draw
        # noise from fold_in(seed, position); decode samples token 2 at
        # position len(ids) (pre-increment), so the first token uses
        # len(ids)-1 to keep every draw's stream unique — a collision
        # would replay identical gumbel noise on two consecutive,
        # similarly-distributed steps.
        seed = 0 if req.seed is None else int(req.seed) & 0xFFFFFFFF
        toks, tok_lp, top_ids, top_lps = self.runner.sample_first(
            last_logits, req.temperature, req.top_k, req.top_p,
            seed, req.seed is not None, len(ids) - 1, self._draw(),
            logit_bias=req.logit_bias,
        )
        if (
            self.overlap
            and not self.speculative
            and not req.logprobs
            and getattr(self.runner, "supports_async_insert", False)
        ):
            self._insert(slot, req, k, v, toks, seed, mixer)
            self._slots[slot] = self._new_slot_info(req)
            # deferred first-token feed: fetched (and rolled back if the
            # request was aborted meanwhile) with the decode pipeline
            self._pending.append(
                (("first", toks), {slot: req.request_id})
            )
            return
        self._finalize_start_sync(
            slot, req, k, v, seed, toks, tok_lp, top_ids, top_lps, mixer
        )

    def _finalize_start_sync(
        self, slot, req, k, v, seed, toks, tok_lp, top_ids, top_lps,
        mixer=None,
    ) -> None:
        """Synchronous first-token path (serial mode, speculative
        proposers, logprobs, multi-host broadcast runners): reads the
        sampled token to the host before insert — a designated sync."""
        ids = req.prompt_ids
        # read whole and indexed here: ``toks[0]`` on the device's array
        # is a program
        with self._phases.wait:
            first = int(np.asarray(toks)[0])
        first_lps = None
        if req.logprobs:
            first_lps = [(
                float(np.asarray(tok_lp)[0]),
                [
                    (int(i), float(lp))
                    for i, lp in zip(
                        np.asarray(top_ids)[0], np.asarray(top_lps)[0]
                    )
                ],
            )]
        self._insert(slot, req, k, v, first, seed, mixer)
        info = self._new_slot_info(req)
        if self.draft_runner is not None:
            # mirror the slot on the draft: prefill + insert (greedy)
            dk_bucket = self.draft_runner.bucket_for(max(1, len(ids)))
            d_padded = list(ids) + [0] * (dk_bucket - len(ids))
            _, dk, dv, *d_mixer = self.draft_runner.prefill(
                d_padded, len(ids)
            )
            self._draft_state = self.draft_runner.insert(
                self._draft_state, dk, dv, slot, len(ids), first,
                0.0, 0, 1.0, **({"mixer": d_mixer[0]} if d_mixer else {}),
            )
        self._slots[slot] = info
        self._deliver(slot, info, [first], first_lps)
        # admission-time delivery: its own coalesced entry (the fetch
        # pipeline's flush points never see this path)
        self._flush_detok()
        if self.draft_runner is not None and slot in self._slots:
            # `first` is already the draft's pending last token (set at
            # insert); queueing it again would double-feed it
            self._slots[slot].pending_draft.clear()

    def _insert(self, slot, req, k, v, first, seed, mixer) -> None:
        """The prefill's rows into ``slot``, live from here on: a slot
        whose last request ended since the last step is no longer one
        to switch off."""
        self._freed.discard(slot)
        self._state = self.runner.insert(
            self._state, k, v, slot, len(req.prompt_ids), first,
            req.temperature, req.top_k, req.top_p,
            seed, req.seed is not None, req.logit_bias,
            **({} if mixer is None else {"mixer": mixer}),
        )

    def _draw(self) -> np.ndarray:
        """The next draw's key as the programs take it
        (``runner.draw_words``): the engine's seed and how many draws
        it has made, this one included."""
        self._draws += 1
        return np.array(
            [self._seed, self._draws & 0xFFFFFFFF], np.uint32
        )

    def _flush_freed(self) -> None:
        """Switch the finished slots off now, a program each
        (``runner.deactivate``): for a step that takes no ``freed``, a
        verify step or a follower's replay, neither of which a
        benchmark cell runs."""
        for slot in sorted(self._freed):
            self._state = self.runner.deactivate(self._state, slot)
        self._freed.clear()

    def _decode_once(self) -> None:
        phases = self._phases
        if self.draft_runner is not None and self._spec_safe():
            # Drain the fetch pipeline first: a draft chain must continue
            # the target's ACTUAL last token — proposing from a lagged
            # context misaligns the whole chain and collapses acceptance
            # (the ngram proposer tolerates lag; a sequential draft does
            # not). One host sync per spec step, amortized over up to
            # spec_tokens generated tokens.
            with phases.drain:
                self._drain_pending()
        with phases.dispatch:
            dispatched = self._dispatch_decode()
        # The depth caps what is in flight, so as many fetches as it
        # takes: an admission's first token is an entry of its own, and
        # one fetch a step let the line grow by one with every admission
        # for as long as the device, not the host, set the pace, each new
        # prefill behind all of it. (Not inside ``_admit``: a host that
        # waits there gathers arrivals into one step, prefill after
        # prefill with no decode step between them.)
        while dispatched and len(self._pending) > self.pipeline_depth:
            with phases.drain:
                self._process_fetch(*self._pending.pop(0))

    def _dispatch_decode(self) -> bool:
        """Hand the device its next decode (or verify) step; False when
        no slot is live."""
        # Snapshot slot ownership at dispatch time: by the time this step's
        # tokens are fetched (lagged), a slot may have been retired and
        # re-used — the request_id check drops such stale tokens.
        owners = {
            s: info.request.request_id for s, info in self._slots.items()
        }
        if not owners:
            return False
        if self.speculative == "ngram" and self._spec_safe():
            self._flush_freed()
            proposals = self._build_proposals()
            self._state, tokens, produced = self.runner.verify_step(
                self._state, proposals
            )
            self._spec_steps += 1
            self._spec_proposed += len(owners) * (self.spec_tokens - 1)
            self._pending.append((("spec", (tokens, produced)), owners))
            self._note_spec_dispatch(len(owners))
        elif self.draft_runner is not None and self._spec_safe():
            self._flush_freed()
            proposals = self._draft_propose()
            self._state, tokens, produced = self.runner.verify_step(
                self._state, proposals
            )
            self._spec_steps += 1
            self._spec_proposed += len(owners) * (self.spec_tokens - 1)
            self._pending.append((("spec", (tokens, produced)), owners))
            self._note_spec_dispatch(len(owners))
        else:
            # a mask of its own a step (the call may read it after this
            # thread has gone on), and none where no slot waits: a
            # runner that replays its calls never has one and takes none
            freed = {}
            if self._freed:
                mask = np.zeros((self.max_slots,), np.bool_)
                mask[list(self._freed)] = True
                self._freed.clear()
                freed = {"freed": mask}
            self._state, out = self.runner.decode_step(
                self._state, self._draw(), **freed
            )
            self._pending.append((("decode", out), owners))
            # decode runs every slot whether or not it is active: the
            # idle-slot share is the decode side of padding waste
            self._step_mode = self._step_mode or "decode"
            self._step_real += len(owners)
            self._step_padded += self.max_slots
            # what the step's live slots attend of what the cache
            # allocates, from the scheduler's own counts (flight
            # ``kv_live_pct``)
            lengths = [
                len(info.request.prompt_ids) + len(info.request.output_ids)
                for info in self._slots.values()
            ]
            self._step_kv_live += sum(lengths)
            self._step_kv_allocated += self.max_slots * self.max_seq_len
            if self._state_bytes:
                self._step_state_slots += len(owners)
            if self._window_bytes:
                self._note_attended(
                    sum(min(n, self._window_rows) for n in lengths),
                    sum(lengths),
                )
        self._step_count += 1
        return True

    def _note_spec_dispatch(self, active: int) -> None:
        """Flight accounting for one verify step: every slot computes
        spec_tokens positions whether active or not."""
        self._step_mode = self._step_mode or "spec_verify"
        self._step_real += active * self.spec_tokens
        self._step_padded += self.max_slots * self.spec_tokens
        self._step_spec_proposed += active * (self.spec_tokens - 1)

    # ---- speculative decoding (greedy n-gram) -------------------------

    def _spec_safe(self) -> bool:
        """Spec steps write P KV slots contiguously; stay clear of the
        cache end (host view lags by pipeline_depth steps, so add
        margin)."""
        margin = self.spec_tokens * (self.pipeline_depth + 2)
        for info in self._slots.values():
            req = info.request
            used = len(req.prompt_ids) + len(req.output_ids)
            if used + margin >= self.max_seq_len:
                return False
        return True

    def _build_proposals(self) -> np.ndarray:
        """N-gram lookup on each slot's (lagged) context via the
        incremental index — O(1) per slot per step."""
        P = self.spec_tokens
        proposals = np.zeros((self.max_slots, P), dtype=np.int32)
        for slot, info in self._slots.items():
            if info.ngram is None:
                continue
            prop = info.ngram.propose(P - 1)
            if prop:
                proposals[slot, : len(prop)] = prop
        return proposals

    def _draft_propose(self) -> np.ndarray:
        """Draft-model proposals [B, spec_tokens].

        1. catch-up: block-ingest each slot's delivered-but-uningested
           tokens into the draft cache (one jitted forward),
        2. propose: spec_tokens-1 greedy draft decode steps,
        3. rewind: restore the draft's positions/last_tokens — the
           speculative cache entries sit above the restored positions and
           are invisible until genuinely accepted tokens overwrite them.

        The draft sees the host's (fetch-lagged) view of each sequence —
        like the ngram proposer, this affects acceptance rate only; the
        target's verify step guarantees greedy-exact output.
        """
        P = self.spec_tokens
        ingest_width = max(
            (len(i.pending_draft) for i in self._slots.values()),
            default=0,
        )
        if ingest_width:
            # bound jit specializations: pad the block to the next power
            # of two, ingest at most 2P per step (leftover stays queued)
            ingest_width = min(ingest_width, 2 * P)
            width = 1
            while width < ingest_width:
                width *= 2
            block = np.zeros((self.max_slots, width), np.int32)
            counts = np.zeros((self.max_slots,), np.int32)
            for slot, info in self._slots.items():
                take = info.pending_draft[:width]
                info.pending_draft = info.pending_draft[len(take):]
                block[slot, : len(take)] = take
                counts[slot] = len(take)
            self._draft_state = self.draft_runner.ingest_step(
                self._draft_state, block, counts
            )
        snap = self.draft_runner.snapshot_sequence(self._draft_state)
        proposals = np.zeros((self.max_slots, P), np.int32)
        key = np.zeros((2,), np.uint32)  # the draft is greedy; key unused
        for j in range(P - 1):
            self._draft_state, out = self.draft_runner.decode_step(
                self._draft_state, key
            )
            with self._phases.wait:
                proposals[:, j] = np.asarray(out[0])
        self._draft_state = self.draft_runner.restore_sequence(
            self._draft_state, snap
        )
        return proposals

    @staticmethod
    def _entry_ready(entry) -> bool:
        """Non-blocking: has the device finished computing this pending
        entry's tokens? (hasattr-guarded — jax builds in this container
        drift across 0.4.x; without the probe, entries wait out the
        full pipeline depth, which is correct, just lazier)."""
        (kind, payload), _ = entry
        arr = payload if kind == "first" else payload[0]
        ready = getattr(arr, "is_ready", None)
        return bool(ready()) if ready is not None else False

    def _drain_ready(self) -> None:
        """Fetch every leading pending entry whose device work already
        completed — the fetches are free (no wait), and delivering them
        promptly keeps slot turnover at serial-mode latency."""
        while self._pending and self._entry_ready(self._pending[0]):
            self._process_fetch(*self._pending.pop(0))

    def _drain_pending(self) -> None:
        while self._pending:
            self._process_fetch(*self._pending.pop(0))

    def _process_fetch(self, out, owners: Dict[int, str]) -> None:
        kind, payload = out
        lp_arr = top_ids_arr = top_lps_arr = None
        if kind == "first":
            # deferred first token from an overlapped admission: one row
            ((slot, owner_id),) = owners.items()
            info = self._slots.get(slot)
            if info is None or info.request.request_id != owner_id:
                # admission was aborted/finished before the fetch —
                # the speculative feed rolls back
                self.flight.note_rollback(1)
                return
            with self._phases.wait:
                first = int(np.asarray(payload)[0])
            self._deliver(slot, info, [first])
            self._flush_detok()
            return
        # the sync point (lagged): the one place a decode step's result
        # makes the scheduler's thread wait for the device
        with self._phases.wait:
            if kind == "spec":
                tok_arr, produced = [np.asarray(x) for x in payload]
            else:
                tokens, tok_lp, top_ids, top_lps, *experts_read = payload
                tok_arr = np.asarray(tokens)[:, None]
                produced = None
                lp_arr = np.asarray(tok_lp)
                top_ids_arr = np.asarray(top_ids)
                top_lps_arr = np.asarray(top_lps)
                if experts_read:   # a model with experts
                    self._step_moe_read += int(experts_read[0])
                    self._step_moe_held += self._moe_held_a_step
        for slot, owner_id in owners.items():
            n = (
                int(produced[slot]) if produced is not None
                else tok_arr.shape[1]
            )
            info = self._slots.get(slot)
            if info is None or info.request.request_id != owner_id:
                # rollback: this step was dispatched before a lagged
                # fetch ended (or re-tenanted) the slot — its tokens
                # never existed as far as any request is concerned
                if n > 0:
                    self.flight.note_rollback(n)
                continue
            if n <= 0:
                continue
            if produced is not None:
                self._spec_hits += n - 1
                self._step_spec_accepted += n - 1
            lps = None
            if lp_arr is not None and info.request.logprobs:
                lps = [(
                    float(lp_arr[slot]),
                    [
                        (int(i), float(lp))
                        for i, lp in zip(top_ids_arr[slot], top_lps_arr[slot])
                    ],
                )]
            self._deliver(
                slot, info, [int(t) for t in tok_arr[slot, :n]], lps
            )
        # coalesce: every slot's accepted tokens from THIS fetch ride
        # one detok queue entry
        self._flush_detok()

    def _deliver(
        self, slot: int, info: _SlotInfo, toks: List[int], lps=None
    ) -> None:
        """Deliver newly generated tokens (``lps``: optional aligned list
        of (token_logprob, [(id, logprob) alternatives])). Termination
        is decided here at the id level; detokenization either runs
        inline (``sync_detok`` — serial mode, stop strings, JSON mode)
        or is batched onto the detok worker."""
        req = info.request
        if req.aborted.is_set():
            # client disconnected mid-generation: free the slot now
            # instead of decoding to max_tokens for nobody
            self._finish(slot, info, "abort")
            return
        if not req.first_token_at:
            req.first_token_at = time.time()
            self._step_first.append(
                (req.trace_id, req.first_token_at - req.submitted_at)
            )
            if self.on_first_token is not None:
                hook, self.on_first_token = self.on_first_token, None
                hook(req.first_token_at)
        offload: List[int] = []
        for j, tok in enumerate(toks):
            is_eos = tok in self.tokenizer.eos_ids or tok in req.stop_ids
            if not is_eos:
                req.output_ids.append(tok)
                if lps is not None and j < len(lps):
                    req.output_logprobs.append(lps[j][0])
                    req.output_top_logprobs.append(lps[j][1])
                self._tokens_generated += 1
                self._step_out += 1
                if info.ngram is not None:
                    info.ngram.append(tok)
                if self.draft_runner is not None:
                    info.pending_draft.append(tok)
                if info.sync_detok:
                    info.buffer_ids.append(tok)
                    if self._emit_text(info, final=False):
                        dropped = len(toks) - j - 1
                        if dropped:
                            self.flight.note_rollback(dropped)
                        self._finish(slot, info, "stop")
                        return
                else:
                    offload.append(tok)
            at_cap = (
                len(req.prompt_ids) + len(req.output_ids)
                >= self.max_seq_len - 1
            )
            if is_eos or at_cap or len(req.output_ids) >= req.max_tokens:
                dropped = len(toks) - j - 1
                if dropped:
                    self.flight.note_rollback(dropped)
                if offload:
                    self._detok_batch.append((info, offload))
                self._finish(slot, info, "stop" if is_eos else "length")
                return
        if offload:
            self._detok_batch.append((info, offload))

    def _emit_text(self, info: _SlotInfo, final: bool) -> bool:
        """Advance incremental detokenization; stream newly-safe text.

        Returns True when a stop string matched (text already truncated and
        flushed). Text that could still turn into a stop string — or a
        dangling multibyte sequence — is held back until resolved.
        """
        req = info.request
        if info.buffer_ids:
            piece = self.tokenizer.decode(info.buffer_ids)
            if final or not piece.endswith("�"):
                info.text += piece
                info.buffer_ids.clear()
        # JSON mode: the first complete top-level JSON value ends the
        # request — scan only the newly decoded chars (incremental state
        # lives in the scanner), truncate any tail past the closing
        # bracket, flush, stop.
        if info.json_scan is not None and len(info.text) > info.json_scanned:
            rel = info.json_scan.feed(info.text[info.json_scanned:])
            if rel != -1:
                info.text = info.text[: info.json_scanned + rel]
                self._push(info, info.text[info.emitted:])
                return True
            info.json_scanned = len(info.text)
        unemitted = info.text[info.emitted:]
        # Stop-string search: hold-back guarantees no stop can straddle the
        # emitted boundary, so searching the unemitted tail is complete.
        for s in req.stop_texts:
            idx = unemitted.find(s)
            if idx != -1:
                info.text = info.text[: info.emitted + idx]
                self._push(info, info.text[info.emitted:])
                return True
        hold = 0
        if not final:
            for s in req.stop_texts:
                for k in range(min(len(s) - 1, len(unemitted)), 0, -1):
                    if unemitted.endswith(s[:k]):
                        hold = max(hold, k)
                        break
        self._push(info, unemitted[: len(unemitted) - hold] if hold else unemitted)
        return False

    def _push(self, info: _SlotInfo, piece: str) -> None:
        if not piece:
            return
        info.emitted += len(piece)
        req = info.request
        if req.stream is not None:
            last = req.output_ids[-1] if req.output_ids else 0
            req.stream.put((last, piece))

    def _finish(self, slot: int, info: _SlotInfo, reason: str) -> None:
        req = info.request
        if info.sync_detok:
            # A late stop-match during the final flush upgrades the
            # reason (only sync requests can carry stop strings).
            if self._emit_text(info, final=True):
                reason = "stop"
            req.output_text = info.text
        req.finish_reason = reason
        req.finished_at = time.time()
        if reason in ("stop", "length"):
            # aborted/errored slots may have undelivered device state;
            # only cleanly finished sequences are safe to cache
            self._store_finished_sequence(slot, info.request)
        if req.first_token_at and req.submitted_at:
            self.ttft_hist.observe(req.first_token_at - req.submitted_at)
            self.e2e_hist.observe(req.finished_at - req.submitted_at)
            if len(req.output_ids) > 1:
                self.tpot_hist.observe(
                    (req.finished_at - req.first_token_at)
                    / (len(req.output_ids) - 1)
                )
        # the next decode program switches it off (``_dispatch_decode``)
        self._freed.add(slot)
        if getattr(self.runner, "replays", False):
            # multi-host: an op on the wire at the finish, as ever
            self._flush_freed()
        if self.draft_runner is not None:
            self._draft_state = self.draft_runner.deactivate(
                self._draft_state, slot
            )
        del self._slots[slot]
        self._free.append(slot)
        if info.sync_detok:
            if req.stream is not None:
                req.stream.put(None)  # sentinel: stream end
            req.done.set()
        else:
            # the final flush, stream sentinel and done event ride the
            # detok worker: flushing the coalesced batch FIRST keeps
            # the FIFO queue's ordering contract (this request's last
            # tokens precede its finish)
            self._flush_detok()
            self._detok.finish(info)
