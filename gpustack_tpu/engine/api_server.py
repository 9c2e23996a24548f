"""OpenAI-compatible HTTP front for the engine (aiohttp).

Endpoint parity with the engine-level API surface the reference proxies to
(reference gpustack/routes/openai.py registers chat/completions/embeddings
prefixes and relays the full parameter surface — tools, logprobs, n,
response_format, seed — to the backend engines, openai.py:185-313):
``/v1/completions``, ``/v1/chat/completions`` (+SSE streaming),
``/v1/models``, ``/healthz``, ``/metrics``.

Runs as a standalone process per model instance — the unit the worker's
serve manager launches and health-probes (reference
worker/serve_manager.py:1291-1412 spawns engine processes the same way).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import queue
import time
import uuid
from typing import Any, Dict, List, Optional

from aiohttp import web

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.engine.openai_tools import (
    JSON_MODE_INSTRUCTION,
    ToolCallHoldback,
    forced_function,
    parse_tool_calls,
)
from gpustack_tpu.observability.startup import EngineStart, process_programs
from gpustack_tpu.observability.tracing import LOG_FORMAT

logger = logging.getLogger(__name__)

# Reported when the request set ``seed``: OpenAI pairs seeded determinism
# with a fingerprint identifying the backend configuration.
SYSTEM_FINGERPRINT = "fp_gpustack_tpu"
MAX_N = 8          # parallel choices per request (each takes a slot)
MAX_TOP_LOGPROBS = 20


def _usage(reqs) -> Dict[str, int]:
    if isinstance(reqs, GenRequest):
        reqs = [reqs]
    # n>1 choices share one prompt: bill prompt tokens once (OpenAI
    # semantics), completions per choice
    pt = len(reqs[0].prompt_ids) if reqs else 0
    ct = sum(len(r.output_ids) for r in reqs)
    return {
        "prompt_tokens": pt,
        "completion_tokens": ct,
        "total_tokens": pt + ct,
    }


def _token_entry(tokenizer, tid: int, lp: float) -> Dict[str, Any]:
    text = tokenizer.decode([tid])
    return {
        "token": text,
        "logprob": lp,
        "bytes": list(text.encode("utf-8")),
    }


def _chat_logprobs(req: GenRequest, tokenizer) -> Dict[str, Any]:
    """OpenAI chat logprobs shape: choices[].logprobs.content[]."""
    content = []
    k = req.top_logprobs
    for tid, lp, tops in zip(
        req.output_ids, req.output_logprobs, req.output_top_logprobs
    ):
        entry = _token_entry(tokenizer, tid, lp)
        entry["top_logprobs"] = [
            _token_entry(tokenizer, i, p) for i, p in tops[:k]
        ]
        content.append(entry)
    return {"content": content}


def _completion_logprobs(req: GenRequest, tokenizer, k: int) -> Dict[str, Any]:
    """Legacy completions logprobs shape: tokens/token_logprobs/
    top_logprobs/text_offset arrays."""
    tokens, offsets = [], []
    off = 0
    for tid in req.output_ids:
        text = tokenizer.decode([tid])
        tokens.append(text)
        offsets.append(off)
        off += len(text)
    return {
        "tokens": tokens,
        "token_logprobs": list(req.output_logprobs),
        "top_logprobs": [
            {tokenizer.decode([i]): p for i, p in tops[:k]}
            for tops in req.output_top_logprobs
        ],
        "text_offset": offsets,
    }


class OpenAIServer:
    """aiohttp application serving one LLMEngine."""

    def __init__(
        self,
        engine: LLMEngine,
        model_name: Optional[str] = None,
        startup: Optional[EngineStart] = None,
    ):
        from gpustack_tpu.observability.tracing import trace_middleware

        self.engine = engine
        self.model_name = model_name or engine.cfg.name
        # the process's start span (``main`` opens it); a server built
        # inside another process has none
        self.startup = startup
        # the engine is the last hop of the trace: the middleware adopts
        # the worker proxy's traceparent and logs this hop's trace=… line.
        # Body cap matches the worker reverse proxy's (256 MiB): a KV
        # handoff push at POST /kv/import carries whole block runs —
        # the aiohttp default 1 MiB would 413 any real import
        self.app = web.Application(
            middlewares=[trace_middleware("engine")],
            client_max_size=256 * 2**20,
        )
        self.app.add_routes(
            [
                web.get("/healthz", self.healthz),
                web.get("/v1/models", self.models),
                web.post("/v1/completions", self.completions),
                web.post("/v1/chat/completions", self.chat_completions),
                web.post("/v1/embeddings", self.embeddings),
                web.post("/v1/rerank", self.rerank),
                web.get("/metrics", self.metrics),
                web.get("/debug/flight", self.debug_flight),
                web.get("/debug/startup", self.debug_startup),
                web.post("/debug/profile", self.debug_profile),
                # disaggregated prefill/decode (docs/KV_CACHE.md "KV
                # handoff"): content-addressed block export/import
                web.post("/kv/export", self.kv_export),
                web.post("/kv/import", self.kv_import),
                # fleet KV fabric (docs/KV_CACHE.md "Fleet KV fabric"):
                # directory scrape + background prefetch trigger
                web.post("/kv/summary", self.kv_summary),
                web.post("/kv/pull", self.kv_pull),
            ]
        )
        self._started = time.time()
        # lazy session for pulling handed-off KV from a peer replica
        # (the X-GPUStack-KV-Source request header names the source)
        self._kv_session = None
        # in-flight background prefetch pulls (strong refs) + outcome
        # counters for the gpustack_kv_prefetch_total metric family
        self._kv_pulls: set = set()
        self.prefetch_ok = 0
        self.prefetch_failed = 0

    # ---- endpoints ------------------------------------------------------

    async def healthz(self, request: web.Request) -> web.Response:
        health = self.engine.health()
        ok = health["status"] == "ok"
        if self.startup is not None:
            if ok:
                self.startup.mark_ready()
            health["startup"] = self.startup.summary()
        return web.json_response(health, status=200 if ok else 503)

    async def models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {
                        "id": self.model_name,
                        "object": "model",
                        "created": int(self._started),
                        "owned_by": "gpustack_tpu",
                    }
                ],
            }
        )

    async def metrics(self, request: web.Request) -> web.Response:
        h = self.engine.health()
        lines = [
            "# TYPE gpustack_engine_slots_used gauge",
            f"gpustack_engine_slots_used {h['slots_used']}",
            "# TYPE gpustack_engine_slots_total gauge",
            f"gpustack_engine_slots_total {h['slots_total']}",
            "# TYPE gpustack_engine_waiting gauge",
            f"gpustack_engine_waiting {h['waiting']}",
            "# TYPE gpustack_engine_tokens_generated_total counter",
            f"gpustack_engine_tokens_generated_total {h['tokens_generated']}",
        ]
        # host KV cache: TYPE text derives from the declared vocabulary
        # (observability/metrics.py METRIC_FAMILIES) so the metrics-
        # drift analyzer sees exactly one declaration site per family
        from gpustack_tpu.observability.metrics import METRIC_FAMILIES

        for family, value in (
            ("gpustack_kv_cache_hits", h["kv_cache_hits"]),
            ("gpustack_kv_cache_misses", h["kv_cache_misses"]),
            (
                "gpustack_kv_cache_prefix_tokens_reused",
                h["kv_cache_prefix_tokens_reused"],
            ),
            ("gpustack_kv_cache_bytes", h["kv_cache_host_bytes"]),
            ("gpustack_engine_kv_cache_bytes", h["kv_cache_bytes"]),
            (
                "gpustack_engine_kv_cache_bytes_per_token",
                h["kv_cache_bytes_per_token"],
            ),
        ):
            lines.append(f"# TYPE {family} {METRIC_FAMILIES[family]}")
            lines.append(f"{family} {value}")
        family = "gpustack_engine_cache_bytes"
        lines.append(f"# TYPE {family} {METRIC_FAMILIES[family]}")
        for kind in ("kv", "state", "window"):
            lines.append(
                f'{family}{{kind="{kind}"}} {h["cache"][kind + "_bytes"]}'
            )
        if h.get("moe_pairs") is not None:   # a share of the experts
            family = "gpustack_engine_moe_pairs_total"
            lines.append(f"# TYPE {family} {METRIC_FAMILIES[family]}")
            for held, key in (("yes", "held"), ("no", "absent")):
                lines.append(
                    f'{family}{{held="{held}"}} {h["moe_pairs"][key]}'
                )
        # disaggregated KV handoff (engine/kv_transfer.py): wire
        # bytes/blocks per direction + pull failures; the latency
        # histogram rides the request-histogram loop below
        ho = self.engine.kv_handoff
        for family, series in (
            (
                "gpustack_kv_handoff_bytes_total",
                (("in", ho.bytes_in), ("out", ho.bytes_out)),
            ),
            (
                "gpustack_kv_handoff_blocks_total",
                (("in", ho.blocks_in), ("out", ho.blocks_out)),
            ),
        ):
            lines.append(f"# TYPE {family} {METRIC_FAMILIES[family]}")
            for direction, value in series:
                lines.append(
                    f'{family}{{direction="{direction}"}} {value}'
                )
        lines.append(
            "# TYPE gpustack_kv_handoff_failures_total "
            f"{METRIC_FAMILIES['gpustack_kv_handoff_failures_total']}"
        )
        lines.append(
            f"gpustack_kv_handoff_failures_total {ho.failures}"
        )
        # fleet KV fabric: disk spill tier + background prefetch
        cache = self.engine.host_kv_cache
        spill = cache.spill if cache is not None else None
        if spill is not None:
            s = spill.snapshot()
            for family, series in (
                (
                    "gpustack_kv_spill_bytes_total",
                    (
                        ("out", s["bytes_spilled"]),
                        ("in", s["bytes_loaded"]),
                    ),
                ),
                (
                    "gpustack_kv_spill_blocks_total",
                    (
                        ("out", s["blocks_spilled"]),
                        ("in", s["blocks_loaded"]),
                    ),
                ),
            ):
                lines.append(
                    f"# TYPE {family} {METRIC_FAMILIES[family]}"
                )
                for direction, value in series:
                    lines.append(
                        f'{family}{{direction="{direction}"}} {value}'
                    )
            for family, value in (
                ("gpustack_kv_spill_resident_bytes", s["bytes"]),
                ("gpustack_kv_spill_corrupt_total", s["corrupt"]),
                ("gpustack_kv_spill_evictions_total", s["evictions"]),
                (
                    "gpustack_kv_spill_faultbacks_total",
                    cache.faultbacks,
                ),
            ):
                lines.append(
                    f"# TYPE {family} {METRIC_FAMILIES[family]}"
                )
                lines.append(f"{family} {value}")
        if cache is not None:
            family = "gpustack_kv_prefetch_total"
            lines.append(f"# TYPE {family} {METRIC_FAMILIES[family]}")
            for result, value in (
                ("ok", self.prefetch_ok),
                ("failed", self.prefetch_failed),
            ):
                lines.append(f'{family}{{result="{result}"}} {value}')
        # flight recorder: per-step scheduler telemetry (step-time
        # histogram by mode, real-vs-padded dispatch, occupancy, queue
        # wait, speculation economics — observability/flight.py)
        flight = getattr(self.engine, "flight", None)
        if flight is not None:
            lines.extend(flight.metrics_lines())
        # request-latency histograms (vLLM's ttft/tpot observability
        # parity — the reference normalizes these into its dashboards,
        # metrics_config.yaml)
        for name, hist in (
            ("gpustack_engine_ttft_seconds", self.engine.ttft_hist),
            ("gpustack_engine_tpot_seconds", self.engine.tpot_hist),
            ("gpustack_engine_e2e_seconds", self.engine.e2e_hist),
            ("gpustack_kv_handoff_seconds", ho.seconds),
        ):
            cum, total, count = hist.snapshot()
            lines.append(f"# TYPE {name} histogram")
            for ub, c in cum:
                le = "+Inf" if ub == float("inf") else repr(ub)
                lines.append(f'{name}_bucket{{le="{le}"}} {c}')
            lines.append(f"{name}_sum {total:.6f}")
            lines.append(f"{name}_count {count}")
        if self.startup is not None:
            lines.extend(self.startup.metrics_lines())
        return web.Response(text="\n".join(lines) + "\n")

    async def debug_startup(self, request: web.Request) -> web.Response:
        """The span of this process's start (observability/startup.py):
        its phases from the process's creation to listening, the
        ``ready`` and ``first_token`` events, and a record for every
        program lowered or loaded in the process's life, by name. The
        ``trace_id`` is the worker's ``instance_start`` span's."""
        if self.startup is None:
            return _error(404, "this server was not started by main()")
        return web.json_response(self.startup.describe())

    async def debug_flight(self, request: web.Request) -> web.Response:
        """Raw flight-recorder view: the most recent per-step records
        plus windowed aggregates (``window_s=`` bounds the aggregate to
        recent steps; ``limit=`` caps the raw records returned). The
        fleet rollup (server ``GET /v2/debug/fleet``) consumes the same
        numbers through the normalized /metrics path — this endpoint is
        the ground truth it must agree with."""
        flight = getattr(self.engine, "flight", None)
        if flight is None:
            return _error(404, "engine has no flight recorder")
        try:
            limit = min(2048, int(request.query.get("limit", 100)))
            window_s = request.query.get("window_s")
            window = float(window_s) if window_s is not None else None
        except ValueError:
            return _error(400, "limit/window_s must be numbers")
        return web.json_response({
            "model": self.model_name,
            "records": flight.snapshot(limit=limit),
            "aggregate": flight.aggregate(window_s=window),
            "overhead_ratio": round(flight.overhead_ratio(), 6),
        })

    async def debug_profile(self, request: web.Request) -> web.Response:
        """On-demand profiler capture: wrap the next N busy scheduler
        steps in ``jax.profiler.trace`` (when this jax build has the
        profiler API — degrades to flight-records-only otherwise),
        writing the artifact under ``out_dir``. Blocks until the steps
        elapse or ``timeout_s`` passes; an idle engine returns whatever
        it captured. Relayed from the server admin surface
        (``POST /v2/model-instances/{id}/profile``) via the worker."""
        try:
            steps = int(request.query.get("steps", 20))
            timeout_s = min(
                120.0, float(request.query.get("timeout_s", 30.0))
            )
        except ValueError:
            return _error(400, "steps/timeout_s must be numbers")
        if steps < 1:
            return _error(400, "steps must be >= 1")
        out_dir = request.query.get("out_dir", "")
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(
                None,
                lambda: self.engine.capture_profile(
                    steps, out_dir=out_dir, timeout_s=timeout_s
                ),
            )
        except ValueError as e:
            return _error(409, str(e))
        return web.json_response(result)

    # ---- disaggregated KV handoff (docs/KV_CACHE.md) -------------------

    @staticmethod
    def _handoff_timeout() -> float:
        return float(
            os.environ.get("GPUSTACK_TPU_KV_HANDOFF_TIMEOUT") or 10.0
        )

    async def kv_export(self, request: web.Request) -> web.StreamResponse:
        """Stream the host cache's matched radix block run for a prompt
        as content-addressed wire frames (engine/kv_transfer.py).

        Body: ``{"prompt_ids": [...], "have": [hex...], "prefill":
        bool}``. ``have`` keys the requester already holds travel as
        token-only dedup frames. ``prefill=true`` on a miss runs a
        one-token generation first so a prefill-role replica can be
        handed a prompt it has never seen — THE disaggregated-serving
        hop: prefill compute happens here, the decode replica imports
        the blocks and prefills only the sub-block tail."""
        eng = self.engine
        cache = eng.host_kv_cache
        if cache is None:
            return _error(404, "engine has no host KV cache")
        try:
            body = await request.json()
            prompt_ids = [int(t) for t in body.get("prompt_ids") or []]
        except (json.JSONDecodeError, TypeError, ValueError):
            return _error(400, "invalid JSON body")
        # tail_key mode (fleet prefetch): the puller has no tokens —
        # only the directory-advertised chain key of the deepest block
        # — so the export walks parent pointers instead of the prompt
        tail_key = str(body.get("tail_key") or "")
        if not prompt_ids and not tail_key:
            return _error(400, "missing 'prompt_ids' or 'tail_key'")
        have = [str(k) for k in body.get("have") or []]
        want_blocks = (
            (len(prompt_ids) - 1) // cache.block_tokens
            if prompt_ids else 0
        )
        loop = asyncio.get_running_loop()
        if prompt_ids and body.get("prefill") and want_blocks > 0:
            held = await loop.run_in_executor(
                None, cache.peek_prefix_len, prompt_ids
            )
            if held < want_blocks * cache.block_tokens:
                err = await loop.run_in_executor(
                    None, self._prefill_for_export, prompt_ids,
                    want_blocks * cache.block_tokens,
                )
                if err:
                    return _error(503, err)
        from gpustack_tpu.engine.kv_transfer import MAGIC, encode_block

        def assemble():
            # ONE trie walk: encode straight off export_blocks and
            # count payload frames as they are produced (a second walk
            # just to count could disagree under concurrent eviction)
            have_set = frozenset(have)
            chunks = [MAGIC]
            payload_blocks = 0
            blocks = (
                cache.export_blocks(prompt_ids) if prompt_ids
                else cache.export_chain(tail_key)
            )
            for blk in blocks:
                frame, carried = encode_block(blk, have_set)
                chunks.append(frame)
                payload_blocks += int(carried)
            return chunks, payload_blocks

        chunks, payload_blocks = await loop.run_in_executor(
            None, assemble
        )
        resp = web.StreamResponse(
            headers={"Content-Type": "application/x-gpustack-kv"}
        )
        await resp.prepare(request)
        for chunk in chunks:
            await resp.write(chunk)
            eng.kv_handoff.bytes_out += len(chunk)
        eng.kv_handoff.blocks_out += payload_blocks
        await resp.write_eof()
        return resp

    def _prefill_for_export(
        self, prompt_ids, want_tokens: int
    ) -> str:
        """Run a one-token generation so the prompt's KV lands in the
        host cache (the prefill-time async store), then wait — bounded
        — for the store to become matchable. Returns an error string,
        or "" on success. Executor-thread only."""
        timeout = self._handoff_timeout()
        try:
            req = GenRequest(
                prompt_ids=list(prompt_ids), max_tokens=1,
                temperature=0.0,
            )
            self.engine.generate(req, timeout=timeout)
        except (TimeoutError, ValueError) as e:
            return f"prefill for export failed: {e}"
        cache = self.engine.host_kv_cache
        if cache is None:
            return "host KV cache disabled mid-prefill"
        deadline = time.time() + timeout
        while (
            cache.peek_prefix_len(prompt_ids) < want_tokens
            and time.time() < deadline
        ):
            time.sleep(0.01)
        return ""

    async def kv_import(self, request: web.Request) -> web.Response:
        """Land wire frames (a prefill replica's push, or a relay) in
        this engine's host cache through the kv stager — decode slots
        never stall on the insert."""
        eng = self.engine
        cache = eng.host_kv_cache
        if cache is None:
            return _error(404, "engine has no host KV cache")
        from gpustack_tpu.engine.kv_transfer import (
            decode_stream,
            prepare_import,
        )

        raw = await request.read()
        loop = asyncio.get_running_loop()

        def convert():
            frames = decode_stream(raw)
            return prepare_import(cache, frames)

        try:
            tokens, prepared, bytes_in = await loop.run_in_executor(
                None, convert
            )
        except ValueError as e:
            eng.kv_handoff.failures += 1
            return _error(400, str(e))
        try:
            # the stager SUBMIT itself can block (two-slot backpressure
            # while an upload lands) — keep it off the event loop, or
            # every SSE stream and health probe on this engine stalls
            fut = await loop.run_in_executor(
                None, eng.kv_import_prepared, tokens, prepared
            )
            attached = await asyncio.wait_for(
                asyncio.wrap_future(fut), self._handoff_timeout()
            )
        except asyncio.TimeoutError:
            eng.kv_handoff.failures += 1
            return _error(
                503,
                "kv import did not land within "
                f"{self._handoff_timeout()}s (stager busy); retry",
            )
        eng.kv_handoff.bytes_in += bytes_in
        return web.json_response({
            "blocks_attached": attached,
            "tokens": len(tokens),
            "bytes": bytes_in,
        })

    async def kv_summary(self, request: web.Request) -> web.Response:
        """The cluster KV directory's scrape: fold the server-reported
        fleet sharing counts into local eviction economics, then return
        this replica's bounded prefix-key summary (conversation-hash →
        resident block depth + deepest RAM chain key) re-checked
        against BOTH cache tiers right now.

        Body (all optional): ``{"sharing": {hash: replica_count},
        "max_keys": n}``. One round-trip carries both directions."""
        eng = self.engine
        cache = eng.host_kv_cache
        conv = getattr(eng, "kv_conv", None)
        if cache is None or conv is None:
            return _error(404, "engine has no host KV cache")
        try:
            body = await request.json() if request.can_read_body else {}
        except json.JSONDecodeError:
            return _error(400, "invalid JSON body")
        sharing = body.get("sharing") or {}
        if not isinstance(sharing, dict):
            return _error(400, "'sharing' must be an object")
        from gpustack_tpu.engine.kv_fabric import DEFAULT_SUMMARY_KEYS

        try:
            max_keys = int(body.get("max_keys") or DEFAULT_SUMMARY_KEYS)
        except (TypeError, ValueError):
            return _error(400, "'max_keys' must be an integer")
        loop = asyncio.get_running_loop()

        def scrape():
            boosted = conv.apply_sharing(cache, sharing)
            summary = conv.summary(cache, max_keys=max(1, max_keys))
            summary["sharing_boosted"] = boosted
            return summary

        return web.json_response(
            await loop.run_in_executor(None, scrape)
        )

    async def kv_pull(self, request: web.Request) -> web.Response:
        """Background prefetch trigger (the fleet fabric's low-priority
        warm-ahead): pull a conversation's block chain from a peer
        replica by its directory-advertised tail chain key. Returns 202
        immediately — the pull runs as a background task so the caller
        (the server's prefetcher) never blocks on transfer time, and a
        dead/slow source degrades to "stayed cold", counted."""
        eng = self.engine
        cache = eng.host_kv_cache
        if cache is None:
            return _error(404, "engine has no host KV cache")
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error(400, "invalid JSON body")
        source = str(body.get("source") or "")
        tail_key = str(body.get("tail_key") or "")
        if not source or not tail_key:
            return _error(400, "missing 'source' or 'tail_key'")
        auth = str(body.get("auth") or "")
        task = asyncio.get_running_loop().create_task(
            self._kv_pull_chain(source, auth, tail_key)
        )
        self._kv_pulls.add(task)
        task.add_done_callback(self._kv_pulls.discard)
        return web.json_response({"accepted": True}, status=202)

    async def _kv_pull_chain(
        self, source: str, auth: str, tail_key: str
    ) -> None:
        """The prefetch pull itself: stream the peer's chain export,
        land it through the stager. Failures are counted + logged,
        never raised — prefetch is advisory."""
        import aiohttp

        eng = self.engine
        cache = eng.host_kv_cache
        from gpustack_tpu.engine.kv_transfer import (
            FrameDecoder,
            prepare_import,
        )

        timeout = self._handoff_timeout()
        t0 = time.perf_counter()
        loop = asyncio.get_running_loop()
        try:
            if self._kv_session is None or self._kv_session.closed:
                self._kv_session = aiohttp.ClientSession()
            headers = {"Authorization": auth} if auth else {}
            decoder = FrameDecoder()
            frames: list = []
            async with self._kv_session.post(
                source,
                json={"tail_key": tail_key},
                headers=headers,
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                if resp.status != 200:
                    raise RuntimeError(f"peer answered HTTP {resp.status}")
                async for chunk in resp.content.iter_any():
                    frames.extend(decoder.feed(chunk))
            if not frames:
                raise RuntimeError("peer exported no blocks")
            tokens, prepared, bytes_in = await loop.run_in_executor(
                None, prepare_import, cache, frames
            )
            fut = await loop.run_in_executor(
                None, eng.kv_import_prepared, tokens, prepared
            )
            blocks = await asyncio.wait_for(
                asyncio.wrap_future(fut),
                max(0.5, timeout - (time.perf_counter() - t0)),
            )
            eng.kv_handoff.bytes_in += bytes_in
            self.prefetch_ok += 1
            logger.info(
                "kv prefetch from %s landed %d block(s) (%d bytes)",
                source, blocks, bytes_in,
            )
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — advisory: stay cold
            self.prefetch_failed += 1
            logger.warning(
                "kv prefetch from %s failed (replica stays cold): %s",
                source, str(e) or type(e).__name__,
            )

    async def _kv_prefetch(
        self, request: web.Request, source: str, prompt_ids
    ) -> None:
        """Pull the prompt's radix prefix blocks from a peer replica
        before submitting the generation — the decode half of the
        disaggregated handoff. Never fails the request: a dead peer, a
        truncated stream or a slow transfer degrades to a cold (or
        partial-prefix) prefill, with the failure counted and traced.
        Complete frames that arrived before a mid-stream death are
        still imported — a radix cache can always use the intact
        prefix."""
        import aiohttp

        eng = self.engine
        cache = eng.host_kv_cache
        stats = eng.kv_handoff
        bt = cache.block_tokens
        want_tokens = (len(prompt_ids) - 1) // bt * bt
        if want_tokens <= 0:
            return
        loop = asyncio.get_running_loop()
        have = await loop.run_in_executor(
            None, cache.prefix_keys, prompt_ids
        )
        if len(have) * bt >= want_tokens:
            return  # the full run is already local
        from gpustack_tpu.engine.kv_transfer import (
            FrameDecoder,
            prepare_import,
        )

        trace = request.get("trace")
        timeout = self._handoff_timeout()
        t0 = time.perf_counter()
        stats.pulls += 1
        frames: list = []
        failed = ""
        try:
            if self._kv_session is None or self._kv_session.closed:
                self._kv_session = aiohttp.ClientSession()
            headers = {}
            auth = request.headers.get("X-GPUStack-KV-Source-Auth", "")
            if auth:
                headers["Authorization"] = auth
            decoder = FrameDecoder()
            async with self._kv_session.post(
                source,
                json={
                    "prompt_ids": [int(t) for t in prompt_ids],
                    "have": have,
                    "prefill": True,
                },
                headers=headers,
                timeout=aiohttp.ClientTimeout(total=timeout),
            ) as resp:
                if resp.status != 200:
                    raise RuntimeError(f"peer answered HTTP {resp.status}")
                async for chunk in resp.content.iter_any():
                    frames.extend(decoder.feed(chunk))
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — any peer fault → cold
            failed = str(e) or type(e).__name__
        imported = 0
        bytes_in = 0
        if frames:
            try:
                tokens, prepared, bytes_in = await loop.run_in_executor(
                    None, prepare_import, cache, frames
                )
                # the stager submit can block on its two-slot bound:
                # off the event loop, like the convert above
                fut = await loop.run_in_executor(
                    None, eng.kv_import_prepared, tokens, prepared
                )
                imported = await asyncio.wait_for(
                    asyncio.wrap_future(fut),
                    max(0.5, timeout - (time.perf_counter() - t0)),
                )
                stats.bytes_in += bytes_in
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001
                failed = failed or (str(e) or type(e).__name__)
        dur = time.perf_counter() - t0
        stats.seconds.observe(dur)
        if failed:
            stats.failures += 1
            logger.warning(
                "kv handoff from %s failed after %.3fs (%d block(s) "
                "landed; continuing cold): %s",
                source, dur, imported, failed,
            )
        if trace is not None:
            # the engine hop's kv_handoff phase: transfer + import wait
            trace.add_phase("kv_handoff", dur)
            attrs = dict(source=source, blocks=imported, bytes=bytes_in)
            if failed:
                attrs["failed"] = failed
            trace.event("kv_handoff", **attrs)

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error(400, "invalid JSON body")
        prompt = body.get("prompt")
        if prompt is None:
            return _error(400, "missing 'prompt'")
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        prompt_ids = self.engine.tokenizer.encode(str(prompt))
        return await self._run(request, body, prompt_ids, chat=False)

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error(400, "invalid JSON body")
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            return _error(400, "missing 'messages'")
        if getattr(self.engine, "kv_conv", None) is not None:
            # same rolling message-prefix hashes the proxy's affinity
            # map and the cluster KV directory key on — recorded at
            # finish (with the generated ids) via _record_conv
            from gpustack_tpu.server.resilience import conversation_chain

            request["conv_chain"] = conversation_chain(
                self.model_name, messages
            )

        tools = body.get("tools") or []
        tool_choice = body.get("tool_choice", "auto")
        tools_active = bool(tools) and tool_choice != "none"
        msgs = list(messages)

        has_images = any(
            isinstance(m.get("content"), list)
            and any(
                isinstance(p, dict) and p.get("type") == "image_url"
                for p in m["content"]
            )
            for m in msgs
        )
        vision = getattr(self.engine, "vision", None)
        if has_images and vision is None:
            return _error(
                400,
                f"model {self.model_name!r} does not accept image input",
            )

        # tool_choice forcing rides an extra system instruction so it
        # works uniformly across template-native and fallback rendering
        if tools_active:
            forced = forced_function(tool_choice)
            if forced:
                msgs.append({
                    "role": "system",
                    "content": f'You MUST call the function "{forced}".',
                })
            elif tool_choice == "required":
                msgs.append({
                    "role": "system",
                    "content": "You MUST call one of the available functions.",
                })

        rf = body.get("response_format") or {}
        json_mode = isinstance(rf, dict) and rf.get("type") in (
            "json_object", "json_schema"
        )
        schema = None
        if json_mode:
            instruction = JSON_MODE_INSTRUCTION
            schema = (rf.get("json_schema") or {}).get("schema")
            if schema:
                # a broken schema is a client error: reject now instead
                # of burning two generations that can only fail
                import jsonschema

                if not isinstance(schema, (dict, bool)):
                    return _error(
                        400, "json_schema.schema must be an object"
                    )
                try:
                    jsonschema.validators.validator_for(
                        schema
                    ).check_schema(schema)
                except jsonschema.SchemaError as e:
                    return _error(400, f"invalid json_schema: {e.message}")
                instruction += (
                    " The object must conform to this JSON schema: "
                    + json.dumps(schema)
                )
            msgs.append({"role": "system", "content": instruction})

        def reencode_with_feedback(attempt_text: str, error: str):
            """Retry prompt for schema-validation failure: the failed
            attempt + the validator's error, re-templated."""
            retry_msgs = msgs + [
                {"role": "assistant", "content": attempt_text},
                {
                    "role": "system",
                    "content": (
                        "Your JSON failed schema validation: "
                        f"{error[:400]}. Respond again with ONLY a "
                        "corrected JSON object."
                    ),
                },
            ]
            return self.engine.tokenizer.apply_chat_template(retry_msgs)

        embeds_override = None
        if has_images:
            from gpustack_tpu.engine.tokenizer import _inject_tools_fallback
            from gpustack_tpu.models.vlm import build_mm_prompt

            # the multimodal template can't take the tools= kwarg, so the
            # function schemas ride the same system-block fallback the
            # text path uses for non-template tokenizers
            if tools_active:
                msgs = _inject_tools_fallback(msgs, tools)
            loop = asyncio.get_running_loop()
            try:
                # PIL decode + (first-call) jit compile + ViT forward are
                # seconds of work — off the event loop, like TTS synthesis
                prompt_ids, embeds, mask = await loop.run_in_executor(
                    None,
                    lambda: build_mm_prompt(
                        self.engine.tokenizer, msgs, vision
                    ),
                )
            except ValueError as e:
                return _error(400, str(e))
            embeds_override = (embeds, mask)
        else:
            try:
                prompt_ids = self.engine.tokenizer.apply_chat_template(
                    msgs, tools=tools if tools_active else None
                )
            except Exception as e:  # tokenizer/template errors: client's
                return _error(400, f"chat template failed: {e}")
        return await self._run(
            request, body, prompt_ids, chat=True,
            tools_active=tools_active, json_mode=json_mode,
            embeds_override=embeds_override,
            schema=schema, reencode=reencode_with_feedback,
        )

    async def rerank(self, request: web.Request) -> web.Response:
        """Jina/Cohere-style rerank: query + documents → ranked scores.

        v1 scoring is embedding cosine similarity (bi-encoder) over the
        served model's pooled representations — the reference exposes
        rerank through its engine registry (gateway/utils.py
        openai_model_prefixes); a cross-encoder head is the planned
        upgrade for dedicated reranker checkpoints.
        """
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error(400, "invalid JSON body")
        query = body.get("query")
        documents = body.get("documents")
        if not isinstance(query, str) or not query:
            return _error(400, "missing 'query'")
        if not isinstance(documents, list) or not documents or not all(
            isinstance(d, str) for d in documents
        ):
            return _error(400, "'documents' must be non-empty strings")
        try:
            top_n = int(body.get("top_n") or len(documents))
        except (TypeError, ValueError):
            return _error(400, "'top_n' must be an integer")
        if top_n <= 0:
            return _error(400, "'top_n' must be positive")
        loop = asyncio.get_running_loop()

        def encode_and_embed():
            # tokenization stays off the event loop too: hundreds of
            # long documents would stall every other request
            batch = [self.engine.tokenizer.encode(query)] + [
                self.engine.tokenizer.encode(d) for d in documents
            ]
            if any(not ids for ids in batch):
                raise ValueError(
                    "query/documents must tokenize non-empty"
                )
            return batch, self.engine.embed(batch)

        try:
            batch, vecs = await loop.run_in_executor(
                None, encode_and_embed
            )
        except ValueError as e:
            return _error(400, str(e))
        import numpy as _np

        q = _np.asarray(vecs[0])
        docs = _np.asarray(vecs[1:])
        # embed() l2-normalizes, so dot == cosine
        scores = docs @ q
        order = _np.argsort(-scores)[:top_n]
        return web.json_response(
            {
                "model": self.model_name,
                "object": "rerank",
                "results": [
                    {
                        "index": int(i),
                        "relevance_score": float(scores[i]),
                        "document": {"text": documents[int(i)]},
                    }
                    for i in order
                ],
                "usage": {
                    "total_tokens": sum(len(ids) for ids in batch)
                },
            }
        )

    async def embeddings(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return _error(400, "invalid JSON body")
        inputs = body.get("input")
        if inputs is None:
            return _error(400, "missing 'input'")
        if isinstance(inputs, str):
            inputs = [inputs]
        # OpenAI also allows a bare token array / list of token arrays
        if inputs and all(isinstance(x, int) for x in inputs):
            inputs = [inputs]
        if not isinstance(inputs, list) or not inputs:
            return _error(400, "'input' must be a string or list")
        batch_ids = []
        total_tokens = 0
        for item in inputs:
            if isinstance(item, str):
                ids = self.engine.tokenizer.encode(item)
            elif isinstance(item, list) and all(
                isinstance(t, int) for t in item
            ):
                ids = list(item)           # pre-tokenized input
            else:
                return _error(
                    400,
                    "'input' items must be strings or token-id arrays",
                )
            if not ids:
                return _error(400, "'input' items must be non-empty")
            batch_ids.append(ids)
            total_tokens += len(ids)
        dimensions = body.get("dimensions")
        if dimensions is not None:
            if isinstance(dimensions, bool) or not isinstance(
                dimensions, int
            ):
                return _error(400, "'dimensions' must be an integer")
            if dimensions < 1:
                return _error(400, "'dimensions' must be positive")
        encoding_format = body.get("encoding_format", "float")
        if encoding_format not in ("float", "base64"):
            return _error(
                400, "'encoding_format' must be float or base64"
            )
        loop = asyncio.get_running_loop()
        try:
            vecs = await loop.run_in_executor(
                None, self.engine.embed, batch_ids
            )
        except ValueError as e:
            return _error(400, str(e))
        if dimensions is not None:
            if dimensions > len(vecs[0]):
                return _error(
                    400,
                    f"'dimensions' {dimensions} exceeds the model's "
                    f"embedding size {len(vecs[0])}",
                )
            # matryoshka-style truncation + renormalize (OpenAI
            # 'dimensions' semantics; vLLM does the same)
            import math

            def shrink(vec):
                cut = vec[:dimensions]
                norm = math.sqrt(sum(x * x for x in cut)) or 1.0
                return [x / norm for x in cut]

            vecs = [shrink(v) for v in vecs]

        def render(vec):
            if encoding_format == "base64":
                import base64
                import struct

                return base64.b64encode(
                    struct.pack(f"<{len(vec)}f", *vec)
                ).decode()
            return vec

        data = [
            {"object": "embedding", "index": i, "embedding": render(vec)}
            for i, vec in enumerate(vecs)
        ]
        return web.json_response(
            {
                "object": "list",
                "data": data,
                "model": self.model_name,
                "usage": {
                    "prompt_tokens": total_tokens,
                    "total_tokens": total_tokens,
                },
            }
        )

    # ---- core -----------------------------------------------------------

    def _gen_request(
        self, body: Dict[str, Any], prompt_ids, *,
        chat: bool = True, json_mode: bool = False,
    ) -> GenRequest:
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        stop_texts = tuple(str(s) for s in stop if s)
        max_tokens = int(
            body.get("max_tokens") or body.get("max_completion_tokens") or 128
        )
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        seed = body.get("seed")
        if seed is not None:
            seed = int(seed)
        logit_bias = body.get("logit_bias")
        if logit_bias is not None:
            if not isinstance(logit_bias, dict):
                raise ValueError("logit_bias must be {token_id: bias}")
            logit_bias = {
                int(k): float(v) for k, v in logit_bias.items()
            }
        # chat: logprobs is a bool + top_logprobs count; legacy
        # completions: logprobs is the alternatives count itself
        if chat:
            want_logprobs = bool(body.get("logprobs"))
            top_lp = int(body.get("top_logprobs") or 0)
        else:
            raw = body.get("logprobs")
            want_logprobs = raw is not None and raw is not False
            top_lp = int(raw or 0) if not isinstance(raw, bool) else 0
        if top_lp < 0 or top_lp > MAX_TOP_LOGPROBS:
            raise ValueError(
                f"top_logprobs must be 0..{MAX_TOP_LOGPROBS}, got {top_lp}"
            )
        if body.get("temperature") is None:
            # A speculative deployment is greedy-only; the OpenAI default
            # of 1.0 would reject every request that simply leaves
            # temperature unset. Explicitly-set temperatures still reach
            # the engine and get its clear rejection.
            temperature = (
                0.0 if getattr(self.engine, "speculative", "") else 1.0
            )
        else:
            temperature = float(body.get("temperature"))
        return GenRequest(
            prompt_ids=prompt_ids,
            max_tokens=max_tokens,
            temperature=temperature,
            top_k=int(body.get("top_k") or 0),
            top_p=float(body.get("top_p") or 1.0),
            seed=seed,
            logit_bias=logit_bias,
            stop_texts=stop_texts,
            logprobs=want_logprobs,
            top_logprobs=top_lp,
            json_mode=json_mode,
            request_id=str(uuid.uuid4()),
        )

    def _make_gens(
        self, body: Dict[str, Any], prompt_ids, chat: bool, json_mode: bool,
        embeds_override=None,
    ) -> List[GenRequest]:
        n = int(body.get("n") or 1)
        if n < 1 or n > MAX_N:
            raise ValueError(f"n must be 1..{MAX_N}, got {n}")
        gens = []
        for i in range(n):
            gen = self._gen_request(
                body, list(prompt_ids), chat=chat, json_mode=json_mode
            )
            gen.embeds_override = embeds_override
            if gen.seed is not None and i > 0:
                # per-choice seeds must differ or every choice is the
                # same sequence; derive deterministically from the base
                gen.seed = gen.seed + i
            gens.append(gen)
        return gens

    def _finish_reason(self, gen: GenRequest, had_tool_calls: bool) -> str:
        return "tool_calls" if had_tool_calls else gen.finish_reason

    async def _validate_schema(
        self, body, gen: GenRequest, schema, reencode, loop,
        remaining_s: float, allow_retry: bool,
    ):
        """Validate a completed generation against the request's JSON
        schema; one guided retry on failure (the failed attempt + the
        validator's error re-enter the prompt). Returns (winning
        GenRequest, ``passed``/``failed: ...`` verdict, retry-or-None —
        the retry rides back for usage accounting).

        Divergence from the reference's vLLM backends (which enforce
        schemas with token-level grammars): this is validate-and-retry —
        the verdict is ALWAYS reported on the non-streaming choice so a
        failure can't pass silently (streams skip validation and say
        so)."""
        import jsonschema

        def verdict_of(text):
            try:
                jsonschema.validate(json.loads(text), schema)
                return "passed"
            except json.JSONDecodeError as e:
                return f"failed: not valid JSON ({e})"
            except jsonschema.ValidationError as e:
                return f"failed: {e.message}"

        verdict = verdict_of(gen.output_text)
        if verdict == "passed" or not allow_retry or remaining_s < 30:
            return gen, verdict, None
        try:
            # reencode runs a chat template; some family templates
            # reject assistant→system sequences — a failed retry
            # RENDERING must degrade to the original verdict, not a 500
            retry_ids = reencode(gen.output_text, verdict)
            retry = self._gen_request(
                body, retry_ids, chat=True, json_mode=True
            )
            retry.trace_id = gen.trace_id
            self.engine.submit(retry)
            await loop.run_in_executor(
                None, retry.done.wait, remaining_s
            )
        except Exception as e:
            logger.warning("schema retry not possible: %s", e)
            return gen, verdict, None
        if not retry.done.is_set():
            # the orphan finishes at max_tokens on its own; bounded
            logger.warning("schema retry timed out; keeping original")
            return gen, verdict, retry
        return retry, verdict_of(retry.output_text), retry

    async def _run(
        self, request: web.Request, body: Dict[str, Any], prompt_ids,
        chat: bool, tools_active: bool = False, json_mode: bool = False,
        embeds_override=None, schema=None, reencode=None,
    ) -> web.StreamResponse:
        try:
            gens = self._make_gens(
                body, prompt_ids, chat, json_mode, embeds_override
            )
        except (TypeError, ValueError) as e:
            return _error(400, f"bad sampling params: {e}")
        trace = request.get("trace")
        if trace is not None:
            # the engine's per-request flight entries carry the id the
            # server's and the worker's hops of this request carry
            for gen in gens:
                gen.trace_id = trace.ctx.trace_id
        # disaggregated handoff: the proxy names the peer replica that
        # already holds this conversation's radix prefix (or the
        # prefill-role replica that should compute it) — pull its
        # blocks before admission so _start_request prefix-hits them
        source = request.headers.get("X-GPUStack-KV-Source", "")
        if source and self.engine.host_kv_cache is not None and (
            embeds_override is None
        ):
            await self._kv_prefetch(request, source, prompt_ids)
        if body.get("stream"):
            return await self._stream(
                request, gens, chat, tools_active,
                schema_active=schema is not None,
            )
        loop = asyncio.get_running_loop()
        try:
            for gen in gens:
                self.engine.submit(gen)
        except ValueError as e:
            return _error(400, str(e))
        deadline = loop.time() + 600
        for gen in gens:
            remaining = max(0.1, deadline - loop.time())
            await loop.run_in_executor(None, gen.done.wait, remaining)
            if not gen.done.is_set():
                return _error(504, "generation timed out")
        self._trace_kv(request, gens)
        self._record_conv(request, gens)
        rid = f"{'chatcmpl' if chat else 'cmpl'}-{gens[0].request_id}"
        # usage is billed on what the CLIENT sent + everything actually
        # generated (incl. discarded schema-retry attempts) — a swapped
        # gen must not rewrite prompt_tokens or vanish output tokens
        usage = _usage(gens)
        verdicts: List[Optional[str]] = [None] * len(gens)
        if chat and schema is not None and reencode is not None:
            for i in range(len(gens)):
                # a tool-call turn is not a schema violation: the JSON
                # contract applies to the final content answer, not to
                # tool-call markup — skip validation entirely
                if tools_active and parse_tool_calls(
                    gens[i].output_text
                )[1]:
                    continue
                # multimodal retries would drop the images (the retry
                # prompt re-templates without the vision path), and a
                # length-truncated attempt would only truncate again:
                # validate only, never retry, in those cases
                allow_retry = (
                    len(gens) == 1
                    and embeds_override is None
                    and gens[i].finish_reason != "length"
                )
                gens[i], verdicts[i], retry = (
                    await self._validate_schema(
                        body, gens[i], schema, reencode, loop,
                        max(0.0, deadline - loop.time()), allow_retry,
                    )
                )
                if retry is not None:
                    usage["completion_tokens"] += len(retry.output_ids)
                    usage["total_tokens"] += len(retry.output_ids)
        choices = []
        for i, gen in enumerate(gens):
            text = gen.output_text
            if chat:
                tool_calls: List[Dict[str, Any]] = []
                content: Optional[str] = text
                if tools_active:
                    content, tool_calls = parse_tool_calls(text)
                    content = content or None
                message: Dict[str, Any] = {
                    "role": "assistant", "content": content,
                }
                if tool_calls:
                    message["tool_calls"] = tool_calls
                choice = {
                    "index": i,
                    "message": message,
                    "finish_reason": self._finish_reason(
                        gen, bool(tool_calls)
                    ),
                }
                if gen.logprobs:
                    choice["logprobs"] = _chat_logprobs(
                        gen, self.engine.tokenizer
                    )
                if verdicts[i] is not None:
                    # always reported: schema conformance is validated,
                    # not grammar-guaranteed (see _validate_schema)
                    choice["x_schema_validation"] = verdicts[i]
            else:
                choice = {
                    "index": i,
                    "text": text,
                    "finish_reason": gen.finish_reason,
                }
                if gen.logprobs:
                    choice["logprobs"] = _completion_logprobs(
                        gen, self.engine.tokenizer, gen.top_logprobs
                    )
            choices.append(choice)
        payload = {
            "id": rid,
            "object": "chat.completion" if chat else "text_completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": choices,
            "usage": usage,
        }
        if gens[0].seed is not None:
            payload["system_fingerprint"] = SYSTEM_FINGERPRINT
        return web.json_response(payload)

    async def _stream(
        self, request: web.Request, gens: List[GenRequest], chat: bool,
        tools_active: bool = False, schema_active: bool = False,
    ) -> web.StreamResponse:
        loop = asyncio.get_running_loop()
        rid = f"{'chatcmpl' if chat else 'cmpl'}-{gens[0].request_id}"
        obj = "chat.completion.chunk" if chat else "text_completion"
        for gen in gens:
            gen.stream = queue.Queue()
        # submit before committing to a 200/SSE response: rejections must
        # surface as real HTTP errors, not in-band stream events
        try:
            for gen in gens:
                self.engine.submit(gen)
        except ValueError as e:
            return _error(400, str(e))
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
        )
        await resp.prepare(request)

        def chunk_for(index: int, delta_or_text, finish=None) -> dict:
            body_ = (
                {"delta": delta_or_text} if chat
                else {"text": delta_or_text}
            )
            payload = {
                "id": rid, "object": obj, "created": int(time.time()),
                "model": self.model_name,
                "choices": [{"index": index, **body_,
                             "finish_reason": finish}],
            }
            if gens[0].seed is not None:
                payload["system_fingerprint"] = SYSTEM_FINGERPRINT
            return payload

        async def write(payload: dict) -> None:
            await resp.write(f"data: {json.dumps(payload)}\n\n".encode())

        if chat:
            for i in range(len(gens)):
                await write(chunk_for(
                    i, {"role": "assistant", "content": ""}
                ))

        # merge the per-choice token queues into one ordered SSE stream
        merged: asyncio.Queue = asyncio.Queue()
        _empty = object()

        def _bounded_get(gen: GenRequest):
            # a plain .get() would pin its executor thread until the
            # engine produces a token — uncancellable after a client
            # disconnect; bound it so threads notice the abort promptly
            try:
                return gen.stream.get(timeout=0.5)
            except queue.Empty:
                return _empty

        async def pump(i: int, gen: GenRequest) -> None:
            while True:
                item = await loop.run_in_executor(None, _bounded_get, gen)
                if item is _empty:
                    if gen.aborted.is_set():
                        return
                    continue
                await merged.put((i, item))
                if item is None:
                    return

        pumps = [
            asyncio.ensure_future(pump(i, g)) for i, g in enumerate(gens)
        ]
        holdbacks = [
            ToolCallHoldback() if (chat and tools_active) else None
            for _ in gens
        ]
        try:
            open_streams = len(gens)
            while open_streams:
                i, item = await merged.get()
                if item is None:
                    open_streams -= 1
                    continue
                _tok, piece = item
                hb = holdbacks[i]
                if hb is not None:
                    piece = hb.filter(piece)
                if piece:
                    await write(chunk_for(
                        i, {"content": piece} if chat else piece
                    ))
        finally:
            # On a client disconnect resp.write raises mid-loop; abort
            # the in-flight generations so the engine frees the slots at
            # its next delivery instead of decoding to max_tokens for
            # nobody. (Completed requests are already finished — setting
            # the flag then is a no-op.) The bounded queue.get above lets
            # the executor threads drain within ~0.5 s.
            for gen in gens:
                gen.abort()
            for p in pumps:
                p.cancel()

        for i, gen in enumerate(gens):
            had_calls = False
            hb = holdbacks[i]
            if hb is not None:
                if hb.in_call:
                    # parse only the HELD region: the text before the
                    # block already streamed, so re-parsing the full
                    # output would duplicate it. Unparseable blocks and
                    # content after the call come back as held_content —
                    # nothing the model produced is ever dropped.
                    held_content, calls = parse_tool_calls(hb.pending)
                    if calls:
                        had_calls = True
                        # whole-call deltas: one chunk per call carrying
                        # the full name+arguments (incremental argument
                        # streaming is a non-goal; clients accumulate by
                        # index)
                        await write(chunk_for(i, {
                            "tool_calls": [
                                {
                                    "index": ci,
                                    "id": c["id"],
                                    "type": "function",
                                    "function": c["function"],
                                }
                                for ci, c in enumerate(calls)
                            ]
                        }))
                    if held_content:
                        await write(chunk_for(i, {"content": held_content}))
                else:
                    tail = hb.flush()
                    if tail:
                        await write(chunk_for(i, {"content": tail}))
            final = chunk_for(
                i, {} if chat else "",
                self._finish_reason(gen, had_calls),
            )
            if schema_active:
                # streams can't be validated retro-actively; say so
                # instead of implying conformance
                final["choices"][0]["x_schema_validation"] = (
                    "skipped (stream)"
                )
            if gen.logprobs:
                # streaming logprobs ride the final chunk (per-piece
                # logprobs would need token-aligned streaming)
                final["choices"][0]["logprobs"] = (
                    _chat_logprobs(gen, self.engine.tokenizer) if chat
                    else _completion_logprobs(
                        gen, self.engine.tokenizer, gen.top_logprobs
                    )
                )
            if i == len(gens) - 1:
                final["usage"] = _usage(gens)
            await write(final)
        await resp.write(b"data: [DONE]\n\n")
        self._trace_kv(request, gens)
        self._record_conv(request, gens)
        return resp

    def _record_conv(
        self, request: web.Request, gens: List[GenRequest]
    ) -> None:
        """Feed the conversation index (engine/kv_fabric.ConvIndex) at
        chat finish: the message-prefix hash chain (stashed on the
        request by chat_completions) plus the token sequence whose KV
        blocks now live in the cache (prompt + generated — what turn
        N+1 will prefix-match)."""
        chain = request.get("conv_chain")
        conv = getattr(self.engine, "kv_conv", None)
        if not chain or conv is None:
            return
        g = gens[0]
        try:
            conv.record(chain, list(g.prompt_ids) + list(g.output_ids))
        except Exception:  # noqa: BLE001 — accounting must never 500
            logger.exception("conversation-index record failed")

    @staticmethod
    def _trace_kv(request: web.Request, gens: List[GenRequest]) -> None:
        """Attach host-KV-cache phases to this hop's trace: the
        ``kv_upload`` span (host→device re-materialization of matched
        prefix blocks, measured by the engine scheduler) plus a
        prefix-hit event carrying the reused-token count."""
        trace = request.get("trace")
        if trace is None:
            return
        upload_s = sum(g.kv_upload_s for g in gens)
        if upload_s > 0:
            trace.add_phase("kv_upload", upload_s)
        reused = sum(g.prefix_tokens_reused for g in gens)
        if reused:
            trace.event("kv_prefix_hit", tokens_reused=reused)


def _error(status: int, message: str) -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": "invalid_request_error"}},
        status=status,
    )


# ---------------------------------------------------------------------------
# Process entrypoint (what the worker's serve manager launches)
# ---------------------------------------------------------------------------


def build_engine_from_args(
    args, start: Optional[EngineStart] = None
) -> LLMEngine:
    """``start`` is the process's start span, in its ``backend`` phase:
    the phases ``config``, ``weights`` and ``engine`` are entered here,
    each where its work begins."""
    enter = start.enter if start is not None else (lambda phase: None)
    # Hermetic-test hook: the serve manager sets GPUSTACK_TPU_PLATFORM=cpu
    # (from --force-platform) so engine subprocesses run on the CPU
    # backend; without it the worker sets JAX_PLATFORMS=tpu and a chip
    # that cannot be opened fails the start.
    forced = os.environ.get("GPUSTACK_TPU_PLATFORM")
    import jax

    if forced:
        jax.config.update("jax_platforms", forced)
    from gpustack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # Multi-host replica: rendezvous through the JAX distributed
    # coordinator (the serve manager sets these from the placement — the
    # TPU replacement for the reference's Ray bootstrap,
    # worker/backends/vllm.py:258-328). After initialize(), jax.devices()
    # spans every host of the slice and the mesh plan tiles all of them.
    coordinator = os.environ.get("GPUSTACK_TPU_COORDINATOR")
    if coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(
                os.environ.get("GPUSTACK_TPU_NUM_PROCESSES", "1")
            ),
            process_id=int(os.environ.get("GPUSTACK_TPU_PROCESS_ID", "0")),
        )
    n_devices = len(jax.devices())      # the chip's runtime is up

    enter("config")
    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.config import get_config, load_hf_config
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.vlm import VLM_PRESETS, get_vlm_config
    from gpustack_tpu.parallel.mesh import MeshPlan, plan_mesh

    vlm_cfg = None
    if args.model_dir:
        from gpustack_tpu.engine.gguf import config_from_gguf
        from gpustack_tpu.engine.weights import checkpoint_source

        # shared precedence helper: config and weights always come from
        # the SAME checkpoint in a mixed directory
        kind, path = checkpoint_source(args.model_dir)
        if kind == "gguf":
            cfg = config_from_gguf(path, name=args.served_name or "")
        else:
            cfg = load_hf_config(args.model_dir)
    elif args.preset in VLM_PRESETS:
        # vision-language preset: the language half runs in the normal
        # engine; the tower+projector attach as engine.vision below
        vlm_cfg = get_vlm_config(args.preset)
        cfg = vlm_cfg.language
    else:
        cfg = get_config(args.preset)

    if args.mesh_plan:
        plan = MeshPlan.parse(args.mesh_plan)
    else:
        plan = plan_mesh(
            min(n_devices, args.num_devices or n_devices),
            cfg.num_kv_heads,
            cfg.num_experts,
        )

    enter("weights")
    from gpustack_tpu.engine.weights import load_or_init_params

    lora = getattr(args, "lora", None)
    # int8: quantized leaf by leaf as the tree is built, so the bf16 tree
    # (16 GB for an 8B model) never has to fit the chip. LoRA deltas
    # apply to bf16 base weights, so only a LoRA start merges first and
    # quantizes the whole tree afterwards.
    params = load_or_init_params(
        cfg, args.model_dir, seed=0,
        quantization="" if lora else args.quantization,
    )
    if lora:
        from gpustack_tpu.engine.weights import merge_lora_adapters

        params = merge_lora_adapters(cfg, params, lora)
        if args.quantization == "int8":
            params = quantize_params(params)

    draft_cfg = draft_params = None
    if args.speculative == "draft":
        source = getattr(args, "draft_source", "")
        if not source:
            raise ValueError("--speculative draft needs --draft-source")
        if os.path.isdir(source):
            draft_cfg = load_hf_config(source)
            draft_params = load_or_init_params(draft_cfg, source, seed=0)
        else:
            draft_cfg = get_config(source)
            draft_params = load_or_init_params(draft_cfg, None, seed=0)
    if start is not None:
        # the phase ends with the tree on the device, not with its last
        # program dispatched (once, here; never in a serving path)
        jax.block_until_ready((params, draft_params))

    enter("engine")
    # the decode batch is dp-sharded, so the slot count must be a
    # multiple of the mesh's dp degree; round capacity UP rather than
    # crash in device_put when the auto-planner picks dp > max_slots
    # (e.g. a small --max-slots on a many-chip host)
    max_slots = args.max_slots
    if max_slots % plan.dp:
        rounded = (max_slots // plan.dp + 1) * plan.dp
        logger.warning(
            "max_slots=%d not divisible by mesh dp=%d; rounding up to %d",
            max_slots, plan.dp, rounded,
        )
        max_slots = rounded

    # dispatch-ahead depth: argv (per-model knob) > env (Config
    # engine_pipeline_depth — engine subprocesses inherit the worker's
    # environment) > built-in default 2
    pipeline_depth = getattr(args, "pipeline_depth", -1)
    if pipeline_depth is None or pipeline_depth < 0:
        pipeline_depth = int(
            os.environ.get("GPUSTACK_TPU_ENGINE_PIPELINE_DEPTH") or 2
        )

    engine = LLMEngine(
        cfg,
        params,
        model_dir=args.model_dir,
        max_slots=max_slots,
        max_seq_len=args.max_seq_len,
        plan=plan,
        speculative=args.speculative,
        spec_tokens=args.spec_tokens,
        draft_cfg=draft_cfg,
        draft_params=draft_params,
        host_kv_cache_mb=getattr(args, "host_kv_cache_mb", 0),
        kv_block_tokens=getattr(args, "kv_block_tokens", 0),
        kv_cache_int8=getattr(args, "kv_cache_int8", False),
        prefill_chunk=getattr(args, "prefill_chunk", 0),
        pipeline_depth=pipeline_depth,
        kv_role=getattr(args, "kv_role", ""),
        kv_spill_mb=getattr(args, "kv_spill_mb", 0),
        kv_spill_dir=getattr(args, "kv_spill_dir", ""),
    )
    logger.info("engine devices: %s", json.dumps(engine.device_info()))
    if vlm_cfg is not None:
        from gpustack_tpu.models.vlm import VisionBundle, init_vision_params

        engine.vision = VisionBundle(
            vlm_cfg, init_vision_params(vlm_cfg, jax.random.key(1))
        )

    # Multi-host replica: multi-controller JAX is SPMD, so the leader
    # broadcasts every device op and followers replay it
    # (engine/multihost.py). Wired here, after the engine owns its
    # runner, so the engine itself stays topology-agnostic.
    n_procs = int(os.environ.get("GPUSTACK_TPU_NUM_PROCESSES", "1"))
    if n_procs > 1:
        if getattr(engine, "vision", None) is not None:
            # the vision encode runs leader-only and its spliced-prefill
            # op is not in the broadcast vocabulary — image requests on
            # a multi-host replica would kill the scheduling loop
            logger.warning(
                "vision tower disabled: VLM serving is single-host only"
            )
            engine.vision = None
        from gpustack_tpu.engine.multihost import (
            BroadcastingRunner,
            CommandLeader,
            FollowerLoop,
        )

        cmd_addr = os.environ["GPUSTACK_TPU_CMD_ADDRESS"]
        proc_id = int(os.environ.get("GPUSTACK_TPU_PROCESS_ID", "0"))
        if proc_id == 0:
            leader = CommandLeader(
                int(cmd_addr.rsplit(":", 1)[1]), n_procs - 1
            )
            engine.runner = BroadcastingRunner(engine.runner, leader)
        else:
            engine.follower_loop = FollowerLoop(
                engine.runner, cmd_addr, state=engine._state
            )
    return engine


def main(argv=None) -> None:
    # the process's start span ends its ``import`` phase here, and the
    # compile listeners are on before the first program (the weights')
    entered = time.time()
    programs = process_programs()
    p = argparse.ArgumentParser("gpustack-tpu engine API server")
    p.add_argument("--model-dir", default="")
    p.add_argument("--preset", default="llama3-8b")
    p.add_argument("--served-name", default="")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=9000)
    p.add_argument("--max-slots", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument(
        "--prefill-chunk", type=int, default=0,
        help="chunked prefill: process prompts in chunks of this many "
        "tokens, interleaving decode between chunks (0 = off)",
    )
    p.add_argument(
        "--pipeline-depth", type=int, default=-1,
        help="decode-fetch pipeline depth (dispatch-ahead overlap): "
        "0 = serial reference mode, -1 = inherit "
        "GPUSTACK_TPU_ENGINE_PIPELINE_DEPTH (default 2) — "
        "docs/ENGINE_PIPELINE.md",
    )
    p.add_argument("--quantization", choices=["", "int8"], default="")
    p.add_argument(
        "--speculative", choices=["", "ngram", "draft"], default=""
    )
    p.add_argument("--spec-tokens", type=int, default=4)
    p.add_argument(
        "--draft-source", default="",
        help="draft model for speculative=draft: preset name or local "
        "checkpoint dir",
    )
    p.add_argument("--mesh-plan", default="", help="e.g. dp1xsp1xep1xtp4")
    p.add_argument("--num-devices", type=int, default=0)
    p.add_argument(
        "--host-kv-cache-mb", type=int, default=0,
        help="host-RAM block KV cache budget (extended-KV-cache role): "
        "finished sequences are cached block-granular and shared "
        "across requests via radix prefix matching",
    )
    p.add_argument(
        "--kv-block-tokens", type=int, default=0,
        help="host KV cache block granularity in tokens (0 = default "
        "256); smaller blocks match shorter shared prefixes at more "
        "per-block overhead",
    )
    p.add_argument(
        "--kv-role", choices=["", "prefill", "decode"], default="",
        help="disaggregated-serving role tag (ModelSpec "
        "prefill_replicas/decode_replicas): prefill replicas compute "
        "prompt KV and export it at POST /kv/export; decode replicas "
        "pull handed-off blocks and own the token loop. Empty = "
        "colocated (both roles)",
    )
    p.add_argument(
        "--kv-spill-mb", type=int, default=0,
        help="disk spill tier budget under the host KV cache (MiB): "
        "blocks evicted from host RAM spill to one content-addressed "
        "file each and fault back on a later prefix hit; 0 disables",
    )
    p.add_argument(
        "--kv-spill-dir", default="",
        help="spill-tier directory (default: a per-process tmp dir; "
        "reusing a directory across restarts keeps the tier warm)",
    )
    p.add_argument(
        "--kv-cache-int8", action="store_true",
        help="quantize host-tier KV blocks to int8 (per-block scales, "
        "dequantized on upload) — ~2x cache capacity per byte",
    )
    p.add_argument(
        "--lora", action="append", default=[],
        help="PEFT LoRA adapter dir merged into the base weights "
        "(repeatable)",
    )
    args = p.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format=LOG_FORMAT)
    start = EngineStart(programs, model=args.served_name or args.preset)
    start.enter("backend", at=entered)
    engine = build_engine_from_args(args, start)
    start.enter("listen")
    engine.on_first_token = start.mark_first_token
    follower = getattr(engine, "follower_loop", None)
    if follower is not None:
        # follower host of a multi-host replica: no scheduling loop —
        # replay the leader's op stream; the HTTP surface stays up for
        # liveness but receives no inference traffic (the server proxies
        # to the leader's port only)
        follower.start()
    else:
        engine.start()
    server = OpenAIServer(
        engine, model_name=args.served_name or None, startup=start
    )

    def listening(*message) -> None:
        # run_app's one message, printed once its sites accept
        start.listening()
        print(*message, flush=True)

    async def on_startup(app):
        async def watchdog():
            # a dead scheduling loop is terminal for this process: exit
            # so the serve manager's process-exit watch drives the
            # crash/restart state machine (a 503 healthz alone is only
            # checked during startup)
            while True:
                await asyncio.sleep(2.0)
                if getattr(engine, "_fatal", ""):
                    logging.getLogger(__name__).error(
                        "terminating: %s", engine._fatal
                    )
                    os._exit(13)

        app["engine_watchdog"] = asyncio.create_task(watchdog())

    server.app.on_startup.append(on_startup)
    web.run_app(
        server.app, host=args.host, port=args.port, print=listening
    )


if __name__ == "__main__":
    main()
