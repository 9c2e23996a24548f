"""Minimal EXTERNAL OpenAI-compatible engine for backend orchestration.

This process stands in for the third-party engines the reference
orchestrates (vLLM / SGLang / llama-box — reference
worker/backends/base.py:150 and custom.py:24): it is launched from an
InferenceBackend catalog command template through the SAME ServeManager
path a real external binary would be, and speaks the contract that path
assumes:

- readiness endpoint at ``/health`` (deliberately NOT /healthz — proves
  the catalog's ``health_path`` is honored, like vLLM's /health),
- ``/v1/chat/completions`` + ``/v1/completions`` (stream and non-stream),
- ``/v1/models``,
- Prometheus ``/metrics`` using vLLM's metric names so the worker's
  runtime-metrics normalization (worker/metrics_map.py) has something
  real to map.

It generates deterministic text (echo-ish) with no model weights, so the
e2e can assert content flowed through the proxy without caring about
quality. Fast startup is a feature: crash-restart tests measure the
manager, not a model load.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time
import uuid

from aiohttp import web

START = time.time()
STATS = {"requests": 0, "prompt_tokens": 0, "generation_tokens": 0}


def _gen_text(prompt: str, max_tokens: int) -> str:
    words = (prompt.strip() or "ok").split()
    out = []
    i = 0
    while len(out) < max(1, min(max_tokens, 64)):
        out.append(words[i % len(words)])
        i += 1
    return "stub: " + " ".join(out)


def _usage(prompt: str, text: str) -> dict:
    pt, ct = len(prompt.split()), len(text.split())
    STATS["requests"] += 1
    STATS["prompt_tokens"] += pt
    STATS["generation_tokens"] += ct
    return {
        "prompt_tokens": pt,
        "completion_tokens": ct,
        "total_tokens": pt + ct,
    }


def _bucket(n: int) -> int:
    """Power-of-two padding stand-in for the real runner's prefill
    buckets — gives the stub's flight records a nonzero padding waste
    the fleet-rollup e2e can assert on."""
    b = 1
    while b < max(1, n):
        b *= 2
    return b


def build_app(
    served_name: str,
    fail_health_after: float = 0.0,
    token_delay: float = 0.0,
) -> web.Application:
    from gpustack_tpu.observability.flight import FlightRecorder
    from gpustack_tpu.observability.tracing import trace_middleware

    # same trace hop contract as the real engine (engine/api_server.py):
    # hermetic e2es assert the full four-hop trace against this stub
    app = web.Application(middlewares=[trace_middleware("engine")])
    # same flight-recorder contract as the real engine: one prefill +
    # one decode record per generation, served at /debug/flight and on
    # /metrics, so `GET /v2/debug/fleet` consistency is e2e-testable
    # without TPUs
    flight = FlightRecorder(slots_total=4)
    app["flight"] = flight

    def record_generation(pt: int, ct: int, dur_s: float) -> None:
        flight.record(
            dur_s=dur_s / 2, mode="prefill", slots_used=1,
            waiting=0, oldest_wait_s=0.0,
            tokens_real=pt, tokens_padded=_bucket(pt),
            tokens_out=1, prompt_tokens=pt,
        )
        flight.record(
            dur_s=dur_s / 2, mode="decode", slots_used=1,
            waiting=0, oldest_wait_s=0.0,
            tokens_real=max(0, ct - 1),
            tokens_padded=flight.slots_total * max(0, ct - 1),
            tokens_out=max(0, ct - 1),
        )

    async def health(_request):
        if fail_health_after and time.time() - START > fail_health_after:
            return web.json_response({"status": "failing"}, status=503)
        return web.json_response({"status": "ok"})

    async def models(_request):
        return web.json_response({
            "object": "list",
            "data": [{"id": served_name, "object": "model",
                      "owned_by": "stub"}],
        })

    async def chat(request: web.Request):
        body = await request.json()
        prompt = " ".join(
            str(m.get("content", "")) for m in body.get("messages", [])
        )
        t0 = time.perf_counter()
        text = _gen_text(prompt, int(body.get("max_tokens", 16)))
        usage = _usage(prompt, text)
        record_generation(
            usage["prompt_tokens"], usage["completion_tokens"],
            time.perf_counter() - t0,
        )
        rid = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        if body.get("stream"):
            resp = web.StreamResponse(
                headers={"Content-Type": "text/event-stream"}
            )
            await resp.prepare(request)
            for piece in text.split(" "):
                chunk = {
                    "id": rid, "object": "chat.completion.chunk",
                    "model": served_name,
                    "choices": [{"index": 0,
                                 "delta": {"content": piece + " "},
                                 "finish_reason": None}],
                }
                await resp.write(
                    f"data: {json.dumps(chunk)}\n\n".encode()
                )
                # paced streaming (drain tests need a generation that is
                # genuinely in flight while the instance drains)
                await asyncio.sleep(token_delay)
            done = {
                "id": rid, "object": "chat.completion.chunk",
                "model": served_name,
                "choices": [{"index": 0, "delta": {},
                             "finish_reason": "stop"}],
                "usage": usage,
            }
            await resp.write(f"data: {json.dumps(done)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
            return resp
        return web.json_response({
            "id": rid, "object": "chat.completion",
            "created": int(time.time()), "model": served_name,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": "stop",
            }],
            "usage": usage,
        })

    async def completions(request: web.Request):
        body = await request.json()
        prompt = str(body.get("prompt", ""))
        t0 = time.perf_counter()
        text = _gen_text(prompt, int(body.get("max_tokens", 16)))
        usage = _usage(prompt, text)
        record_generation(
            usage["prompt_tokens"], usage["completion_tokens"],
            time.perf_counter() - t0,
        )
        return web.json_response({
            "id": f"cmpl-{uuid.uuid4().hex[:12]}",
            "object": "text_completion",
            "created": int(time.time()), "model": served_name,
            "choices": [{"index": 0, "text": text,
                         "finish_reason": "stop"}],
            "usage": usage,
        })

    async def metrics(_request):
        # vLLM metric names → exercised by worker/metrics_map.py
        lines = [
            "# TYPE vllm:num_requests_running gauge",
            "vllm:num_requests_running 0",
            "# TYPE vllm:prompt_tokens_total counter",
            f"vllm:prompt_tokens_total {STATS['prompt_tokens']}",
            "# TYPE vllm:generation_tokens_total counter",
            f"vllm:generation_tokens_total {STATS['generation_tokens']}",
            "# TYPE vllm:request_success_total counter",
            f"vllm:request_success_total {STATS['requests']}",
            # in-repo engine gauge names too, so the fleet rollup's
            # slots/occupancy math is exercised against the stub
            "# TYPE gpustack_engine_slots_total gauge",
            f"gpustack_engine_slots_total {flight.slots_total}",
            "# TYPE gpustack_engine_slots_used gauge",
            "gpustack_engine_slots_used 0",
            "# TYPE gpustack_engine_waiting gauge",
            "gpustack_engine_waiting 0",
        ]
        # flight families ride along exactly like the real engine
        # exporter, so the worker's normalization and the server's
        # fleet rollup see the full vocabulary in hermetic e2es
        lines.extend(flight.metrics_lines())
        return web.Response(text="\n".join(lines) + "\n")

    async def debug_flight(request: web.Request):
        try:
            limit = min(2048, int(request.query.get("limit", 100)))
        except ValueError:
            return web.json_response(
                {"error": "limit must be an integer"}, status=400
            )
        return web.json_response({
            "model": served_name,
            "records": flight.snapshot(limit=limit),
            "aggregate": flight.aggregate(),
            "overhead_ratio": round(flight.overhead_ratio(), 6),
        })

    async def debug_profile(request: web.Request):
        # the stub has no jax: what the real engine answers to a capture
        # with no out_dir, the steps' flight records alone
        try:
            steps = int(request.query.get("steps", 20))
        except ValueError:
            return web.json_response(
                {"error": "steps must be an integer"}, status=400
            )
        records = flight.snapshot(limit=max(1, steps))
        from gpustack_tpu.observability.flight import aggregate_records

        return web.json_response({
            "requested": steps,
            "steps_captured": len(records),
            "profiler": "flight-only",
            "artifact": "",
            "error": "",
            "records": records,
            "aggregate": aggregate_records(
                records, flight.slots_total
            ) if records else {},
        })

    app.router.add_get("/health", health)
    app.router.add_get("/v1/models", models)
    app.router.add_post("/v1/chat/completions", chat)
    app.router.add_post("/v1/completions", completions)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/flight", debug_flight)
    app.router.add_post("/debug/profile", debug_profile)
    return app


def main(argv=None) -> None:
    p = argparse.ArgumentParser("stub external engine")
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--served-name", default="stub-model")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument(
        "--fail-health-after", type=float, default=0.0,
        help="seconds after which /health flips 503 (crash-path tests)",
    )
    p.add_argument(
        "--token-delay", type=float, default=0.0,
        help="seconds between streamed SSE chunks (drain tests)",
    )
    args = p.parse_args(argv)
    web.run_app(
        build_app(
            args.served_name, args.fail_health_after, args.token_delay
        ),
        host=args.host, port=args.port, print=None,
    )


if __name__ == "__main__":
    main()
