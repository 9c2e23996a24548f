"""Worker HTTP server: health, node+engine metrics, instance logs.

Reference parity: the worker's own FastAPI (reference
worker/worker.py:332-413: logs/proxy routes) + MetricExporter
(worker/exporter.py:76-171 node gauges; /metrics aggregated engine
metrics via RuntimeMetricsAggregator, runtime_metrics_aggregator.py:48).
"""

from __future__ import annotations

import asyncio
import logging
import os
import re
import time
from typing import Dict, List, Optional, Tuple

import aiohttp
from aiohttp import web

from gpustack_tpu.observability.capture import CHILD_TIMEOUT_S

logger = logging.getLogger(__name__)

TAIL_DEFAULT = 200
TAIL_MAX = 5000


class WorkerServer:
    # no secret material flows through these; everything else requires
    # the per-worker proxy secret issued at registration
    PUBLIC_PATHS = {"/healthz", "/metrics", "/metrics/raw"}

    def __init__(self, agent) -> None:
        from gpustack_tpu.observability import tracing

        self.agent = agent
        # standalone worker: size this hop's trace ring from the
        # worker's own config (GPUSTACK_TPU_TRACE_RING_SIZE)
        tracing.get_store("worker").configure(
            int(getattr(
                getattr(agent, "cfg", None), "trace_ring_size", 512
            ))
        )
        # body cap must dominate the hops it relays for (server app: 64
        # MiB, audio engine: 256 MiB) — the default 1 MiB would 413 every
        # real audio upload at this middle hop
        self.app = web.Application(
            middlewares=[self._auth_middleware],
            client_max_size=256 * 2**20,
        )
        self.app.add_routes(
            [
                web.get("/healthz", self.healthz),
                web.get("/metrics", self.metrics),
                web.get("/metrics/raw", self.metrics_raw),
                web.get(
                    "/v2/instances/{id:\\d+}/logs", self.instance_logs
                ),
                web.get("/v2/filesystem/probe", self.filesystem_probe),
                web.post(
                    "/v2/dev-instances/{id:\\d+}/exec", self.dev_exec
                ),
                web.post(
                    "/v2/instances/{id:\\d+}/profile",
                    self.instance_profile,
                ),
                web.route(
                    "*",
                    "/proxy/instances/{id:\\d+}/{tail:.*}",
                    self.instance_proxy,
                ),
            ]
        )
        self._runner: Optional[web.AppRunner] = None
        # long-lived pool for the hot proxy path — per-request sessions
        # would pay connect+teardown per completion
        self._proxy_session: Optional[aiohttp.ClientSession] = None
        # in-flight data-plane requests per instance: the graceful-drain
        # gate (ServeManager waits for zero before SIGTERM) and a
        # /metrics gauge
        self._inflight: Dict[int, int] = {}
        # last-good engine scrape per instance: a wedged engine keeps
        # serving its frozen gauges WITH a visibly growing
        # gpustack_tpu:scrape_age_seconds instead of silently vanishing
        # from (or freezing inside) the worker's /metrics
        self._engine_scrape_cache: Dict[int, Tuple[str, float]] = {}

    def inflight_count(self, instance_id: int) -> int:
        return self._inflight.get(instance_id, 0)

    # KV-scoped tokens (api/auth.py mint_kv_token) authorize exactly
    # one instance's /kv/export relay — the credential engine→engine
    # pulls carry in a per-request header, so the full proxy secret
    # (which opens every route here) never travels between workers
    _KV_EXPORT_RE = re.compile(
        r"^/proxy/instances/(\d+)/kv/export/?$"
    )

    @web.middleware
    async def _auth_middleware(self, request: web.Request, handler):
        """Server→worker auth: bearer must equal this worker's proxy
        secret (reference confines the worker API behind worker auth,
        routes/worker/proxy.py; round 1 left these ports open) — or a
        short-lived KV-scoped token for that one export path."""
        import hmac as _hmac

        if request.path in self.PUBLIC_PATHS:
            return await handler(request)
        secret = getattr(self.agent, "proxy_secret", "")
        authz = request.headers.get("Authorization", "")
        token = authz[7:] if authz.startswith("Bearer ") else ""
        if not secret or not token:
            return web.json_response(
                {"error": "worker proxy authentication required"},
                status=401,
            )
        kv_target = self._KV_EXPORT_RE.match(request.path)
        if kv_target is not None:
            # the export relay accepts ONLY the instance-scoped token:
            # a peer engine holding the credential for this path must
            # not be able to replay it (or a captured full secret)
            # anywhere else — and conversely the full secret staying
            # off the engine→engine wire means a compromised engine
            # process never saw a credential that opens other routes
            from gpustack_tpu.api.auth import verify_kv_token

            if verify_kv_token(
                token, secret, int(kv_target.group(1))
            ):
                return await handler(request)
            return web.json_response(
                {"error": "kv export requires an instance-scoped "
                          "kv token"},
                status=401,
            )
        if _hmac.compare_digest(token, secret):
            return await handler(request)
        return web.json_response(
            {"error": "worker proxy authentication required"},
            status=401,
        )

    async def instance_proxy(self, request: web.Request) -> web.StreamResponse:
        """Authenticated reverse proxy to a local engine instance
        (reference routes/worker/proxy.py:200 model-name→port middleware;
        here instance-id→port — the server already resolved the model).
        Engines bind to 127.0.0.1, so this is the only way in.

        This hop adopts the server's ``traceparent``, records its own
        connect/ttft/stream spans (``gpustack_worker_request_duration_``
        ``seconds`` on /metrics + one ``trace=…`` log line), and hands
        a fresh child context to the engine."""
        from gpustack_tpu.observability import tracing

        sm = self.agent.serve_manager
        if sm is None:
            return web.json_response({"error": "not ready"}, status=503)
        instance_id = int(request.match_info["id"])
        run = sm.running.get(instance_id)
        if run is None or not run.port:
            # the header distinguishes THIS 404 (stale routing view —
            # the server's failover may retry another replica) from an
            # engine's own 404 (a client error that must pass through)
            return web.json_response(
                {"error": f"instance {instance_id} not running here"},
                status=404,
                headers={"X-GPUStack-Worker": "instance-not-running"},
            )
        tail = request.match_info["tail"]
        qs = f"?{request.query_string}" if request.query_string else ""
        url = f"http://127.0.0.1:{run.port}/{tail}{qs}"
        body = await request.read()
        headers = {
            k: v for k, v in request.headers.items()
            if k.lower() in (
                "content-type",
                "accept",
                # disaggregated KV handoff: the engine needs the peer
                # source URL + its worker-proxy credential to pull the
                # conversation's blocks (routes/openai_proxy.py)
                "x-gpustack-kv-source",
                "x-gpustack-kv-source-auth",
            )
        }
        trace = tracing.RequestTrace(
            tracing.from_headers(request.headers),
            "worker",
            f"{request.method} /proxy/instances/{instance_id}/{tail}",
        )
        # forward THIS hop's span id so the engine's parent_id points
        # at a recorded span (reconstructable cross-process tree)
        headers.update(trace.ctx.propagation_headers())
        if self._proxy_session is None or self._proxy_session.closed:
            self._proxy_session = aiohttp.ClientSession()
        # counted over the WHOLE relay (headers through last stream
        # byte): drain waits on this, so an in-flight SSE generation
        # holds the count until its final chunk lands
        self._inflight[instance_id] = (
            self._inflight.get(instance_id, 0) + 1
        )
        status = 502
        try:
            trace.begin("connect")
            async with self._proxy_session.request(
                request.method,
                url,
                data=body or None,
                headers=headers,
                timeout=aiohttp.ClientTimeout(total=600),
            ) as upstream:
                trace.end("connect")
                status = upstream.status
                out_headers = {
                    "Content-Type": upstream.headers.get(
                        "Content-Type", "application/json"
                    ),
                    "Cache-Control": "no-cache",
                }
                out_headers.update(trace.ctx.propagation_headers())
                resp = web.StreamResponse(
                    status=upstream.status, headers=out_headers,
                )
                await resp.prepare(request)
                trace.begin("ttft")
                first = True
                async for chunk in upstream.content.iter_any():
                    if first:
                        first = False
                        trace.end("ttft")
                        trace.begin("stream")
                    await resp.write(chunk)
                await resp.write_eof()
                return resp
        except (aiohttp.ClientError, OSError) as e:
            trace.event("engine_unreachable", error=str(e))
            return web.json_response(
                {"error": f"engine unreachable: {e}"}, status=502
            )
        finally:
            trace.finish(status=status, instance_id=instance_id)
            n = self._inflight.get(instance_id, 1) - 1
            if n <= 0:
                self._inflight.pop(instance_id, None)
            else:
                self._inflight[instance_id] = n

    async def start(self, host: str, port: int) -> int:
        """Bind and return the actual port (``port=0`` binds ephemeral —
        the caller registers whatever the kernel handed out, so two
        workers on one host can never fight over a fixed port)."""
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        bound = port
        for sock in site._server.sockets:  # noqa: SLF001 (aiohttp has no API)
            bound = sock.getsockname()[1]
            break
        logger.info("worker http listening on %s:%d", host, bound)
        return bound

    async def stop(self) -> None:
        if self._proxy_session and not self._proxy_session.closed:
            await self._proxy_session.close()
        if self._runner:
            await self._runner.cleanup()

    # ------------------------------------------------------------------

    async def healthz(self, request: web.Request) -> web.Response:
        sm = self.agent.serve_manager
        return web.json_response(
            {
                "status": "ok",
                "worker_id": self.agent.worker_id,
                "instances": sorted(sm.running) if sm else [],
            }
        )

    async def metrics(self, request: web.Request) -> web.Response:
        status = self.agent.detector.detect()
        lines = [
            "# TYPE gpustack_worker_cpu_count gauge",
            f"gpustack_worker_cpu_count {status.cpu_count}",
            "# TYPE gpustack_worker_memory_total_bytes gauge",
            f"gpustack_worker_memory_total_bytes "
            f"{status.memory_total_bytes}",
            "# TYPE gpustack_worker_memory_used_bytes gauge",
            f"gpustack_worker_memory_used_bytes "
            f"{status.memory_used_bytes}",
            "# TYPE gpustack_worker_tpu_chips gauge",
            f"gpustack_worker_tpu_chips {len(status.chips)}",
        ]
        for chip in status.chips:
            lines.append(
                f'gpustack_worker_tpu_hbm_bytes{{chip="{chip.index}",'
                f'type="{chip.chip_type}"}} {chip.hbm_bytes}'
            )
        # data-plane resilience: in-flight relay counts (the drain gate)
        # + cumulative drain accounting from the serve manager
        if self._inflight:
            lines.append(
                "# TYPE gpustack_worker_inflight_requests gauge"
            )
            for iid, n in sorted(self._inflight.items()):
                lines.append(
                    f"gpustack_worker_inflight_requests"
                    f'{{instance_id="{iid}"}} {n}'
                )
        sm = self.agent.serve_manager
        if sm is not None:
            lines += [
                "# TYPE gpustack_worker_drains_total counter",
                f"gpustack_worker_drains_total "
                f"{getattr(sm, 'drains_total', 0)}",
                "# TYPE gpustack_worker_drain_seconds_total counter",
                f"gpustack_worker_drain_seconds_total "
                f"{round(getattr(sm, 'drain_seconds_total', 0.0), 3)}",
            ]
        # per-phase relay latency histograms (observability/metrics.py):
        # connect/ttft/stream through this reverse proxy
        from gpustack_tpu.observability.metrics import get_registry

        lines.extend(get_registry("worker").render_lines())
        # normalized engine metrics: per-engine names mapped onto the
        # gpustack_tpu:* namespace (reference RuntimeMetricsAggregator +
        # metrics_config.yaml)
        from gpustack_tpu.worker.metrics_map import (
            normalize_engine_metrics,
        )

        scrapes = await self._scrape_engines()
        if scrapes:
            # scrape staleness: age of the body each instance's series
            # below were read from — 0-ish on a live engine, growing on
            # a wedged one (the cached last-good body keeps serving so
            # the freeze is visible instead of silent)
            lines.append("# TYPE gpustack_tpu:scrape_age_seconds gauge")
            for iid, _body, age_s, _model in scrapes:
                lines.append(
                    f"gpustack_tpu:scrape_age_seconds"
                    f'{{instance_id="{iid}"}} {age_s:.3f}'
                )
        for iid, body, _age_s, model in scrapes:
            extra = {"instance_id": str(iid)}
            if model:
                extra["model"] = model
            lines.extend(normalize_engine_metrics(body, extra))
        return web.Response(text="\n".join(lines) + "\n")

    async def metrics_raw(self, request: web.Request) -> web.Response:
        """Unmapped engine metrics passthrough (reference /metrics/raw)."""
        from gpustack_tpu.worker.metrics_map import raw_engine_metrics

        lines = []
        for iid, body, _age_s, model in await self._scrape_engines():
            extra = {"instance_id": str(iid)}
            if model:
                extra["model"] = model
            lines.extend(raw_engine_metrics(body, extra))
        return web.Response(text="\n".join(lines) + "\n")

    async def _scrape_engines(
        self,
    ) -> List[Tuple[int, str, float, str]]:
        """Scrape every local engine's /metrics. Returns
        ``(instance_id, body, age_seconds, model_name)`` per instance —
        ``body`` is the freshest successful scrape (this call's when it
        succeeded, the cached last-good one when the engine is wedged)
        and ``age_seconds`` says how stale it is."""
        sm = self.agent.serve_manager
        out: List[Tuple[int, str, float, str]] = []
        if not sm:
            return out
        running = dict(sm.running)
        async with aiohttp.ClientSession() as session:
            for iid, run in running.items():
                now = time.time()
                try:
                    async with session.get(
                        f"http://127.0.0.1:{run.port}/metrics",
                        timeout=aiohttp.ClientTimeout(total=2),
                    ) as resp:
                        if resp.status == 200:
                            self._engine_scrape_cache[iid] = (
                                await resp.text(), now,
                            )
                except (aiohttp.ClientError, OSError):
                    pass
                cached = self._engine_scrape_cache.get(iid)
                if cached is None:
                    continue   # never scraped successfully yet
                body, scraped_at = cached
                out.append((
                    iid, body, max(0.0, now - scraped_at),
                    getattr(run, "model_name", ""),
                ))
        # instances gone from the routing table take their cache along
        for iid in list(self._engine_scrape_cache):
            if iid not in running:
                self._engine_scrape_cache.pop(iid, None)
        return out

    async def instance_profile(self, request: web.Request) -> web.Response:
        """Relay an on-demand profiler capture to a local engine
        (server admin ``POST /v2/model-instances/{id}/profile`` lands
        here). The worker picks the artifact directory — under the
        instance log dir, next to the engine's logs — because the
        engine process runs on this host and can write it directly."""
        sm = self.agent.serve_manager
        if sm is None:
            return web.json_response({"error": "not ready"}, status=503)
        instance_id = int(request.match_info["id"])
        run = sm.running.get(instance_id)
        if run is None or not run.port:
            return web.json_response(
                {"error": f"instance {instance_id} not running here"},
                status=404,
                headers={"X-GPUStack-Worker": "instance-not-running"},
            )
        try:
            steps = int(request.query.get("steps", 20))
            timeout_s = min(
                120.0, float(request.query.get("timeout_s", 30.0))
            )
        except ValueError:
            return web.json_response(
                {"error": "steps/timeout_s must be numbers"}, status=400
            )
        if steps < 1:
            return web.json_response(
                {"error": "steps must be >= 1"}, status=400
            )
        out_dir = os.path.join(
            sm.log_dir, f"profile-{instance_id}-{int(time.time())}"
        )
        from urllib.parse import quote

        url = (
            f"http://127.0.0.1:{run.port}/debug/profile"
            f"?steps={steps}&timeout_s={timeout_s}"
            f"&out_dir={quote(out_dir, safe='')}"
        )
        if self._proxy_session is None or self._proxy_session.closed:
            self._proxy_session = aiohttp.ClientSession()
        try:
            async with self._proxy_session.post(
                url,
                # the steps, the profiler's stop, and the summary of the
                # trace (a child with a time limit of its own)
                timeout=aiohttp.ClientTimeout(
                    total=timeout_s + 60 + CHILD_TIMEOUT_S
                ),
            ) as upstream:
                try:
                    payload = await upstream.json()
                except (aiohttp.ContentTypeError, ValueError):
                    payload = {"error": await upstream.text()}
                return web.json_response(
                    payload, status=upstream.status
                )
        except (
            aiohttp.ClientError, OSError, asyncio.TimeoutError,
        ) as e:
            return web.json_response(
                {"error": f"engine unreachable: {e}"}, status=502
            )

    async def filesystem_probe(self, request: web.Request) -> web.Response:
        """Probe a worker-local model path for the scheduler/evaluator
        (reference routes/worker/filesystem.py: remote filesystem checks
        for scheduling + config probing).

        Deliberately narrow: only paths under the worker's model roots
        (cache dir + GPUSTACK_TPU_MODEL_ROOTS) are probe-able — the
        worker port carries no auth, so this must not be a filesystem
        oracle — and only ``config.json`` content is ever returned.
        """
        import glob as _glob
        import json as _json

        path = request.query.get("path", "")
        if not path or not os.path.isabs(path):
            return web.json_response(
                {"error": "absolute 'path' query param required"},
                status=400,
            )
        real = os.path.realpath(path)
        roots = [os.path.realpath(self.agent.cfg.cache_dir)]
        roots += [
            os.path.realpath(r)
            for r in os.environ.get(
                "GPUSTACK_TPU_MODEL_ROOTS", ""
            ).split(":")
            if r
        ]
        if not any(
            real == root or real.startswith(root + os.sep)
            for root in roots
        ):
            return web.json_response(
                {
                    "error": (
                        "path outside configured model roots (cache dir "
                        "or GPUSTACK_TPU_MODEL_ROOTS)"
                    )
                },
                status=403,
            )
        path = real
        result = {
            "path": path,
            "exists": os.path.isdir(path),
            "safetensors_files": 0,
            "gguf_files": 0,
            "total_bytes": 0,
            "config": None,
        }
        if result["exists"]:

            def _scan():
                # checkpoint dirs hold hundreds of multi-GB shards and
                # may sit on networked storage — never glob them on the
                # event loop
                escaped = _glob.escape(path)
                st = _glob.glob(os.path.join(escaped, "*.safetensors"))
                gg = _glob.glob(os.path.join(escaped, "*.gguf"))
                total = sum(
                    os.path.getsize(f)
                    for f in st + gg
                    if os.path.exists(f)
                )
                return len(st), len(gg), total

            (
                result["safetensors_files"],
                result["gguf_files"],
                result["total_bytes"],
            ) = await asyncio.to_thread(_scan)
            cfg_path = os.path.join(path, "config.json")
            # re-resolve: a symlinked config.json inside an allowed root
            # must not read files outside the roots
            cfg_real = os.path.realpath(cfg_path)
            cfg_allowed = any(
                cfg_real == root or cfg_real.startswith(root + os.sep)
                for root in roots
            )
            if os.path.exists(cfg_path) and cfg_allowed:

                def _load_config():
                    with open(cfg_real) as f:
                        return _json.load(f)

                try:
                    result["config"] = await asyncio.to_thread(
                        _load_config
                    )
                except (OSError, _json.JSONDecodeError) as e:
                    result["config_error"] = str(e)
            elif os.path.exists(cfg_path):
                result["config_error"] = "config.json escapes model roots"
        return web.json_response(result)

    async def dev_exec(self, request: web.Request) -> web.Response:
        """Run a command in a dev instance's environment (the TPU-native
        access path of the reference's SSH-able gpu_instances — chips
        scoped via TPU_VISIBLE_CHIPS, auth via the worker proxy secret,
        reached only through the server's authorized exec route)."""
        dm = getattr(self.agent, "dev_manager", None)
        if dm is None:
            return web.json_response({"error": "not ready"}, status=503)
        dev_id = int(request.match_info["id"])
        try:
            body = await request.json()
        except ValueError:
            return web.json_response(
                {"error": "invalid JSON"}, status=400
            )
        argv = body.get("cmd")
        if not isinstance(argv, list) or not argv or not all(
            isinstance(a, str) for a in argv
        ):
            return web.json_response(
                {"error": "'cmd' must be a non-empty list of strings"},
                status=400,
            )
        try:
            timeout = min(float(body.get("timeout", 60.0)), 600.0)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "bad 'timeout'"}, status=400
            )
        try:
            result = await dm.exec(dev_id, argv, timeout=timeout)
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response(result)

    async def instance_logs(self, request: web.Request) -> web.Response:
        sm = self.agent.serve_manager
        if sm is None:
            return web.json_response({"error": "not ready"}, status=503)
        instance_id = int(request.match_info["id"])
        try:
            tail = min(
                TAIL_MAX, int(request.query.get("tail", TAIL_DEFAULT))
            )
        except ValueError:
            return web.json_response(
                {"error": "tail must be an integer"}, status=400
            )
        # log files are named {instance_name}-{id}.log
        def _find_log():
            for fname in os.listdir(sm.log_dir):
                if fname.endswith(f"-{instance_id}.log"):
                    return os.path.join(sm.log_dir, fname)
            return None

        match = await asyncio.to_thread(_find_log)
        if match is None:
            return web.json_response(
                {"error": f"no logs for instance {instance_id}"}, status=404
            )
        def _read_tail():
            with open(match, "rb") as f:
                f.seek(0, os.SEEK_END)
                end = f.tell()
                f.seek(max(0, end - 512 * 1024))
                return end, f.read().decode(errors="replace")

        size, text = await asyncio.to_thread(_read_tail)
        lines = text.splitlines()[-tail:]
        body = "\n".join(lines) + "\n"
        if request.query.get("follow") not in ("1", "true"):
            return web.Response(text=body)

        # follow mode (reference routes/worker/logs.py tail+follow):
        # stream the tail, then poll the file for appended bytes until
        # the client disconnects or the instance's log goes away
        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/plain; charset=utf-8",
                "Cache-Control": "no-cache",
            }
        )
        await resp.prepare(request)
        await resp.write(body.encode())
        offset = size
        try:
            while True:
                await asyncio.sleep(0.5)
                try:
                    new_size = os.path.getsize(match)
                except OSError:
                    break  # rotated/removed
                if new_size < offset:
                    offset = 0  # truncated: restart from head
                if new_size > offset:

                    def _read_chunk(start=offset):
                        with open(match, "rb") as f:
                            f.seek(start)
                            return f.read(512 * 1024)

                    chunk = await asyncio.to_thread(_read_chunk)
                    offset += len(chunk)
                    await resp.write(chunk)
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        return resp
