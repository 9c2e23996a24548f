"""Worker agent: register → heartbeat/status loops → instance watch.

Reference parity (gpustack/worker/worker.py:65): registration with retry
(cluster token → server-issued worker token), heartbeat + status sync
threads (async tasks here), instance event watch feeding the ServeManager.
"""

from __future__ import annotations

import asyncio
import logging
import socket
import uuid
from typing import List, Optional

from gpustack_tpu.client.client import (
    APIError,
    NETWORK_ERRORS,
    ClientSet,
)
from gpustack_tpu.config import Config
from gpustack_tpu.detectors import create_detector
from gpustack_tpu.worker.serve_manager import ServeManager

logger = logging.getLogger(__name__)


def _default_ip() -> str:
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except OSError:
        return "127.0.0.1"


class WorkerAgent:
    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.client: Optional[ClientSet] = None
        self.worker_id = 0
        self.worker_name = cfg.worker_name or socket.gethostname()
        self.worker_uuid = self._load_or_create_uuid()
        self.detector = create_detector(cfg.fake_detector or None)
        self.serve_manager: Optional[ServeManager] = None
        self.bound_port = 0  # actual HTTP port once bound (worker_port=0 ⇒ ephemeral)
        self._tasks: List[asyncio.Task] = []
        self._stopping = False
        self._recovery_reconcile: Optional[asyncio.Task] = None

    def _load_or_create_uuid(self) -> str:
        """Stable worker identity across restarts: a fresh uuid per boot
        would make re-registration collide on the worker name forever
        (server keeps the old record)."""
        import os

        path = os.path.join(self.cfg.data_dir, "worker_uuid")
        try:
            with open(path) as f:
                value = f.read().strip()
            if value:
                return value
        except OSError:
            pass
        value = uuid.uuid4().hex
        try:
            with open(path, "w") as f:
                f.write(value)
        except OSError:
            logger.warning("cannot persist worker uuid at %s", path)
        return value

    async def start(self) -> None:
        from gpustack_tpu.worker.server import WorkerServer

        self.http = WorkerServer(self)
        # Bind BEFORE registering: the worker HTTP server is the sole
        # inference ingress (engines bind to loopback), so failing to
        # bind is a total outage — die loudly here rather than register
        # a worker the server can never dial. Binding first also lets
        # worker_port=0 mean "ephemeral": registration below carries the
        # port the kernel actually handed out. (Round 3 postmortem: a
        # stale process holding the fixed port killed the embedded
        # worker with zero diagnostics.)
        try:
            self.bound_port = await self.http.start(
                "0.0.0.0", self.cfg.worker_port
            )
        except OSError as e:
            raise RuntimeError(
                f"worker HTTP server cannot bind port "
                f"{self.cfg.worker_port}: {e} — another process holds it; "
                f"set --worker-port 0 for an ephemeral port"
            ) from e
        await self._register_with_retry()
        self.serve_manager = ServeManager(
            self.cfg, self.client, self.worker_id
        )
        # graceful drain: stops wait for the reverse proxy's in-flight
        # count to reach zero before SIGTERM (worker/server.py counter)
        self.serve_manager.inflight_source = self.http.inflight_count
        self.serve_manager.start_log_rotation()
        # reaps block on /proc probes and grace waits — keep them off
        # the event loop so /healthz and registration stay responsive
        # during startup cleanup after a crash
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            None, self.serve_manager.reap_orphans
        )
        from gpustack_tpu.worker.benchmark_manager import BenchmarkManager

        self.benchmark_manager = BenchmarkManager(
            self.client, self.worker_id
        )
        from gpustack_tpu.worker.dev_manager import DevManager

        self.dev_manager = DevManager(
            self.cfg, self.client, self.worker_id
        )
        await loop.run_in_executor(None, self.dev_manager.reap_orphans)
        # push one status immediately so the scheduler sees chips
        await self._post_status_once()
        # converge with the server's view (restart recovery: zombie
        # RUNNING records, orphan stops) before the watch stream starts
        await self.serve_manager.reconcile()
        await self.dev_manager.reconcile()
        self._tasks = [
            asyncio.create_task(self._heartbeat_loop(), name="wk-heartbeat"),
            asyncio.create_task(self._status_loop(), name="wk-status"),
            asyncio.create_task(self._watch_instances(), name="wk-watch"),
            asyncio.create_task(self._watch_benchmarks(), name="wk-bench"),
            asyncio.create_task(
                self._watch_dev_instances(), name="wk-dev"
            ),
            asyncio.create_task(
                self._watch_backends(), name="wk-backends"
            ),
            asyncio.create_task(
                self.benchmark_manager.rescan_loop(), name="wk-bench-rescan"
            ),
        ]
        if self.cfg.tunnel:
            # NAT'd deployment: dial out and serve over the tunnel
            from gpustack_tpu.tunnel.client import TunnelClient

            self.tunnel_client = TunnelClient(
                self.cfg.server_url,
                self._worker_token,
                self.bound_port or self.cfg.worker_port,
            )
            self._tasks.append(
                asyncio.create_task(
                    self.tunnel_client.run_forever(), name="wk-tunnel"
                )
            )
        logger.info(
            "worker %s (id=%d) started", self.worker_name, self.worker_id
        )

    async def run_forever(self) -> None:
        """Run until SIGTERM/SIGINT, then stop gracefully: engines are
        terminated before the agent exits, so none is left on a chip."""
        from gpustack_tpu.utils.process import (
            signalled_before,
            stop_signal_event,
        )

        signalled = stop_signal_event()
        await self.start()
        if await signalled_before(signalled, asyncio.gather(*self._tasks)):
            logger.info("stop signal received: shutting down")
            await self.stop()

    async def stop(self) -> None:
        self._stopping = True
        for t in self._tasks:
            t.cancel()
        if self._recovery_reconcile is not None:
            # a reconcile racing shutdown could spawn a fresh engine
            # AFTER stop_all() below, or use the client after close()
            self._recovery_reconcile.cancel()
        if self.serve_manager:
            await self.serve_manager.stop_all()
        if getattr(self, "dev_manager", None):
            await self.dev_manager.stop_all()
        if getattr(self, "http", None):
            await self.http.stop()
        if self.client:
            await self.client.close()

    # ---- registration ---------------------------------------------------

    async def _register_with_retry(self) -> None:
        anon = ClientSet(self.cfg.server_url)
        delay = 2.0
        while True:
            try:
                result = await anon.register_worker(
                    {
                        "registration_token": self.cfg.registration_token,
                        "name": self.worker_name,
                        "worker_uuid": self.worker_uuid,
                        "ip": self.cfg.worker_ip or _default_ip(),
                        "port": self.bound_port or self.cfg.worker_port,
                    }
                )
                break
            except NETWORK_ERRORS as e:
                logger.warning(
                    "registration failed (%s); retrying in %.0fs", e, delay
                )
                await asyncio.sleep(delay)
                delay = min(30.0, delay * 1.7)
        await anon.close()
        self.worker_id = result["worker_id"]
        self.worker_name = result["name"]
        self.proxy_secret = result.get("proxy_secret", "")
        self._worker_token = result["token"]
        self.client = ClientSet(self.cfg.server_url, result["token"])

    # ---- loops ----------------------------------------------------------

    async def _heartbeat_loop(self) -> None:
        import random

        interval = self.cfg.heartbeat_interval
        while not self._stopping:
            recovered = False
            # one FAST retry: heartbeats are the worker's liveness
            # signal and the server's staleness budget is only ~4.5
            # intervals — waiting a full interval after a single lost
            # request spends a third of it for nothing
            for attempt in (0, 1):
                try:
                    resp = await self.client.heartbeat(self.worker_id)
                    recovered = bool(resp and resp.get("recovered"))
                    break
                except NETWORK_ERRORS as e:
                    if attempt == 0:
                        logger.warning(
                            "heartbeat failed: %s; fast retry", e
                        )
                        await asyncio.sleep(
                            min(1.0, interval * 0.2)
                            * random.uniform(0.5, 1.0)
                        )
                    else:
                        logger.warning("heartbeat retry failed: %s", e)
            if recovered and self.serve_manager is not None:
                # the server had us marked UNREACHABLE: our instances
                # may be parked UNREACHABLE and only this agent can
                # legally re-drive them — reconcile now instead of
                # waiting for a watch RESYNC that may never come.
                # FIRE-AND-FORGET (deduped): awaiting reconcile inline
                # would starve the liveness signal during exactly the
                # flaky-network window that triggers it — slow API
                # calls would stall heartbeats past the staleness
                # budget and re-park everything in a recover/park loop.
                # The level-triggered `recovered` flag re-arms this on
                # a later heartbeat if the attempt fails.
                task = self._recovery_reconcile
                if task is None or task.done():
                    logger.warning(
                        "server reports we were unreachable; reconciling"
                    )
                    self._recovery_reconcile = asyncio.create_task(
                        self._post_recovery_reconcile(),
                        name="wk-recovery-reconcile",
                    )
            # jittered cadence: a fleet restarted together must not
            # heartbeat in lockstep forever
            await asyncio.sleep(interval * random.uniform(0.9, 1.1))

    async def _post_recovery_reconcile(self) -> None:
        try:
            await self.serve_manager.reconcile()
        except Exception:
            logger.exception("post-recovery reconcile failed")

    async def _status_loop(self) -> None:
        while not self._stopping:
            await asyncio.sleep(self.cfg.status_interval)
            await self._post_status_once()

    async def _post_status_once(self) -> None:
        try:
            status = self.detector.detect()
            await self.client.post_status(
                self.worker_id, status.model_dump(mode="json")
            )
        except NETWORK_ERRORS as e:
            logger.warning("status post failed: %s", e)
        except Exception:
            logger.exception("detector failed")

    async def _watch_instances(self) -> None:
        async for event in self.client.watch("model-instances"):
            try:
                await self.serve_manager.handle_event(event)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("serve manager failed on %s", event.type)

    async def _watch_benchmarks(self) -> None:
        async for event in self.client.watch("benchmarks"):
            try:
                await self.benchmark_manager.handle_event(event)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("benchmark manager failed on %s", event.type)

    async def _watch_dev_instances(self) -> None:
        async for event in self.client.watch("dev-instances"):
            try:
                await self.dev_manager.handle_event(event)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("dev manager failed on %s", event.type)

    async def _watch_backends(self) -> None:
        async for event in self.client.watch("inference-backends"):
            try:
                self.serve_manager.handle_backend_event(event)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("backend cache failed on %s", event.type)
