"""Server bootstrap (reference gpustack/server/server.py:254 Server.start):
migrations → data init (admin user, default cluster, backend catalog) →
app → leader tasks (controllers, scheduler, syncer) → HTTP site →
optional embedded worker.

The embedded worker runs as an asyncio task in-process talking to
localhost over HTTP — same contract as a remote worker (the reference
spawns a multiprocessing.Process instead, cmd/start.py:736-755; our engine
processes are the true process boundary)."""

from __future__ import annotations

import asyncio
import logging
import os
import secrets
from typing import List, Optional

from aiohttp import web

from gpustack_tpu.api import auth as auth_mod
from gpustack_tpu.config import Config
from gpustack_tpu.orm.db import Database, run_migrations
from gpustack_tpu.orm.record import Record
from gpustack_tpu.scheduler.scheduler import Scheduler
from gpustack_tpu.schemas import Cluster, InferenceBackend, User
from gpustack_tpu.schemas.inference_backends import BackendVersionConfig
from gpustack_tpu.server.app import create_app
from gpustack_tpu.server.bus import EventBus
from gpustack_tpu.server.controllers import (
    InstanceRescuer,
    ModelController,
    ModelProviderController,
    WorkerController,
    WorkerSyncer,
)

logger = logging.getLogger(__name__)


BUILTIN_BACKEND = InferenceBackend(
    name="tpu-native",
    description="Built-in JAX/XLA serving engine (gpustack_tpu.engine)",
    builtin=True,
    versions=[
        BackendVersionConfig(
            version="latest",
            command=[
                "{python}", "-m", "gpustack_tpu.engine.api_server",
                "--port", "{port}",
                "--served-name", "{served_name}",
                "--max-seq-len", "{max_seq_len}",
                "--max-slots", "{max_slots}",
            ],
            health_path="/healthz",
        )
    ],
)


class Server:
    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.db: Optional[Database] = None
        self.bus = EventBus()
        self._tasks: List = []
        self._runner: Optional[web.AppRunner] = None
        self._stop = asyncio.Event()
        self.worker_agent = None

    async def start(self) -> None:
        cfg = self.cfg
        self.db = Database(cfg.database_path)
        run_migrations(self.db)
        # record classes register at module import; collector-owned
        # tables (resource_event, system_load, usage_archive) must be
        # registered BEFORE create_all_tables or they silently miss
        import gpustack_tpu.server.collectors  # noqa: F401
        Record.bind(self.db, self.bus)
        # context-local binding too: the in-process multi-server chaos
        # harness boots several Servers in one process — every task this
        # coroutine spawns (coordinator, controllers, HTTP accept path)
        # inherits THIS server's db/bus instead of whichever server
        # bound last; request handlers additionally re-bind via the app
        # middleware below
        Record.bind_context(self.db, self.bus)
        Record.create_all_tables(self.db)
        if not cfg.ha:
            # HA: bootstrap writes are leader-only (racing get-or-create
            # on a shared DB would duplicate the admin user/cluster)
            await self._init_data()

        app = create_app(cfg)
        self.app = app
        app["record_binding"] = (self.db, self.bus)
        # bounded shutdown: a restart must not hang behind long-lived
        # watch/log-follow streams (chaos finding: the default 60 s
        # connection drain made restart-mid-reconcile a minute-long
        # op). On the runner, not the site — the site-level parameter
        # is deprecated in aiohttp 3.11.
        self._runner = web.AppRunner(
            app, shutdown_timeout=cfg.shutdown_timeout
        )
        await self._runner.setup()
        site = web.TCPSite(self._runner, cfg.host, cfg.port)

        # leader-only tasks gate on the coordinator (reference
        # server/server.py:1256-1339): LocalCoordinator for single-server,
        # LeaseCoordinator for shared-DB HA
        from gpustack_tpu.server.coordinator import (
            LeaseCoordinator,
            LocalCoordinator,
        )

        # a plugin may supply the coordinator (reference: distributed
        # coordinators ship as plugins, server/server.py:1166-1194)
        plugin_coordinator = None
        for plugin in app.get("plugins", []):
            try:
                plugin_coordinator = plugin.coordinator(cfg)
            except Exception:
                logger.exception(
                    "plugin %s coordinator() failed",
                    plugin.name or type(plugin),
                )
            if plugin_coordinator is not None:
                break
        self.coordinator = plugin_coordinator or (
            LeaseCoordinator(self.db, bus=self.bus, ttl=cfg.ha_ttl)
            if cfg.ha else LocalCoordinator()
        )
        if cfg.ha:
            # replicate every post-commit event to HA peers through the
            # shared change_log table (id-only; peers re-fetch). A sync
            # bus tap: publish_remote only enqueues.
            self.bus.add_tap(self.coordinator.publish_remote)
        from gpustack_tpu.cloud.controller import WorkerPoolController

        from gpustack_tpu.server.controllers import RouteTargetController

        self.controllers = [
            ModelController(),
            ModelProviderController(),
            RouteTargetController(),
            WorkerController(),
            WorkerPoolController(
                server_url=cfg.advertised_url
                or f"http://{cfg.host}:{cfg.port}",
                registration_token=cfg.registration_token,
            ),
        ]
        self.scheduler = Scheduler()
        self.syncer = WorkerSyncer(
            stale_after=cfg.heartbeat_interval * 4.5,
            interval=cfg.heartbeat_interval,
            # degraded-mode safety: heartbeats this server has SEEN but
            # not yet flushed must never read as stale (the combiner's
            # in-memory freshness map is ahead of the DB by design)
            freshness_source=app["write_combiner"].freshness_for,
        )
        self.rescuer = InstanceRescuer(
            grace=cfg.unreachable_rescue_after,
            interval=cfg.heartbeat_interval,
        )

        from gpustack_tpu.server.collectors import (
            ResourceEventLogger,
            SystemLoadCollector,
            UsageArchiver,
        )

        # heartbeat/status write combiner (constructed in create_app so
        # unit mounts have the debug/metrics surface): flushes on every
        # server, leader or follower — heartbeats land wherever the
        # load balancer sends them
        self.write_combiner = app["write_combiner"]
        self.write_combiner.start()
        # reload-config propagates rotated tokens/URLs into controllers
        # that copied them at construction (routes/extras.py)
        app["controllers"] = self.controllers
        self.usage_archiver = UsageArchiver()
        self.resource_events = ResourceEventLogger()
        self.system_load = SystemLoadCollector()
        from gpustack_tpu.server.sloeval import SLOEvaluator

        # per-model SLO engine: burn-rate alerting + incident ring
        # (observability/slo.py). Constructed unconditionally so the
        # /v2/debug/slo surface and /metrics families exist on every
        # server; evaluation ticks are leader-only like the other
        # collectors (two HA peers double-judging would double-count
        # availability samples).
        self.slo_evaluator = SLOEvaluator(app, cfg)
        app["slo"] = self.slo_evaluator
        from gpustack_tpu.server.autoscaler import Autoscaler
        from gpustack_tpu.server.rollout import RolloutController

        # rollouts + autoscaling consume the SLO/fleet signals above;
        # constructed always (debug surfaces + manual rollback need
        # them on every server), reconcile ticks leader-only
        self.rollout_controller = RolloutController(app, cfg)
        app["rollout"] = self.rollout_controller
        self.autoscaler = Autoscaler(app, cfg)
        app["autoscaler"] = self.autoscaler
        from gpustack_tpu.server.update_check import UpdateChecker

        self.update_checker = UpdateChecker()
        self.update_checker.start()  # no-op without GPUSTACK_TPU_UPDATE_URL

        from gpustack_tpu.server.backend_catalog import BackendCatalogSync

        self.backend_catalog = BackendCatalogSync(
            cfg.backend_catalog_url
            or os.environ.get("GPUSTACK_TPU_BACKEND_CATALOG", "")
        )

        async def on_leadership(leading: bool) -> None:
            if leading:
                if cfg.ha:
                    if cfg.ha_epoch_fence and getattr(
                        self.coordinator, "epoch", 0
                    ):
                        # stamp this context with the acquired epoch
                        # BEFORE starting leader-only tasks: every task
                        # below inherits it, so their writes reject
                        # atomically once a successor bumps the lease
                        # epoch (orm/fencing.py)
                        from gpustack_tpu.orm import fencing

                        fencing.set_fence(self.coordinator.epoch)
                    await self._init_data()
                for c in self.controllers:
                    c.start()
                self.scheduler.start()
                self.syncer.start()
                self.rescuer.start()
                self.usage_archiver.start()
                self.resource_events.start()
                self.system_load.start()
                self.backend_catalog.start()
                self.slo_evaluator.start()
                self.rollout_controller.start()
                self.autoscaler.start()

        self.coordinator.on_leadership_change(on_leadership)
        await self.coordinator.start()
        app["coordinator"] = self.coordinator

        await site.start()
        logger.info("server listening on %s:%d", cfg.host, cfg.port)

        if not cfg.disable_worker:
            from gpustack_tpu.worker.worker import WorkerAgent

            worker_cfg = cfg.model_copy()
            worker_cfg.server_url = f"http://127.0.0.1:{cfg.port}"
            self.worker_agent = WorkerAgent(worker_cfg)
            worker_task = asyncio.create_task(
                self.worker_agent.start(), name="embedded-worker"
            )

            def _on_worker_done(t: asyncio.Task) -> None:
                # An embedded worker that dies at startup (e.g. its HTTP
                # port is already taken) must be LOUD: round-3 postmortem
                # was an entire e2e tier red with zero diagnostics
                # because this task swallowed its exception. Log it and
                # flip /healthz to degraded so operators and tests see it.
                if t.cancelled():
                    return
                exc = t.exception()
                if exc is not None:
                    logger.error(
                        "embedded worker died during startup: %s", exc,
                        exc_info=exc,
                    )
                    app["embedded_worker_error"] = repr(exc)

            worker_task.add_done_callback(_on_worker_done)
            self._tasks.append(worker_task)

    async def run_forever(self) -> None:
        """Serve until SIGTERM/SIGINT (graceful: the embedded worker's
        engines are stopped first) or until a fatal path shut us down."""
        from gpustack_tpu.utils.process import (
            signalled_before,
            stop_signal_event,
        )

        signalled = stop_signal_event()
        await self.start()
        if await signalled_before(signalled, self._stop.wait()):
            logger.info("stop signal received: shutting down")
            await self.stop()

    async def stop(self) -> None:
        await self._shutdown(release_lease=True)

    async def abort(self) -> None:
        """Hard stop without releasing the leadership lease — the fatal
        path (lost lease) and the chaos harness's leader-kill both come
        through here. A crashed leader deletes nothing: its lease row
        must EXPIRE before a follower may acquire, which is exactly the
        failover the TTL contract promises."""
        await self._shutdown(release_lease=False)

    async def _shutdown(self, release_lease: bool) -> None:
        if self.worker_agent:
            await self.worker_agent.stop()
        if hasattr(self, "coordinator"):
            halt = getattr(self.coordinator, "halt", None)
            if release_lease or halt is None:
                await self.coordinator.stop()
            else:
                await halt()
        for c in getattr(self, "controllers", []):
            c.stop()
        if hasattr(self, "scheduler"):
            self.scheduler.stop()
        if hasattr(self, "syncer"):
            self.syncer.stop()
        if hasattr(self, "rescuer"):
            self.rescuer.stop()
        if hasattr(self, "write_combiner"):
            # shared drain contract: buffered heartbeat/status writes
            # land now or fail LOUDLY with the same typed error a
            # write queued behind Database.close() gets
            try:
                await self.write_combiner.drain()
            except Exception:
                logger.exception(
                    "write combiner drain dropped buffered writes"
                )
        if hasattr(self, "usage_archiver"):
            self.usage_archiver.stop()
        if hasattr(self, "update_checker"):
            self.update_checker.stop()
        if hasattr(self, "backend_catalog"):
            self.backend_catalog.stop()
        if hasattr(self, "resource_events"):
            self.resource_events.stop()
        if hasattr(self, "system_load"):
            self.system_load.stop()
        if hasattr(self, "slo_evaluator"):
            self.slo_evaluator.stop()
        if hasattr(self, "rollout_controller"):
            self.rollout_controller.stop()
        if hasattr(self, "autoscaler"):
            self.autoscaler.stop()
        for t in self._tasks:
            t.cancel()
        if self._runner:
            # open watch streams would each sit out the runner's whole
            # grace period; requests that can finish still get it
            from gpustack_tpu.routes.crud import WATCH_STREAMS

            for t in list(self._runner.app.get(WATCH_STREAMS, ())):
                t.cancel()
            await self._runner.cleanup()
        if self.db:
            self.db.close()
        self._stop.set()

    # ------------------------------------------------------------------

    async def _init_data(self) -> None:
        """Admin user, default cluster, builtin backend catalog (reference
        server/server.py:714-1141 _init_data)."""
        cfg = self.cfg
        admin = await User.first(username="admin")
        if admin is None:
            password = cfg.bootstrap_password or secrets.token_urlsafe(12)
            await User.create(
                User(
                    username="admin",
                    is_admin=True,
                    password_hash=auth_mod.hash_password(password),
                    require_password_change=not cfg.bootstrap_password,
                )
            )
            if not cfg.bootstrap_password:
                logger.warning("generated admin password: %s", password)

        cluster = await Cluster.first()
        if cluster is None:
            await Cluster.create(
                Cluster(
                    name="default",
                    registration_token_hash=auth_mod.hash_secret(
                        cfg.registration_token
                    ),
                )
            )
        else:
            # keep the persisted token authoritative across restarts
            expected = auth_mod.hash_secret(cfg.registration_token)
            if cluster.registration_token_hash != expected:
                await cluster.update(registration_token_hash=expected)

        backend = await InferenceBackend.first(name="tpu-native")
        if backend is None:
            b = BUILTIN_BACKEND.model_copy(deep=True)
            await InferenceBackend.create(b)
