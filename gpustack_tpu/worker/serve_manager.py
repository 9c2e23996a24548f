"""ServeManager: model-instance lifecycle on this worker.

Reference parity (gpustack/worker/serve_manager.py:89): watch instance
events → start engine processes for instances scheduled here → drive the
state machine (SCHEDULED → STARTING → RUNNING), health-probe, persist
logs, restart with backoff on crash, reap orphans.
"""

from __future__ import annotations

import asyncio
import logging
import os
import socket
import time
from typing import Callable, Dict, Optional, Set

import aiohttp

from gpustack_tpu.client.client import (
    APIError,
    NETWORK_ERRORS,
    ClientSet,
)
from gpustack_tpu.config import Config
from gpustack_tpu.observability.tracing import (
    TRACEPARENT_ENV,
    RequestTrace,
    TraceContext,
    make_trace_id,
)
from gpustack_tpu.schemas import Model, ModelInstance, ModelInstanceState
from gpustack_tpu.schemas.inference_backends import InferenceBackend
from gpustack_tpu.server.bus import Event, EventType
from gpustack_tpu.worker.backends import build_command, health_path_for

logger = logging.getLogger(__name__)

HEALTH_TIMEOUT = 600.0        # engine startup budget (compile can be slow)
HEALTH_INTERVAL = 2.0
MAX_RESTARTS = 5


class RunningInstance:
    def __init__(self, instance_id: int, port: int):
        self.instance_id = instance_id
        self.port = port
        self.process: Optional[asyncio.subprocess.Process] = None
        self.monitor_task: Optional[asyncio.Task] = None
        self.restarts = 0
        self.stopping = False
        self.draining = False
        # the engine answered its health check (set by the monitor):
        # only then may anyone report this instance RUNNING
        self.ready = False
        self.is_leader = True
        # external engines declare their own readiness endpoint (vLLM
        # uses /health) via BackendVersionConfig.health_path
        self.health_path = "/healthz"
        # served model name: labels this instance's scraped engine
        # metrics on the worker exporter (worker/server.py)
        self.model_name = ""
        # the span ``instance_start`` of the start in progress (phases
        # ``spawn``, ``health_wait``); the engine's ``engine_start`` is
        # its child. None once sealed into the worker's trace store.
        self.start_trace: Optional[RequestTrace] = None


class ServeManager:
    def __init__(self, cfg: Config, client: ClientSet, worker_id: int):
        self.cfg = cfg
        self.client = client
        self.worker_id = worker_id
        self.running: Dict[int, RunningInstance] = {}
        self.log_dir = os.path.join(cfg.data_dir, "instance-logs")
        os.makedirs(self.log_dir, exist_ok=True)
        from gpustack_tpu.worker.model_file_manager import ModelFileManager

        self.file_manager = ModelFileManager(cfg, client, worker_id)
        # backend catalog cache, kept warm by the agent's
        # inference-backends watch (reference InferenceBackendManager
        # caches via watch instead of fetching per start)
        self.backends_cache: Dict[str, InferenceBackend] = {}
        # graceful drain: the worker HTTP server's per-instance in-flight
        # count (WorkerServer.inflight_count), wired by the agent; stop
        # waits for it to reach zero (bounded) before SIGTERM
        self.inflight_source: Optional[Callable[[int], int]] = None
        self.drains_total = 0
        self.drain_seconds_total = 0.0
        # drains in progress: stop_instance pops self.running at entry,
        # so reconcile's "DRAINING row with no local engine" orphan
        # check needs this to not mistake an ACTIVE drain (engine still
        # serving its last streams) for an agent-restart leftover
        self._draining_ids: Set[int] = set()
        self._rotate_task: Optional[asyncio.Task] = None
        # strong refs to fire-and-forget stop/drain tasks: asyncio only
        # weak-refs scheduled tasks, and a GC'd drain would strand a
        # DRAINING row holding its chip claim forever
        self._bg_tasks: Set[asyncio.Task] = set()
        # reconcile is no longer single-caller (startup + watch RESYNC
        # + the heartbeat-recovery task): two interleaved runs would
        # race the trailing orphan-stop sweep against the other's
        # spawn_start and kill a freshly spawned engine
        self._reconcile_lock = asyncio.Lock()

    def _track(self, task: asyncio.Task) -> asyncio.Task:
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    def handle_backend_event(self, event: Event) -> None:
        if event.type == EventType.RESYNC:
            self.backends_cache.clear()   # fall back to per-start fetch
            return
        data = event.data or {}
        name = data.get("name", "")
        if not name:
            return
        if event.type == EventType.DELETED:
            self.backends_cache.pop(name, None)
        else:
            try:
                self.backends_cache[name] = (
                    InferenceBackend.model_validate(data)
                )
            except ValueError:
                logger.warning("bad backend payload for %r", name)

    # ---- event handling -------------------------------------------------

    def _my_role(self, data: dict):
        """(process_index, chip_indexes) when this worker participates in
        the instance — 0 for the leader, >0 for a subordinate host of a
        multi-host replica (reference serve_manager.py:1306-1320 follower
        startup) — else None."""
        if data.get("worker_id") == self.worker_id:
            return 0, list(data.get("chip_indexes") or [])
        for sub in data.get("subordinate_workers") or []:
            if sub.get("worker_id") == self.worker_id:
                return (
                    int(sub.get("process_index", 1)),
                    list(sub.get("chip_indexes") or []),
                )
        return None

    async def handle_event(self, event: Event) -> None:
        if event.type == EventType.RESYNC:
            await self.reconcile()
            return
        if event.type == EventType.DELETED:
            # hard removal: the row — and its CHIP CLAIM — is already
            # gone, so the scheduler may place a replacement onto these
            # chips immediately; draining here would make the old
            # engine contend with the new one for the device (graceful
            # paths go through the DRAINING state, which holds the
            # claim until the engine has stopped). AWAITED, not
            # backgrounded: a replacement's SCHEDULED event must not be
            # processed until this engine has released the chips.
            await self.stop_instance(event.id, drain=False)
            return
        data = event.data or {}
        role = self._my_role(data)
        if role is None:
            # instance moved away from us (reschedule): the claim now
            # points elsewhere — same fast-stop reasoning as DELETED
            if event.id in self.running:
                await self.stop_instance(event.id, drain=False)
            return
        state = data.get("state")
        if (
            state == ModelInstanceState.SCHEDULED.value
            and event.id not in self.running
        ):
            self.spawn_start(event.id)
        elif state == ModelInstanceState.DRAINING.value:
            # server-requested graceful retirement (rolling update /
            # rebalance): finish in-flight requests, SIGTERM, then
            # delete the row so replica sync creates a replacement.
            # LEADER-ONLY: data-plane traffic flows through the leader's
            # reverse proxy, so a subordinate's in-flight count is
            # always zero — it would SIGTERM its engine shard instantly,
            # collapsing the distributed engine mid-generation. The
            # subordinates stop when the leader's retirement DELETEs
            # the row.
            run = self.running.get(event.id)
            if (
                role[0] == 0
                and run is not None
                and not run.stopping
                and not run.draining
            ):
                run.draining = True
                self._track(asyncio.create_task(
                    self._drain_and_retire(event.id),
                    name=f"drain-{event.id}",
                ))

    def spawn_start(self, instance_id: int) -> None:
        """Run start_instance as its own task: downloads can take minutes
        and must not block the instance-event loop (other instances'
        stop/start events keep flowing)."""
        if instance_id in self.running:
            return
        run = RunningInstance(instance_id, 0)
        self.running[instance_id] = run

        async def go():
            try:
                await self.start_instance(instance_id)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception(
                    "start_instance %d failed", instance_id
                )
            finally:
                # start_instance replaces the placeholder on success;
                # a placeholder without a process means startup failed
                current = self.running.get(instance_id)
                if current is run and run.process is None:
                    self.running.pop(instance_id, None)

        run.monitor_task = asyncio.create_task(
            go(), name=f"start-{instance_id}"
        )

    async def reconcile(self) -> None:
        """Converge local processes with the server's view (orphan reaping —
        reference worker/workload_cleaner.py role). Serialized: the
        orphan-stop sweep at the end acts on a list snapshot and must
        not interleave with another reconcile's spawns."""
        async with self._reconcile_lock:
            await self._reconcile_locked()

    async def _reconcile_locked(self) -> None:
        try:
            items = await self.client.list_all("model-instances")
        except NETWORK_ERRORS:
            # transport errors too: the recovery path runs reconcile
            # precisely during flaky-network windows, and the startup
            # call has no try/except above it — a ClientConnectorError
            # escaping here would kill the agent at boot
            logger.exception("reconcile list failed")
            return
        mine: Set[int] = set()
        for item in items:
            if self._my_role(item) is None:
                continue
            inst = ModelInstance.model_validate(item)
            mine.add(inst.id)
            role = self._my_role(item)
            is_leader = role is not None and role[0] == 0
            if (
                inst.state == ModelInstanceState.SCHEDULED
                and inst.id not in self.running
            ):
                self.spawn_start(inst.id)
            elif (
                is_leader
                and inst.state
                in (
                    ModelInstanceState.STARTING,
                    ModelInstanceState.RUNNING,
                    ModelInstanceState.DOWNLOADING,
                    # we are reachable again (this reconcile reached
                    # the server) but the engine is gone — e.g. a
                    # drain interrupted by the partition that marked
                    # us unreachable; re-drive to restore capacity
                    ModelInstanceState.UNREACHABLE,
                )
                and inst.id not in self.running
                and inst.id not in self._draining_ids
            ):
                # DB says alive but no local process (agent restarted, or
                # the engine was reaped as an orphan): re-drive through the
                # state machine (reference sync_model_instances_state,
                # serve_manager.py:244). Leader-only: a follower losing its
                # process surfaces as the leader engine's collective
                # failure, and the leader's crash-restart re-SCHEDULEs the
                # whole replica (followers then respawn on that event).
                logger.warning(
                    "instance %s is %s with no local engine; restarting",
                    inst.name, inst.state.value,
                )
                await self._set_state(
                    inst.id, ModelInstanceState.SCHEDULED,
                    "engine process lost; restarting",
                )
                self.spawn_start(inst.id)
            elif (
                is_leader
                and inst.state == ModelInstanceState.UNREACHABLE
                and inst.id in self.running
                and inst.id not in self._draining_ids
            ):
                run = self.running[inst.id]
                if run.stopping or run.draining:
                    pass  # a stop/drain already owns this engine
                elif run.process is None:
                    # mid-start PLACEHOLDER: spawn_start registers the
                    # run before start_instance fills in the process
                    # (downloads take minutes). An in-flight start task
                    # owns this id — its RUNNING report un-parks the
                    # row when it lands; respawning here would
                    # double-spawn the engine and leak the loser
                    pass
                elif not run.ready and run.process.returncode is None:
                    # still starting (an 8B engine compiles for minutes;
                    # seen on the chip, PR 23): the monitor's health wait
                    # owns this id and its RUNNING report un-parks the
                    # row — reporting RUNNING here would route requests
                    # to an engine that does not listen yet
                    pass
                elif run.process.returncode is None:
                    # we are reachable again AND the engine survived
                    # the partition: resume serving in place — a
                    # restart here would throw away a healthy engine
                    # and its in-flight work (declared transition
                    # UNREACHABLE -> RUNNING)
                    logger.warning(
                        "instance %s survived the partition; resuming "
                        "as running", inst.name,
                    )
                    await self._set_state(
                        inst.id, ModelInstanceState.RUNNING,
                        "engine survived worker partition",
                    )
                else:
                    # the tracked engine EXITED during the partition
                    # and its crash report never reached the server
                    # (the monitor's state write failed with the
                    # network): drop the stale handle and re-drive, or
                    # the row sits UNREACHABLE forever — the rescuer
                    # skips it (worker READY) and the orphan sweep
                    # skips it (id is in mine)
                    logger.warning(
                        "instance %s: engine died during the "
                        "partition; re-driving", inst.name,
                    )
                    self.running.pop(inst.id, None)
                    await self._set_state(
                        inst.id, ModelInstanceState.SCHEDULED,
                        "engine died during partition; restarting",
                    )
                    self.spawn_start(inst.id)
            elif inst.state == ModelInstanceState.DRAINING and is_leader:
                run = self.running.get(inst.id)
                if run is None and inst.id not in self._draining_ids:
                    # drain orphaned by an agent restart: the engine is
                    # gone; retire the row so replica sync replaces it
                    # (an ACTIVE drain also has run popped, but its id
                    # sits in _draining_ids — deleting under it would
                    # free the chip claim while the engine still serves)
                    try:
                        await self.client.delete(
                            "model-instances", inst.id
                        )
                    except APIError:
                        logger.exception(
                            "failed to retire drained instance %d",
                            inst.id,
                        )
                elif (
                    run is not None
                    and not run.stopping
                    and not run.draining
                ):
                    run.draining = True
                    self._track(asyncio.create_task(
                        self._drain_and_retire(inst.id),
                        name=f"drain-{inst.id}",
                    ))
        for iid in list(self.running):
            if iid not in mine:
                await self.stop_instance(iid, drain=False)

    # ---- lifecycle ------------------------------------------------------

    async def start_instance(self, instance_id: int) -> None:
        try:
            raw = await self.client.get("model-instances", instance_id)
            inst = ModelInstance.model_validate(raw)
            model = Model.model_validate(
                await self.client.get("models", inst.model_id)
            )
        except APIError as e:
            logger.warning("cannot fetch instance %d: %s", instance_id, e)
            return
        role = self._my_role(raw)
        if role is None:
            return
        process_index, my_chips = role
        is_leader = process_index == 0

        # resolve weight files (download into the cache when hf-sourced;
        # every participating host needs the files)
        if model.huggingface_repo_id:
            if is_leader:
                await self._set_state(
                    instance_id, ModelInstanceState.DOWNLOADING, ""
                )
            try:
                resolved = await self.file_manager.ensure_local(model)
            except Exception as e:
                if is_leader:
                    await self._set_state(
                        instance_id, ModelInstanceState.ERROR,
                        f"model download failed: {e}",
                    )
                return
            model = model.model_copy(update={"local_path": resolved})

        backend = None
        if model.backend not in ("", "tpu-native"):
            backend = self.backends_cache.get(model.backend)
            if backend is None:   # cache cold (startup/RESYNC)
                backends = await self.client.list(
                    "inference-backends", name=model.backend
                )
                backend = (
                    InferenceBackend.model_validate(backends[0])
                    if backends else None
                )
                if backend is not None:
                    self.backends_cache[model.backend] = backend
        own_coord: tuple = ()
        if inst.coordinator_address:
            cp = int(inst.coordinator_address.rsplit(":", 1)[1])
            own_coord = (cp, cp + 1)
        port = self._allocate_port(exclude=own_coord)
        try:
            argv, extra_env = build_command(
                model, inst, port, backend,
                force_platform=self.cfg.force_platform,
                process_index=process_index,
                chip_indexes=my_chips,
                cluster_secret=self.cfg.registration_token,
            )
        except ValueError as e:
            if is_leader:
                await self._set_state(
                    instance_id, ModelInstanceState.ERROR, str(e)
                )
            return
        # one trace a replica start: this span from the command built to
        # the engine's first 200, the engine process's own as its child
        model_name = inst.model_name or model.name
        trace = RequestTrace(
            TraceContext(make_trace_id()), "worker", "instance_start",
            model=model_name,
        )
        trace.begin("spawn")

        # multi-host leader: fence the jax.distributed coordinator port
        # pair (coordinator + command channel, engine/multihost.py)
        # before spawning — the scheduler avoids DB-known collisions but
        # only the leader host can see ports taken by unrelated
        # processes (reference port-band probing,
        # serve_manager.py:1456-1508)
        if is_leader and own_coord:
            for probe_port in own_coord:
                with socket.socket(
                    socket.AF_INET, socket.SOCK_STREAM
                ) as probe:
                    # SO_REUSEADDR: TIME_WAIT remnants of a crashed
                    # leader's coordinator must not fail the restart path
                    probe.setsockopt(
                        socket.SOL_SOCKET, socket.SO_REUSEADDR, 1
                    )
                    try:
                        probe.bind(("0.0.0.0", probe_port))
                    except OSError as e:
                        # a busy coordinator port is usually TRANSIENT
                        # (the previous placement's engine still
                        # releasing) — retry with backoff instead of
                        # parking the instance in a terminal ERROR
                        # nobody reschedules. The attempt count lives on
                        # the INSTANCE ROW: the event path recreates the
                        # RunningInstance per attempt, so a local
                        # counter would reset every time.
                        attempts = inst.restarts + 1
                        if attempts > MAX_RESTARTS:
                            await self._set_state(
                                instance_id,
                                ModelInstanceState.ERROR,
                                f"coordinator port {probe_port} "
                                f"unavailable after "
                                f"{MAX_RESTARTS} retries: {e}",
                            )
                            return
                        delay = min(30.0, 2.0 ** attempts)
                        logger.warning(
                            "instance %d: coordinator port %d busy "
                            "(%s); retry %d in %.0fs",
                            instance_id, probe_port, e, attempts,
                            delay,
                        )
                        await self._set_state(
                            instance_id,
                            ModelInstanceState.SCHEDULED,
                            f"coordinator port {probe_port} busy; "
                            f"retry {attempts}",
                            restarts=attempts,
                        )

                        async def _retry(iid=instance_id):
                            # spawn_start wraps start_instance with
                            # the same exception handling + placeholder
                            # cleanup as the event path
                            await asyncio.sleep(delay)
                            self.spawn_start(iid)

                        asyncio.create_task(
                            _retry(), name=f"coord-retry-{instance_id}"
                        )
                        return

        run = self.running.get(instance_id) or RunningInstance(
            instance_id, port
        )
        run.port = port
        run.is_leader = is_leader
        run.health_path = health_path_for(model, backend)
        run.model_name = model_name
        run.start_trace = trace
        self.running[instance_id] = run

        env = dict(os.environ)
        env.update(extra_env)
        env[TRACEPARENT_ENV] = trace.ctx.traceparent()
        # the engine subprocess must be able to import gpustack_tpu even
        # when the package isn't installed (repo checkout)
        import gpustack_tpu

        pkg_root = os.path.dirname(
            os.path.dirname(os.path.abspath(gpustack_tpu.__file__))
        )
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        log_path = os.path.join(
            self.log_dir, f"{inst.name}-{instance_id}.log"
        )
        logger.info(
            "starting instance %s: %s (log %s)",
            inst.name, " ".join(argv), log_path,
        )
        log_file = open(log_path, "ab")
        try:
            run.process = await asyncio.create_subprocess_exec(
                *argv, env=env, stdout=log_file, stderr=log_file,
                start_new_session=True,
            )
            import json as _json

            pid_payload = _json.dumps(
                # argv fingerprint so the reaper can verify the pid
                # wasn't recycled to an unrelated process
                {"pid": run.process.pid, "argv": argv[:4]}
            )

            def _write_pidfile() -> None:
                with open(self._pidfile(instance_id), "w") as pf:
                    pf.write(pid_payload)

            await asyncio.to_thread(_write_pidfile)
        except OSError as e:
            log_file.close()
            self._seal_start(run, 500)
            if is_leader:
                await self._set_state(
                    instance_id, ModelInstanceState.ERROR,
                    f"failed to spawn engine: {e}",
                )
            return
        finally:
            if not log_file.closed:
                log_file.close()

        trace.end("spawn")
        # followers report nothing: the leader's health probe is the
        # instance's state (the engine blocks until all hosts rendezvous)
        if is_leader:
            trace.begin("health_wait")
            await self._set_state(
                instance_id, ModelInstanceState.STARTING, "",
                port=port, pid=run.process.pid,
            )
        run.monitor_task = asyncio.create_task(
            self._monitor(run, model), name=f"monitor-{instance_id}"
        )

    def _pidfile(self, instance_id: int) -> str:
        return os.path.join(self.log_dir, f"{instance_id}.pid")

    def reap_orphans(self) -> int:
        """Kill engine processes left behind by a previous agent run (the
        reference's workload cleaner role, worker/workload_cleaner.py):
        engines outlive a hard-killed agent because they run in their own
        session; pidfiles (pid + argv fingerprint) identify them across
        restarts. Blocks briefly until reaped pids exit so respawned
        engines don't race the old ones for the TPU device lock."""
        import json as _json

        reaped_pids = []
        for fname in os.listdir(self.log_dir):
            if not fname.endswith(".pid"):
                continue
            path = os.path.join(self.log_dir, fname)
            try:
                with open(path) as f:
                    raw = f.read().strip()
                rec = (
                    _json.loads(raw)
                    if raw.startswith("{")
                    else {"pid": int(raw), "argv": []}
                )
                pid = int(rec["pid"])
            except (OSError, ValueError, KeyError):
                os.unlink(path)
                continue
            try:
                with open(f"/proc/{pid}/cmdline") as f:
                    cmdline = f.read()
            except OSError:
                os.unlink(path)       # process already gone
                continue
            fingerprint = rec.get("argv") or ["gpustack_tpu", "api_server"]
            if all(tok in cmdline for tok in fingerprint):
                logger.warning("reaping orphan engine pid %d", pid)
                try:
                    os.kill(pid, 15)
                    reaped_pids.append(pid)
                except OSError:
                    pass
                os.unlink(path)
            else:
                # pid recycled to an unrelated process: never kill it, and
                # keep the file out of future scans
                logger.warning(
                    "pidfile %s points at unrelated pid %d; skipping",
                    fname, pid,
                )
                os.unlink(path)
        from gpustack_tpu.utils.process import wait_exit_or_kill

        wait_exit_or_kill(reaped_pids)
        return len(reaped_pids)

    async def stop_instance(
        self, instance_id: int, *, drain: bool = True
    ) -> None:
        run = self.running.pop(instance_id, None)
        if run is not None:
            run.stopping = True
            if run.monitor_task:
                run.monitor_task.cancel()
            if run.process and run.process.returncode is None:
                if drain:
                    await self._drain(run)
                logger.info("terminating instance %d", instance_id)
                try:
                    run.process.terminate()
                    try:
                        await asyncio.wait_for(run.process.wait(), 10)
                    except asyncio.TimeoutError:
                        run.process.kill()
                        await run.process.wait()
                except ProcessLookupError:
                    pass
        # pidfile LAST: while the drain waits (up to drain_timeout) the
        # engine is still alive, and an agent crash in that window must
        # leave the pidfile for reap_orphans to find the survivor
        try:
            os.unlink(self._pidfile(instance_id))
        except OSError:
            pass

    async def _drain(self, run: RunningInstance) -> None:
        """Wait (bounded by ``drain_timeout``) for the worker reverse
        proxy's in-flight count for this instance to reach zero before
        the SIGTERM — a scheduler-driven rebalance or rolling update
        must not kill a live generation mid-stream. The DRAINING state
        makes the server's picker stop routing new requests here while
        the wait runs."""
        if self.inflight_source is None:
            return
        timeout = float(getattr(self.cfg, "drain_timeout", 30.0))
        if timeout <= 0:
            return
        inflight = self.inflight_source(run.instance_id)
        if inflight <= 0:
            return
        self.drains_total += 1
        if run.is_leader:
            # best-effort: on a DELETE-triggered stop the row is already
            # gone and this update just logs a warning
            await self._set_state(
                run.instance_id, ModelInstanceState.DRAINING,
                f"draining {inflight} in-flight request(s)",
            )
        t0 = time.monotonic()
        deadline = t0 + timeout
        while time.monotonic() < deadline:
            if self.inflight_source(run.instance_id) <= 0:
                break
            if run.process is None or run.process.returncode is not None:
                break  # engine died on its own; nothing left to drain
            await asyncio.sleep(0.2)
        waited = time.monotonic() - t0
        self.drain_seconds_total += waited
        remaining = self.inflight_source(run.instance_id)
        if remaining > 0:
            logger.warning(
                "instance %d drain timed out after %.1fs with %d "
                "request(s) still in flight; terminating anyway",
                run.instance_id, waited, remaining,
            )
        else:
            logger.info(
                "instance %d drained in %.1fs", run.instance_id, waited
            )

    async def _drain_and_retire(self, instance_id: int) -> None:
        """DRAINING event path: graceful stop, then delete the instance
        row so the ModelController's replica sync creates a fresh
        replacement (the rolling-update contract)."""
        self._draining_ids.add(instance_id)
        try:
            try:
                await self.stop_instance(instance_id)
            except Exception:
                logger.exception(
                    "drain of instance %d failed", instance_id
                )
            try:
                await self.client.delete("model-instances", instance_id)
            except APIError as e:
                logger.warning(
                    "failed to retire drained instance %d: %s",
                    instance_id, e,
                )
        finally:
            self._draining_ids.discard(instance_id)

    async def stop_all(self) -> None:
        if self._rotate_task is not None:
            self._rotate_task.cancel()
            self._rotate_task = None
        for iid in list(self.running):
            # agent shutdown: fast teardown — draining every instance
            # serially could hold SIGTERM handling for minutes
            await self.stop_instance(iid, drain=False)

    # ---- log rotation ---------------------------------------------------

    def start_log_rotation(self, interval: float = 10.0) -> None:
        """Periodic size-capped rotation of instance log files
        (reference rotates per-instance logs, serve_manager.py:902-1289;
        without it a long-lived chatty engine grows one file unbounded)."""
        if self._rotate_task is None and float(
            getattr(self.cfg, "instance_log_max_bytes", 0)
        ) > 0:
            self._rotate_task = asyncio.create_task(
                self._rotate_loop(interval), name="log-rotation"
            )

    async def _rotate_loop(self, interval: float) -> None:
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(interval)
            try:
                # executor: copying a >=64 MiB log synchronously would
                # stall every relay, /healthz, and the drain poll
                await loop.run_in_executor(None, self.rotate_logs_once)
            except Exception:
                logger.exception("instance log rotation failed")

    def rotate_logs_once(self) -> int:
        """Copy-truncate rotation: ``x.log`` over the cap is copied to
        ``x.log.1`` (shifting .1→.2 … up to ``instance_log_keep``, oldest
        dropped) and the live file truncated to zero. Copy-truncate, not
        rename: the engine holds an O_APPEND fd ("ab"), so truncation is
        safe — its next write lands at offset 0 — while a rename would
        carry the fd into the rotated file and the live path would stop
        growing. Bytes appended between the copy and the truncate are
        lost; the window is one copyfile of a capped file.

        Follow-streaming (worker/server.py instance_logs) survives: its
        poll loop treats a shrinking file as truncation and restarts
        from offset zero."""
        import shutil

        cap = int(getattr(self.cfg, "instance_log_max_bytes", 0))
        keep = max(1, int(getattr(self.cfg, "instance_log_keep", 3)))
        if cap <= 0:
            return 0
        rotated = 0
        for fname in os.listdir(self.log_dir):
            if not fname.endswith(".log"):
                continue
            path = os.path.join(self.log_dir, fname)
            try:
                if os.path.getsize(path) <= cap:
                    continue
            except OSError:
                continue
            try:
                oldest = f"{path}.{keep}"
                if os.path.exists(oldest):
                    os.unlink(oldest)
                for i in range(keep - 1, 0, -1):
                    src = f"{path}.{i}"
                    if os.path.exists(src):
                        os.replace(src, f"{path}.{i + 1}")
                shutil.copyfile(path, f"{path}.1")
                os.truncate(path, 0)
                rotated += 1
                logger.info("rotated instance log %s", fname)
            except OSError:
                logger.exception("failed to rotate %s", fname)
        return rotated

    # ---- monitoring -----------------------------------------------------

    @staticmethod
    def _seal_start(run: RunningInstance, status: int) -> None:
        """Seal the start's span into the worker's trace store (``GET
        /v2/debug/traces?component=worker``); no request, so not in the
        request-duration histogram."""
        trace, run.start_trace = run.start_trace, None
        if trace is not None:
            if status == 200:
                trace.end("health_wait")     # _wait_healthy's 200
            trace.finish(
                status=status, observe=False,
                instance_id=run.instance_id, model=run.model_name,
            )

    async def _monitor(self, run: RunningInstance, model: Model) -> None:
        if run.is_leader:
            healthy = await self._wait_healthy(run)
            self._seal_start(run, 200 if healthy else 503)
            if run.stopping:
                return
            if healthy:
                run.ready = True
                await self._set_state(
                    run.instance_id, ModelInstanceState.RUNNING, ""
                )
            else:
                if run.process and run.process.returncode is None:
                    run.process.kill()
                await self._crash(run, model, "engine failed health check")
                return
        self._seal_start(run, 200)      # a follower's: ``spawn`` alone
        # process exit watch
        assert run.process is not None
        code = await run.process.wait()
        if run.stopping:
            return
        await self._crash(run, model, f"engine exited with code {code}")

    async def _wait_healthy(self, run: RunningInstance) -> bool:
        deadline = time.monotonic() + HEALTH_TIMEOUT
        url = f"http://127.0.0.1:{run.port}{run.health_path}"
        async with aiohttp.ClientSession() as session:
            while time.monotonic() < deadline and not run.stopping:
                if run.process and run.process.returncode is not None:
                    return False
                try:
                    async with session.get(
                        url, timeout=aiohttp.ClientTimeout(total=3)
                    ) as resp:
                        if resp.status == 200:
                            return True
                except aiohttp.ClientError:
                    pass
                except asyncio.TimeoutError:
                    pass
                await asyncio.sleep(HEALTH_INTERVAL)
        return False

    async def _crash(
        self, run: RunningInstance, model: Model, reason: str
    ) -> None:
        logger.warning("instance %d: %s", run.instance_id, reason)
        if run.stopping or self.running.get(run.instance_id) is not run:
            # identity check BEFORE the ERROR write, not just after the
            # backoff: the recovery reconcile may already have popped
            # this dead run and re-driven the instance — a late ERROR
            # write would knock the fresh row into a state nobody on a
            # healthy worker re-drives
            return
        restartable = (
            model.restart_on_error and run.restarts < MAX_RESTARTS
        )
        if run.is_leader:
            await self._set_state(
                run.instance_id, ModelInstanceState.ERROR, reason
            )
        if not restartable:
            self.running.pop(run.instance_id, None)
            return
        run.restarts += 1
        backoff = min(60.0, 2.0 ** run.restarts)
        logger.info(
            "restarting instance %d in %.0fs (attempt %d/%d)",
            run.instance_id, backoff, run.restarts, MAX_RESTARTS,
        )
        await asyncio.sleep(backoff)
        if run.stopping or self.running.get(run.instance_id) is not run:
            # IDENTITY, not membership: the recovery reconcile may have
            # popped this dead run and spawned a replacement under the
            # same id while we slept — restarting on top of it would
            # double-spawn the engine and knock the fresh row backwards
            return
        if run.is_leader:
            await self._set_state(
                run.instance_id, ModelInstanceState.SCHEDULED,
                f"restart {run.restarts}",
                restarts=run.restarts,
            )
        restarts = run.restarts
        await self.start_instance(run.instance_id)
        if run.instance_id in self.running:
            self.running[run.instance_id].restarts = restarts

    # ---- helpers --------------------------------------------------------

    async def _set_state(
        self,
        instance_id: int,
        state: ModelInstanceState,
        message: str,
        **extra,
    ) -> None:
        fields = {"state": state.value, "state_message": message, **extra}
        if state == ModelInstanceState.ERROR:
            fields["last_error"] = message
        for attempt in range(3):
            try:
                await self.client.update(
                    "model-instances", instance_id, fields
                )
                return
            except APIError as e:
                # the server 409s when the row moved between its
                # validation and write (routes/crud.py) — a one-shot
                # lifecycle report (STARTING->RUNNING racing a rescuer
                # blip) must re-read and re-decide, not drop the
                # transition and leave the row wedged until a rollout
                # deadline reaps a healthy canary
                retriable = (
                    e.status == 409
                    and "changed concurrently" in e.message
                    and attempt < 2
                )
                if not retriable:
                    logger.warning(
                        "failed to update instance %d state: %s",
                        instance_id, e,
                    )
                    return
                try:
                    current = await self.client.get(
                        "model-instances", instance_id
                    )
                except NETWORK_ERRORS:
                    return  # row gone/unreadable; reconcile re-drives
                if current.get("state") == state.value:
                    return  # another writer already landed it
            except NETWORK_ERRORS as e:
                # network errors too, not just HTTP-level APIError: a
                # state write failing mid-partition must degrade to a
                # warning — an exception here propagates into the
                # monitor/crash tasks and kills the restart machinery
                # with the engine down
                logger.warning(
                    "failed to update instance %d state: %s",
                    instance_id, e,
                )
                return

    def _allocate_port(self, exclude=()) -> int:
        """Free engine port from the configured band.

        ``exclude``: ports this instance must never take — its own
        coordinator pair (the engine binding the port its own
        jax.distributed coordinator needs starts fine once, then every
        restart collides). When the band overlaps the scheduler's
        coordinator range, ports OUTSIDE that range are preferred, but
        overlap alone never exhausts the band."""
        from gpustack_tpu.scheduler.scheduler import (
            COORDINATOR_PORT_BASE,
            COORDINATOR_PORT_RANGE,
        )

        used = {r.port for r in self.running.values()} | set(exclude)
        base = self.cfg.engine_port_base
        coord_band = range(
            COORDINATOR_PORT_BASE,
            COORDINATOR_PORT_BASE + COORDINATOR_PORT_RANGE,
        )

        def bindable(port: int) -> bool:
            with socket.socket(
                socket.AF_INET, socket.SOCK_STREAM
            ) as s:
                try:
                    s.bind(("127.0.0.1", port))
                except OSError:
                    return False
            return True

        in_band_candidates = []
        for offset in range(self.cfg.engine_port_range):
            port = base + offset
            if port in used:
                continue
            if port in coord_band:
                in_band_candidates.append(port)
                continue
            if bindable(port):
                return port
        for port in in_band_candidates:
            if bindable(port):
                logger.warning(
                    "engine port %d falls inside the scheduler's "
                    "coordinator band (%d..%d): engine_port_base "
                    "overlaps it and no out-of-band port was free — a "
                    "future multi-host placement assigned this port "
                    "as its coordinator will have to wait for this "
                    "engine to stop; reconfigure engine_port_base to "
                    "a disjoint range",
                    port, COORDINATOR_PORT_BASE,
                    COORDINATOR_PORT_BASE + COORDINATOR_PORT_RANGE,
                )
                return port
        raise RuntimeError(
            "no free engine ports (band "
            f"{base}..{base + self.cfg.engine_port_range})"
        )
