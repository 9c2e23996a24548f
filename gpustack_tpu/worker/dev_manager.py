"""Worker-side dev-instance manager: holder processes + remote exec.

Reference parity: gpu_instances' operator reconciles SSH-able dev pods
(gpu_instances/controllers.py); here the worker agent reconciles
DevInstance records assigned to it — a long-lived **holder process** per
instance pins the reservation's env (``TPU_VISIBLE_CHIPS`` limited to
the scheduled chips), and commands exec beside it with the same env
through the worker's authenticated proxy (worker/server.py dev_exec).
Holder death flips the record to ERROR (the analogue of a pod crash).
"""

from __future__ import annotations

import asyncio
import logging
import os
import signal
import subprocess
import sys
from typing import Dict, Optional

from gpustack_tpu.client.client import APIError, ClientSet, update_settled
from gpustack_tpu.schemas import DevInstance, DevInstanceState
from gpustack_tpu.server.bus import Event, EventType

logger = logging.getLogger(__name__)

HOLDER_CODE = "import time\nwhile True:\n    time.sleep(3600)\n"
EXEC_OUTPUT_CAP = 256 * 1024


class RunningDev:
    def __init__(self, dev_id: int, proc: subprocess.Popen,
                 env: Dict[str, str]):
        self.dev_id = dev_id
        self.proc = proc
        self.env = env


class DevManager:
    def __init__(self, cfg, client: ClientSet, worker_id: int) -> None:
        self.cfg = cfg
        self.client = client
        self.worker_id = worker_id
        self.running: Dict[int, RunningDev] = {}
        self.log_dir = os.path.join(cfg.data_dir or ".", "dev-logs")
        os.makedirs(self.log_dir, exist_ok=True)

    def _pidfile(self, dev_id: int) -> str:
        return os.path.join(self.log_dir, f"{dev_id}.pid")

    def reap_orphans(self) -> int:
        """Kill holder processes left behind by a previous agent run —
        they outlive a hard-killed agent (own session) and would
        double-run the user's command / hold TPU device locks against
        the respawn (same workload-cleaner role as
        serve_manager.reap_orphans; pid + argv fingerprint guards
        against pid recycling)."""
        import json as _json
        import time as _time

        reaped = []
        for fname in os.listdir(self.log_dir):
            if not fname.endswith(".pid"):
                continue
            path = os.path.join(self.log_dir, fname)
            try:
                with open(path) as f:
                    rec = _json.load(f)
                pid = int(rec["pid"])
            except (OSError, ValueError, KeyError):
                os.unlink(path)
                continue
            fingerprint = rec.get("argv", [])[:2]

            def read_cmdline() -> Optional[str]:
                try:
                    with open(f"/proc/{pid}/cmdline") as f:
                        return f.read()
                except OSError:
                    return None

            cmdline = read_cmdline()
            if cmdline is None:
                os.unlink(path)       # already gone
                continue
            matches = all(tok in cmdline for tok in fingerprint)
            if not matches:
                # a freshly forked child still shows the PARENT's image
                # until exec; re-probe briefly before declaring the pid
                # recycled — shooting it then would be wrong, skipping a
                # real just-spawned holder would double-run the command
                for _ in range(20):
                    _time.sleep(0.1)
                    cmdline = read_cmdline()
                    if cmdline is None:
                        break
                    matches = all(tok in cmdline for tok in fingerprint)
                    if matches:
                        break
                if cmdline is None:
                    os.unlink(path)
                    continue
            if matches:
                logger.warning("reaping orphan dev holder pid %d", pid)
                try:
                    os.killpg(pid, signal.SIGTERM)
                except (OSError, ProcessLookupError):
                    try:
                        os.kill(pid, signal.SIGTERM)
                    except OSError:
                        pass
                reaped.append(pid)
            else:
                logger.warning(
                    "dev pidfile %s points at unrelated pid %d; skipping",
                    fname, pid,
                )
            os.unlink(path)
        from gpustack_tpu.utils.process import wait_exit_or_kill

        wait_exit_or_kill(reaped)
        return len(reaped)

    # -- event plumbing (mirrors ServeManager.handle_event) --------------

    async def handle_event(self, event: Event) -> None:
        if event.type == EventType.DELETED:
            await self.stop_instance(event.id)
            return
        data = event.data or {}
        mine = data.get("worker_id") == self.worker_id
        state = data.get("state", "")
        if not mine:
            if event.id in self.running:
                await self.stop_instance(event.id)  # reassigned elsewhere
            return
        if (
            state == DevInstanceState.SCHEDULED.value
            and event.id not in self.running
        ):
            await self.start_instance(event.id)

    async def reconcile(self) -> None:
        """DB is truth at startup: start SCHEDULED/claimed instances,
        stop local processes whose record is gone."""
        try:
            items = await self.client.list_all("dev-instances")
        except APIError as e:
            logger.warning("dev reconcile list failed: %s", e)
            return
        wanted = set()
        for raw in items:
            dev = DevInstance.model_validate(raw)
            if dev.worker_id != self.worker_id:
                continue
            if dev.state in (
                DevInstanceState.SCHEDULED,
                DevInstanceState.STARTING,
                DevInstanceState.RUNNING,
            ):
                wanted.add(dev.id)
                if dev.id not in self.running:
                    await self.start_instance(dev.id)
        for dev_id in list(self.running):
            if dev_id not in wanted:
                await self.stop_instance(dev_id)

    # -- lifecycle --------------------------------------------------------

    def _env_for(self, dev: DevInstance) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(dev.env)
        if dev.chip_indexes:
            from gpustack_tpu.worker.backends import chip_env

            env.update(chip_env(dev.chip_indexes))
        env["GPUSTACK_TPU_DEV_INSTANCE"] = str(dev.id)
        return env

    async def start_instance(self, dev_id: int) -> None:
        try:
            raw = await self.client.get("dev-instances", dev_id)
            dev = DevInstance.model_validate(raw)
        except APIError as e:
            logger.warning("dev instance %d fetch failed: %s", dev_id, e)
            return
        if dev.worker_id != self.worker_id:
            return
        await self._set_state(dev_id, DevInstanceState.STARTING)
        env = self._env_for(dev)
        argv = list(dev.command) or [
            sys.executable, "-c", HOLDER_CODE
        ]
        log_path = os.path.join(
            self.log_dir, f"{dev.name}-{dev.id}.log"
        )
        pidfile = self._pidfile(dev_id)

        def _spawn():
            # fork/exec + pidfile write are sync syscalls — keep them
            # off the event loop (one slow NFS write would stall every
            # in-flight worker request)
            import json as _json

            with open(log_path, "ab") as logf:
                proc = subprocess.Popen(
                    argv,
                    env=env,
                    stdout=logf,
                    stderr=subprocess.STDOUT,
                    start_new_session=True,
                )
            try:
                with open(pidfile, "w") as pf:
                    _json.dump({"pid": proc.pid, "argv": argv}, pf)
            except OSError:
                # a holder without a pidfile is invisible to
                # reap_orphans and would pin its chips forever if we
                # error out here — kill AND reap it (no wait = zombie)
                # before reporting failure
                proc.kill()
                proc.wait()
                raise
            return proc

        spawn = asyncio.get_running_loop().run_in_executor(None, _spawn)
        try:
            proc = await spawn
        except asyncio.CancelledError:
            # the executor thread runs to completion regardless; a
            # holder spawned after our cancellation would be registered
            # nowhere and pin its chips until the next reap_orphans —
            # kill it the moment the spawn lands
            def _kill_stranded(fut) -> None:
                try:
                    stranded = fut.result()
                except BaseException:
                    return
                stranded.kill()
                stranded.wait()
                try:
                    os.unlink(pidfile)
                except OSError:
                    pass

            spawn.add_done_callback(_kill_stranded)
            raise
        except OSError as e:
            await self._set_state(
                dev_id, DevInstanceState.ERROR,
                f"failed to start holder: {e}",
            )
            return
        self.running[dev_id] = RunningDev(dev_id, proc, env)
        await self._set_state(
            dev_id, DevInstanceState.RUNNING, pid=proc.pid
        )
        asyncio.create_task(
            self._monitor(dev_id, proc), name=f"dev-mon-{dev_id}"
        )
        logger.info(
            "dev instance %s running (pid %d, chips %s)",
            dev.name, proc.pid, dev.chip_indexes,
        )

    async def _monitor(self, dev_id: int, proc: subprocess.Popen) -> None:
        rc = await asyncio.get_running_loop().run_in_executor(
            None, proc.wait
        )
        if self.running.get(dev_id) is None or (
            self.running[dev_id].proc is not proc
        ):
            return  # stopped deliberately
        self.running.pop(dev_id, None)
        try:
            os.unlink(self._pidfile(dev_id))
        except OSError:
            pass
        await self._set_state(
            dev_id, DevInstanceState.ERROR,
            f"holder process exited rc={rc}",
        )

    async def stop_instance(self, dev_id: int) -> None:
        run = self.running.pop(dev_id, None)
        if run is None:
            return
        try:
            os.killpg(run.proc.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: run.proc.wait(timeout=5)
            )
        except subprocess.TimeoutExpired:
            try:
                os.killpg(run.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        try:
            os.unlink(self._pidfile(dev_id))
        except OSError:
            pass
        logger.info("dev instance %d stopped", dev_id)

    async def stop_all(self) -> None:
        for dev_id in list(self.running):
            await self.stop_instance(dev_id)

    # -- exec -------------------------------------------------------------

    async def exec(self, dev_id: int, argv: list,
                   timeout: float = 60.0) -> dict:
        """Run a command in the instance's environment; capped output."""
        run = self.running.get(dev_id)
        if run is None:
            raise KeyError(f"dev instance {dev_id} not running here")

        def go():
            try:
                p = subprocess.run(
                    argv,
                    env=run.env,
                    capture_output=True,
                    timeout=timeout,
                )
                return {
                    "rc": p.returncode,
                    "stdout": p.stdout[-EXEC_OUTPUT_CAP:].decode(
                        errors="replace"
                    ),
                    "stderr": p.stderr[-EXEC_OUTPUT_CAP:].decode(
                        errors="replace"
                    ),
                }
            except subprocess.TimeoutExpired:
                return {"rc": -1, "stdout": "", "stderr": "exec timeout"}
            except OSError as e:
                return {"rc": -1, "stdout": "", "stderr": str(e)}

        return await asyncio.get_running_loop().run_in_executor(None, go)

    # -- record updates ----------------------------------------------------

    async def _set_state(
        self, dev_id: int, state: DevInstanceState,
        message: str = "", pid: Optional[int] = None,
    ) -> None:
        fields = {"state": state.value, "state_message": message}
        if pid is not None:
            fields["pid"] = pid
        try:
            # settled: a one-shot owner report must survive the crud
            # layer's 409 when an unrelated writer touched the row
            # between the server's validation and write
            await update_settled(
                self.client, "dev-instances", dev_id, fields
            )
        except APIError as e:
            logger.warning(
                "dev instance %d state update failed: %s", dev_id, e
            )
