"""Two-process multi-host serving e2e.

The full multi-host path the reference drives through Ray
(worker/backends/vllm.py:258-328 multinode bootstrap): two worker agent
PROCESSES register against one server, the scheduler places a single
replica across both hosts (leader + subordinate), each serve manager
spawns an engine process, the engines rendezvous over jax.distributed on
localhost, the leader broadcasts ops to the follower
(engine/multihost.py), and a chat completion flows through the server
proxy. Then the follower host dies (SIGKILL agent + engine) and the
control plane must tear the replica down and create a replacement
instance for rescheduling (server/controllers.py subordinate-loss path).

CPU-hermetic: v4_8_host0/1 fixtures (4 chips each, one ici_domain);
engines run on 4 virtual CPU devices per process.
"""

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

import aiohttp
import pytest

from gpustack_tpu.testing.ports import engine_port_base

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
FIXTURES = os.path.join(REPO, "tests", "fixtures", "workers")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_worker(server_port, data_dir, fixture, name, host):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["GPUSTACK_TPU_HEARTBEAT_INTERVAL"] = "1.0"
    env["GPUSTACK_TPU_STATUS_INTERVAL"] = "2.0"
    # DISJOINT engine-port bands per worker: on real deployments each
    # worker is its own host, but both e2e workers share localhost —
    # identical bands race the probe-then-bind window and an engine can
    # die at bind (recoverable via restart, but it flakes the test).
    # And disjoint from every other test process's clusters.
    env["GPUSTACK_TPU_ENGINE_PORT_BASE"] = str(engine_port_base(host))
    return subprocess.Popen(
        [
            sys.executable, "-m", "gpustack_tpu", "start",
            "--server-url", f"http://127.0.0.1:{server_port}",
            "--data-dir", data_dir,
            "--registration-token", "mh-token",
            "--fake-detector", os.path.join(FIXTURES, fixture),
            "--force-platform", "cpu",
            "--worker-port", "0",
            "--worker-name", name,
        ],
        env=env,
        stdout=open(os.path.join(data_dir, "agent.log"), "ab"),
        stderr=subprocess.STDOUT,
    )


def _kill_engines_under(data_dir) -> int:
    """SIGKILL engine processes recorded in a worker's pidfiles (engines
    outlive a killed agent — they run in their own session)."""
    killed = 0
    log_dir = os.path.join(data_dir, "instance-logs")
    if not os.path.isdir(log_dir):
        return 0
    for fname in os.listdir(log_dir):
        if not fname.endswith(".pid"):
            continue
        try:
            with open(os.path.join(log_dir, fname)) as f:
                pid = int(json.loads(f.read())["pid"])
            os.kill(pid, signal.SIGKILL)
            killed += 1
        except (OSError, ValueError, KeyError):
            continue
    return killed


def test_multihost_serve_and_follower_loss(tmp_path, own_coordinator_band):
    from gpustack_tpu.config import Config
    from gpustack_tpu.server.server import Server

    server_port = _free_port()
    cfg = Config.load(
        {
            "host": "127.0.0.1",
            "port": server_port,
            "data_dir": str(tmp_path / "server"),
            "registration_token": "mh-token",
            "bootstrap_password": "mh-pass",
            "disable_worker": True,
            "heartbeat_interval": 1.0,
        }
    )
    dirs = [str(tmp_path / "w0"), str(tmp_path / "w1")]
    for d in dirs:
        os.makedirs(d)

    async def go():
        server = Server(cfg)
        await server.start()
        server.scheduler.scan_interval = 2.0
        base = f"http://127.0.0.1:{server_port}"
        workers = []
        try:
            workers.append(_spawn_worker(
                server_port, dirs[0], "v4_8_host0.json", "host0",
                host=0,
            ))
            workers.append(_spawn_worker(
                server_port, dirs[1], "v4_8_host1.json", "host1",
                host=1,
            ))
            async with aiohttp.ClientSession() as http:
                async with http.post(
                    f"{base}/auth/login",
                    json={"username": "admin", "password": "mh-pass"},
                ) as r:
                    assert r.status == 200, await r.text()
                    token = (await r.json())["token"]
                hdrs = {"Authorization": f"Bearer {token}"}

                # both worker hosts register + report chips
                deadline = time.time() + 90
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/workers", headers=hdrs
                    ) as r:
                        items = (await r.json())["items"]
                    ready = [
                        w for w in items
                        if w["state"] == "ready" and w["status"]["chips"]
                    ]
                    if len(ready) == 2:
                        break
                    await asyncio.sleep(1.0)
                else:
                    raise AssertionError(
                        f"2 workers never ready: {items}"
                    )

                # deploy one replica needing BOTH hosts (8 chips over
                # two 4-chip hosts of one ici_domain)
                async with http.post(
                    f"{base}/v2/models",
                    headers=hdrs,
                    json={
                        "name": "mh-tiny",
                        "preset": "tiny",
                        "replicas": 1,
                        "chips_per_replica": 8,
                        "max_seq_len": 256,
                        "max_slots": 8,
                    },
                ) as r:
                    assert r.status == 201, await r.text()

                # placement must be multi-host: leader + 1 subordinate +
                # coordinator address
                inst = await _wait_instance(
                    http, base, hdrs,
                    lambda i: i["state"] in (
                        "scheduled", "starting", "downloading", "running"
                    ),
                    60, "instance never scheduled",
                )
                assert len(inst["subordinate_workers"]) == 1, inst
                assert inst["coordinator_address"], inst

                inst = await _wait_instance(
                    http, base, hdrs,
                    lambda i: i["state"] == "running",
                    420, "multi-host replica never RUNNING",
                    fail_state="error",
                )
                leader_worker_id = inst["worker_id"]
                sub_worker_id = (
                    inst["subordinate_workers"][0]["worker_id"]
                )
                assert sub_worker_id != leader_worker_id

                # inference through the server proxy spans both hosts
                async with http.post(
                    f"{base}/v1/chat/completions",
                    headers=hdrs,
                    json={
                        "model": "mh-tiny",
                        "messages": [
                            {"role": "user", "content": "hello"}
                        ],
                        "max_tokens": 4,
                        "temperature": 0,
                    },
                    # first-request budget covers cold jit compiles in
                    # BOTH engine processes on a loaded 1-core box
                    timeout=aiohttp.ClientTimeout(total=420),
                ) as r:
                    assert r.status == 200, await r.text()
                    data = await r.json()
                assert data["usage"]["completion_tokens"] >= 1
                old_instance_id = inst["id"]

                # --- follower host dies ---------------------------------
                follower_dir = (
                    dirs[1]
                    if inst["worker_name"] == "host0" else dirs[0]
                )
                victim = (
                    workers[1]
                    if inst["worker_name"] == "host0" else workers[0]
                )
                victim.send_signal(signal.SIGKILL)
                victim.wait(timeout=10)
                _kill_engines_under(follower_dir)

                # heartbeat staleness -> subordinate UNREACHABLE -> the
                # replica is torn down (old instance deleted)...
                deadline = time.time() + 180
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/model-instances", headers=hdrs
                    ) as r:
                        insts = (await r.json())["items"]
                    ids = [i["id"] for i in insts]
                    if old_instance_id not in ids:
                        break
                    await asyncio.sleep(2.0)
                else:
                    raise AssertionError(
                        f"replica never torn down: {insts}"
                    )

                # ...and the ModelController's replica sync creates a
                # REPLACEMENT instance (it cannot place while the
                # follower host is dead -> pending/scheduled)
                deadline = time.time() + 180
                replacement = None
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/model-instances", headers=hdrs
                    ) as r:
                        insts = (await r.json())["items"]
                    fresh = [
                        i for i in insts if i["id"] != old_instance_id
                    ]
                    if fresh:
                        replacement = fresh[0]
                        break
                    await asyncio.sleep(2.0)
                assert replacement is not None, "no replacement instance"
                assert replacement["state"] in (
                    "analyzing", "pending", "scheduled", "starting",
                    "downloading", "error",
                ), replacement
        finally:
            for w in workers:
                if w.poll() is None:
                    w.send_signal(signal.SIGKILL)
            for d in dirs:
                _kill_engines_under(d)
            await server.stop()

    asyncio.run(go())


async def _wait_instance(
    http, base, hdrs, pred, budget_s, fail_msg, fail_state=None
):
    deadline = time.time() + budget_s
    last = None
    while time.time() < deadline:
        async with http.get(
            f"{base}/v2/model-instances", headers=hdrs
        ) as r:
            items = (await r.json())["items"]
        if items:
            last = items[0]
            if pred(last):
                return last
            if fail_state and last["state"] == fail_state:
                raise AssertionError(
                    f"instance errored: {last['state_message']}"
                )
        await asyncio.sleep(1.5)
    raise AssertionError(f"{fail_msg}; last: {last}")
