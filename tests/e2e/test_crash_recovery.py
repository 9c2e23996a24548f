"""Crash recovery e2e: SIGKILL the whole server+worker process, restart
on the same data dir, and require the instance to come back RUNNING with
a fresh engine (orphan reaped, worker re-registered, zombie state
re-driven).

This encodes a three-bug regression found by crash injection: ephemeral
worker uuids broke re-registration, orphaned engines were never reaped,
and DB-RUNNING records without a process were never relaunched.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import aiohttp
import pytest

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "workers", "v5e_8.json",
)
REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_server(port, data_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [
            sys.executable, "-m", "gpustack_tpu", "start",
            "--host", "127.0.0.1", "--port", str(port),
            "--data-dir", data_dir,
            "--registration-token", "crash-tok",
            "--bootstrap-password", "crash-pass",
            "--fake-detector", FIXTURE,
            "--force-platform", "cpu",
            "--worker-port", "0",
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


import asyncio  # noqa: E402


async def _api(base, method, path, token=None, body=None, timeout=10):
    headers = {"Authorization": f"Bearer {token}"} if token else {}
    async with aiohttp.ClientSession() as http:
        async with http.request(
            method, base + path, headers=headers, json=body,
            timeout=aiohttp.ClientTimeout(total=timeout),
        ) as r:
            return r.status, await r.json()


async def _wait_running(base, token, deadline_s):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            _, data = await _api(
                base, "GET", "/v2/model-instances", token
            )
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
            await asyncio.sleep(2)
            continue
        items = data.get("items", [])
        if items and items[0]["state"] == "running":
            return items[0]
        await asyncio.sleep(2)
    raise AssertionError("instance did not reach running")


def test_sigkill_recovery(tmp_path):
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    data_dir = str(tmp_path)
    proc = _spawn_server(port, data_dir)
    try:
        async def phase1():
            # login (retry while booting)
            deadline = time.time() + 60
            while True:
                try:
                    status, resp = await _api(
                        base, "POST", "/auth/login",
                        body={
                            "username": "admin",
                            "password": "crash-pass",
                        },
                    )
                    if status == 200:
                        return resp["token"]
                except (aiohttp.ClientError, OSError):
                    pass
                if time.time() > deadline:
                    raise AssertionError("server never came up")
                await asyncio.sleep(1)

        token = asyncio.run(phase1())

        async def phase2():
            status, _ = await _api(
                base, "POST", "/v2/models", token,
                body={
                    "name": "crash-model", "preset": "tiny",
                    "replicas": 1, "max_seq_len": 256, "max_slots": 2,
                },
            )
            assert status == 201
            return await _wait_running(base, token, 240)

        inst = asyncio.run(phase2())
        pidfile = os.path.join(data_dir, "instance-logs", "1.pid")
        with open(pidfile) as f:
            old_engine_pid = json.loads(f.read())["pid"]

        # hard-kill the whole control plane
        proc.send_signal(signal.SIGKILL)
        proc.wait(10)
        # the engine survives as an orphan (own session)
        assert os.path.exists(f"/proc/{old_engine_pid}")

        proc2 = _spawn_server(port, data_dir)
        try:
            # wait for a NEW engine pidfile (the restart re-drives the
            # instance; the DB briefly still says 'running' for the old
            # engine, so waiting on state alone races)
            deadline = time.time() + 240
            new_engine_pid = old_engine_pid
            while time.time() < deadline:
                try:
                    with open(pidfile) as f:
                        new_engine_pid = json.loads(f.read())["pid"]
                    if new_engine_pid != old_engine_pid:
                        break
                except (OSError, ValueError):
                    pass
                time.sleep(1)
            assert new_engine_pid != old_engine_pid, "no new engine spawned"
            # exited (a zombie waiting for init to collect it holds no
            # chip and no port — utils/process.py)
            from gpustack_tpu.utils.process import pid_running

            assert not pid_running(old_engine_pid)
            asyncio.run(_wait_running(base, token, 240))

            async def chat():
                return await _api(
                    base, "POST", "/v1/chat/completions", token,
                    body={
                        "model": "crash-model",
                        "messages": [{"role": "user", "content": "hi"}],
                        "max_tokens": 3, "temperature": 0,
                    },
                    timeout=120,
                )

            status, resp = asyncio.run(chat())
            assert status == 200, resp
            assert resp["usage"]["completion_tokens"] >= 1
        finally:
            proc2.send_signal(signal.SIGTERM)
            try:
                proc2.wait(15)
            except subprocess.TimeoutExpired:
                proc2.kill()
    finally:
        if proc.poll() is None:
            proc.kill()
        # engines spawned during the test
        for pidf in ("1.pid",):
            path = os.path.join(data_dir, "instance-logs", pidf)
            if os.path.exists(path):
                try:
                    pid = json.loads(open(path).read())["pid"]
                    os.kill(pid, signal.SIGKILL)
                except (OSError, ValueError, KeyError):
                    pass
