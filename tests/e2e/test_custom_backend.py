"""External-engine orchestration e2e (verdict r4 #3).

GPUStack's identity is configuring and orchestrating inference engines
(reference README.md:33-41; worker/backends/base.py:150 + the concrete
vllm/custom adapters). This test proves the whole contract against a
real EXTERNAL OpenAI-compatible server binary — the in-tree stub engine
(gpustack_tpu/testing/stub_engine.py), launched from a catalog command
template exactly as vLLM-TPU or JetStream would be:

1. the backend-catalog sync seeds InferenceBackend rows from the
   shipped assets/backend-catalog.json,
2. a model deployed with ``backend: stub-openai`` is scheduled, spawned
   from the rendered argv, health-probed at the backend's OWN
   ``health_path`` (/health — not the in-repo engines' /healthz),
3. completions flow through the server's OpenAI proxy and usage is
   recorded,
4. the worker scrapes the engine's vllm:* metrics and serves them
   normalized,
5. SIGKILLing the engine binary crash-restarts it through the same
   ServeManager path and service resumes.
"""

import asyncio
import json
import os
import signal
import socket
import time

import aiohttp

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
FIXTURE = os.path.join(
    REPO, "tests", "fixtures", "workers", "v5e_8.json"
)
CATALOG = os.path.join(
    REPO, "gpustack_tpu", "assets", "backend-catalog.json"
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_custom_backend_full_lifecycle(tmp_path):
    from gpustack_tpu.config import Config
    from gpustack_tpu.server.server import Server

    port = _free_port()
    cfg = Config.load(
        {
            "host": "127.0.0.1",
            "port": port,
            "data_dir": str(tmp_path),
            "registration_token": "cb-token",
            "bootstrap_password": "cb-pass",
            "fake_detector": FIXTURE,
            "force_platform": "cpu",
            "heartbeat_interval": 1.0,
            "status_interval": 2.0,
            "worker_port": 0,
            "backend_catalog_url": CATALOG,
        }
    )

    async def go():
        server = Server(cfg)
        await server.start()
        server.scheduler.scan_interval = 2.0
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as http:
                async with http.post(
                    f"{base}/auth/login",
                    json={"username": "admin", "password": "cb-pass"},
                ) as r:
                    assert r.status == 200, await r.text()
                    token = (await r.json())["token"]
                hdrs = {"Authorization": f"Bearer {token}"}

                # catalog sync seeded the shipped backends
                deadline = time.time() + 30
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/inference-backends", headers=hdrs
                    ) as r:
                        rows = (await r.json())["items"]
                    names = {b["name"] for b in rows}
                    # rows land one by one: wait for all that are checked
                    if {"stub-openai", "vllm-tpu", "jetstream"} <= names:
                        break
                    await asyncio.sleep(0.5)
                else:
                    raise AssertionError(
                        f"catalog never seeded: {names}"
                    )
                assert {"vllm-tpu", "jetstream"} <= names
                stub = next(
                    b for b in rows if b["name"] == "stub-openai"
                )
                assert stub["managed"] is True
                assert (
                    stub["versions"][0]["health_path"] == "/health"
                )

                # worker ready
                deadline = time.time() + 60
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/workers", headers=hdrs
                    ) as r:
                        workers = (await r.json())["items"]
                    if workers and workers[0]["state"] == "ready" and (
                        workers[0]["status"]["chips"]
                    ):
                        break
                    await asyncio.sleep(0.5)
                else:
                    raise AssertionError("worker never ready")

                # deploy on the EXTERNAL backend
                async with http.post(
                    f"{base}/v2/models",
                    headers=hdrs,
                    json={
                        "name": "ext-model",
                        "preset": "tiny",
                        "backend": "stub-openai",
                        "replicas": 1,
                        "max_seq_len": 512,
                        "max_slots": 2,
                    },
                ) as r:
                    assert r.status == 201, await r.text()

                inst = await _wait_running(http, base, hdrs, 180)

                # the spawned process is the stub engine, not the in-repo
                # server (pidfile argv fingerprint)
                logdir = os.path.join(str(tmp_path), "instance-logs")
                pid, argv = _read_pidfile(logdir)
                assert any("stub_engine" in a for a in argv), argv

                # chat through the server's OpenAI proxy
                async with http.post(
                    f"{base}/v1/chat/completions",
                    headers=hdrs,
                    json={
                        "model": "ext-model",
                        "messages": [
                            {"role": "user", "content": "ping pong"}
                        ],
                        "max_tokens": 8,
                        "temperature": 0,
                    },
                ) as r:
                    assert r.status == 200, await r.text()
                    data = await r.json()
                assert data["choices"][0]["message"]["content"].startswith(
                    "stub:"
                )
                assert data["usage"]["completion_tokens"] >= 1

                # streaming relays through the proxy too
                async with http.post(
                    f"{base}/v1/chat/completions",
                    headers=hdrs,
                    json={
                        "model": "ext-model",
                        "messages": [
                            {"role": "user", "content": "stream me"}
                        ],
                        "max_tokens": 4,
                        "stream": True,
                    },
                ) as r:
                    assert r.status == 200
                    body = (await r.read()).decode()
                assert "data:" in body and "[DONE]" in body

                # usage middleware recorded the external engine's counts
                async with http.get(
                    f"{base}/v2/model-usage", headers=hdrs
                ) as r:
                    usage = (await r.json())["items"]
                assert usage and usage[0]["total_tokens"] > 0

                # worker scrapes vllm:* metrics and normalizes names
                wport = workers[0]["port"]
                deadline = time.time() + 30
                normalized = ""
                while time.time() < deadline:
                    try:
                        async with http.get(
                            f"http://127.0.0.1:{wport}/metrics"
                        ) as r:
                            normalized = await r.text()
                        if "gpustack_tpu:prompt_tokens_total" in normalized:
                            break
                    except aiohttp.ClientError:
                        pass
                    await asyncio.sleep(1.0)
                assert "gpustack_tpu:prompt_tokens_total" in normalized
                async with http.get(
                    f"http://127.0.0.1:{wport}/metrics/raw"
                ) as r:
                    raw = await r.text()
                assert "vllm:prompt_tokens_total" in raw

                # --- crash the external binary; manager must restart it
                os.kill(pid, signal.SIGKILL)
                deadline = time.time() + 120
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/model-instances", headers=hdrs
                    ) as r:
                        items = (await r.json())["items"]
                    if items and items[0]["state"] == "running" and (
                        _read_pidfile(logdir)[0] != pid
                    ):
                        break
                    await asyncio.sleep(1.0)
                else:
                    raise AssertionError(
                        f"engine never restarted: {items}"
                    )
                assert items[0]["restarts"] >= 1, items[0]

                # service resumed through the proxy
                async with http.post(
                    f"{base}/v1/chat/completions",
                    headers=hdrs,
                    json={
                        "model": "ext-model",
                        "messages": [
                            {"role": "user", "content": "back"}
                        ],
                        "max_tokens": 4,
                        "temperature": 0,
                    },
                ) as r:
                    assert r.status == 200, await r.text()
                assert inst  # placement happened above
        finally:
            await server.stop()

    asyncio.run(go())


def _read_pidfile(logdir):
    for fname in sorted(os.listdir(logdir)):
        if fname.endswith(".pid"):
            with open(os.path.join(logdir, fname)) as f:
                rec = json.loads(f.read())
            return int(rec["pid"]), rec.get("argv", [])
    raise AssertionError(f"no pidfile in {logdir}")


async def _wait_running(http, base, hdrs, budget_s):
    deadline = time.time() + budget_s
    items = []
    while time.time() < deadline:
        async with http.get(
            f"{base}/v2/model-instances", headers=hdrs
        ) as r:
            items = (await r.json())["items"]
        if items:
            if items[0]["state"] == "running":
                return items[0]
            if items[0]["state"] == "error":
                raise AssertionError(
                    f"instance error: {items[0]['state_message']}"
                )
        await asyncio.sleep(1.0)
    raise AssertionError(f"never RUNNING: {items}")
