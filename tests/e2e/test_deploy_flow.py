"""End-to-end: server + embedded worker + engine subprocess on CPU.

The full reference core loop (SURVEY.md §3.2-3.3) hermetically: deploy a
model via the management API → controller creates an instance → scheduler
places it onto the (fake-detected v5e-8) worker → serve manager spawns a
real engine process → OpenAI request proxied through the server answers.
"""

import asyncio
import os
import socket
import time

import aiohttp
import pytest

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "workers", "v5e_8.json",
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_deploy_and_infer(tmp_path):
    from gpustack_tpu.config import Config
    from gpustack_tpu.server.server import Server

    port = _free_port()
    cfg = Config.load(
        {
            "host": "127.0.0.1",
            "port": port,
            "data_dir": str(tmp_path),
            "registration_token": "e2e-token",
            "bootstrap_password": "admin-e2e-pass",
            "fake_detector": FIXTURE,
            "force_platform": "cpu",
            "heartbeat_interval": 1.0,
            "status_interval": 2.0,
            # ephemeral: a stale process on the fixed default port must
            # never be able to kill this tier again
            "worker_port": 0,
        }
    )

    async def go():
        server = Server(cfg)
        await server.start()
        # faster scheduling retries for the test
        server.scheduler.scan_interval = 2.0
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as http:
                # login
                async with http.post(
                    f"{base}/auth/login",
                    json={
                        "username": "admin",
                        "password": "admin-e2e-pass",
                    },
                ) as r:
                    assert r.status == 200, await r.text()
                    token = (await r.json())["token"]
                hdrs = {"Authorization": f"Bearer {token}"}

                # unauthenticated management is rejected
                async with http.get(f"{base}/v2/models") as r:
                    assert r.status == 401

                # wait for the embedded worker to register + report chips
                deadline = time.time() + 60
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/workers", headers=hdrs
                    ) as r:
                        items = (await r.json())["items"]
                    if items and items[0]["state"] == "ready" and (
                        items[0]["status"]["chips"]
                    ):
                        break
                    await asyncio.sleep(0.5)
                else:
                    raise AssertionError("worker never became ready")
                assert len(items[0]["status"]["chips"]) == 8

                # deploy the tiny preset
                async with http.post(
                    f"{base}/v2/models",
                    headers=hdrs,
                    json={
                        "name": "tiny-chat",
                        "preset": "tiny",
                        "replicas": 1,
                        "max_seq_len": 512,
                        "max_slots": 2,
                    },
                ) as r:
                    assert r.status == 201, await r.text()
                    model = await r.json()

                # instance goes PENDING → ... → RUNNING
                deadline = time.time() + 300
                state_seen = set()
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/model-instances", headers=hdrs
                    ) as r:
                        insts = (await r.json())["items"]
                    if insts:
                        state_seen.add(insts[0]["state"])
                        if insts[0]["state"] == "running":
                            break
                        if insts[0]["state"] == "error":
                            raise AssertionError(
                                f"instance error: "
                                f"{insts[0]['state_message']}"
                            )
                    await asyncio.sleep(1.0)
                else:
                    raise AssertionError(
                        f"instance never ran; states seen: {state_seen}; "
                        f"last: {insts}"
                    )
                inst = insts[0]
                assert inst["worker_id"] == items[0]["id"]
                assert inst["chip_indexes"] == [0]
                assert inst["computed_resource_claim"]["mesh_plan"]

                # chat through the server's OpenAI proxy: the same walk
                # chip_smoke.py makes on the chip (greedy twice and
                # identical, streamed, a long prompt, four at once) —
                # one piece of code, rehearsed here on the CPU
                import chip_smoke

                walked = await asyncio.to_thread(
                    chip_smoke.exercise_chat, base, hdrs, "tiny-chat",
                    long_prompt_chars=300, min_long_prompt_tokens=300,
                    max_tokens=4, timeout=120.0,
                )
                assert walked["first"]["usage"]["completion_tokens"] >= 1
                assert walked["long"]["usage"]["prompt_tokens"] >= 300

                # the engine says what it runs on, through its worker
                # (what chip_smoke.py's last line is read from)
                workers = chip_smoke.worker_endpoints(str(tmp_path))
                health = await asyncio.to_thread(
                    chip_smoke.engine_health, workers, inst
                )
                assert health["device"]["platform"] == "cpu"
                assert health["device"]["count"] == 1
                assert health["tokens_generated"] > 0

                # /v1/models lists the route
                async with http.get(
                    f"{base}/v1/models", headers=hdrs
                ) as r:
                    names = [m["id"] for m in (await r.json())["data"]]
                assert "tiny-chat" in names

                # usage was recorded
                async with http.get(
                    f"{base}/v2/model-usage", headers=hdrs
                ) as r:
                    usage = (await r.json())["items"]
                assert usage and usage[0]["total_tokens"] > 0

                # run a smoke benchmark against the running instance
                async with http.post(
                    f"{base}/v2/benchmarks",
                    headers=hdrs,
                    json={
                        "name": "bench-tiny",
                        "model_id": model["id"],
                        "profile": "smoke",
                    },
                ) as r:
                    assert r.status == 201, await r.text()
                    bench = await r.json()
                deadline = time.time() + 120
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/benchmarks/{bench['id']}", headers=hdrs
                    ) as r:
                        bench = await r.json()
                    if bench["state"] in ("completed", "error"):
                        break
                    await asyncio.sleep(1.0)
                assert bench["state"] == "completed", bench
                assert bench["metrics"]["output_tok_per_s"] > 0
                assert bench["metrics"]["ttft_ms_p50"] > 0
                assert bench["metrics"]["error_count"] == 0

                # server prometheus metrics
                async with http.get(f"{base}/metrics") as r:
                    metrics_text = await r.text()
                assert 'gpustack_model_instances{state="running"} 1' in (
                    metrics_text
                )
                assert "gpustack_usage_total_tokens" in metrics_text

                # instance logs proxied through server -> worker
                async with http.get(
                    f"{base}/v2/model-instances/{inst['id']}/logs",
                    headers=hdrs,
                ) as r:
                    assert r.status == 200, await r.text()
                    logs = await r.text()
                assert "Running on" in logs or "engine" in logs.lower()

                # scale to zero retires the instance
                async with http.patch(
                    f"{base}/v2/models/{model['id']}",
                    headers=hdrs,
                    json={"replicas": 0},
                ) as r:
                    assert r.status == 200
                deadline = time.time() + 30
                while time.time() < deadline:
                    async with http.get(
                        f"{base}/v2/model-instances", headers=hdrs
                    ) as r:
                        if not (await r.json())["items"]:
                            break
                    await asyncio.sleep(0.5)
                else:
                    raise AssertionError("instance was not retired")
        finally:
            await server.stop()

    asyncio.run(go())
