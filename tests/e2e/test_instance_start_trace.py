"""One trace a replica start, over the real processes: the worker's span
``instance_start`` (``GET /v2/debug/traces?component=worker``) and the
engine subprocess's ``engine_start`` (its ``/debug/startup``, through the
worker's proxy) share a trace id, parent and child. One server, one
deployment, one engine process for every test of this file."""

import asyncio
import os
import re
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


@pytest.fixture(scope="module")
def started(tmp_path_factory):
    from gpustack_tpu.config import Config
    from gpustack_tpu.server.server import Server
    import chip_smoke

    tmp = tmp_path_factory.mktemp("instance_start")
    port = _free_port()
    cfg = Config.load({
        "host": "127.0.0.1", "port": port, "data_dir": str(tmp),
        "registration_token": "start-token",
        "bootstrap_password": "admin-start-pass",
        "fake_detector": FIXTURE, "force_platform": "cpu",
        "heartbeat_interval": 1.0, "status_interval": 2.0,
        "worker_port": 0,
    })
    base = f"http://127.0.0.1:{port}"

    async def go():
        server = Server(cfg)
        await server.start()
        server.scheduler.scan_interval = 2.0
        try:
            async with aiohttp.ClientSession() as http:
                async with http.post(f"{base}/auth/login", json={
                    "username": "admin", "password": "admin-start-pass",
                }) as r:
                    assert r.status == 200, await r.text()
                    hdrs = {"Authorization": f"Bearer {(await r.json())['token']}"}

                async def poll(path, ok, seconds):
                    deadline = time.time() + seconds
                    while time.time() < deadline:
                        async with http.get(f"{base}{path}", headers=hdrs) as r:
                            items = (await r.json())["items"]
                        if ok(items):
                            return items
                        await asyncio.sleep(0.5)
                    raise AssertionError(f"{path}: {items}")

                await poll("/v2/workers", lambda w: w and w[0]["state"] == "ready"
                           and w[0]["status"]["chips"], 60)
                posted = time.time()
                async with http.post(f"{base}/v2/models", headers=hdrs, json={
                    "name": "tiny-start", "preset": "tiny", "replicas": 1,
                    "max_seq_len": 256, "max_slots": 2,
                }) as r:
                    assert r.status == 201, await r.text()

                def running(insts):
                    assert not insts or insts[0]["state"] != "error", insts
                    return insts and insts[0]["state"] == "running"

                inst = (await poll("/v2/model-instances", running, 300))[0]
                seen_running = time.time()
                async with http.get(
                    f"{base}/v2/debug/traces?component=worker&limit=200",
                    headers=hdrs,
                ) as r:
                    assert r.status == 200, await r.text()
                    traces = (await r.json())["items"]
                workers = chip_smoke.worker_endpoints(str(tmp))
                url, secret = workers[inst["worker_id"]]
                engine = f"{url}/proxy/instances/{inst['id']}"
                auth = {"Authorization": f"Bearer {secret}"}

                async def engine_get(path):
                    async with http.get(engine + path, headers=auth) as r:
                        assert r.status == 200, await r.text()
                        return await r.json()

                before = await engine_get("/debug/startup")
                async with http.post(f"{base}/v1/chat/completions", headers=hdrs, json={
                    "model": "tiny-start", "max_tokens": 3,
                    "messages": [{"role": "user", "content": "hi"}],
                }) as r:
                    assert r.status == 200, await r.text()
                after = await engine_get("/debug/startup")
                health = await engine_get("/healthz")
                async with http.get(
                    f"{base}/v2/model-instances/{inst['id']}/logs", headers=hdrs,
                ) as r:
                    assert r.status == 200, await r.text()
                    logs = await r.text()
                return {
                    "posted": posted, "seen_running": seen_running,
                    "inst": inst, "traces": traces, "before": before,
                    "after": after, "health": health, "logs": logs,
                }
        finally:
            await server.stop()

    return asyncio.run(go())


def _instance_start(started):
    # the worker's trace store is the process's: another test file on this
    # xdist worker may have left an ``instance_start`` of its own model
    mine = [
        t for t in started["traces"]
        if t["name"] == "instance_start" and t.get("model") == "tiny-start"
    ]
    assert len(mine) == 1, started["traces"]
    return mine[0]


def test_the_workers_span_has_spawn_and_health_wait(started):
    from gpustack_tpu.testing.traces import assert_phases

    span = _instance_start(started)
    assert span["component"] == "worker" and span["model"] == "tiny-start"
    assert span["outcome"] == "ok" and span["status"] == 200
    assert span["attrs"] == {
        "instance_id": started["inst"]["id"], "model": "tiny-start"}
    assert_phases(span, ["spawn", "health_wait"])
    spawn, wait = span["spans"]
    assert [spawn["phase"], wait["phase"]] == ["spawn", "health_wait"]
    assert "attrs" not in wait            # ended by the 200, not cut short
    # the wait begins where the spawn ended, and is the longer by far
    assert wait["offset_ms"] >= spawn["offset_ms"] + spawn["duration_ms"] - 0.01
    assert wait["duration_ms"] > spawn["duration_ms"]
    assert started["posted"] <= span["started_at"] <= started["seen_running"]
    assert span["started_at"] + span["duration_ms"] / 1e3 <= started["seen_running"]


def test_the_engines_span_is_the_workers_child(started):
    span = _instance_start(started)
    for view in (started["before"], started["after"]):
        assert view["trace_id"] == span["trace_id"]
        assert view["parent_id"] == span["span_id"]
        assert view["span_id"] != span["span_id"]
        assert view["name"] == "engine_start" and view["model"] == "tiny-start"


def test_the_engine_was_created_inside_the_workers_spawn(started):
    span = _instance_start(started)
    spawn, wait = span["spans"]
    s = started["after"]["summary"]
    created = s["t0"] - span["started_at"]           # seconds into the span
    assert spawn["offset_ms"] / 1e3 - 0.05 <= created
    assert created <= (spawn["offset_ms"] + spawn["duration_ms"]) / 1e3 + 0.05
    # the 200 the worker waited for is the engine's ``ready``: its poll
    # (every HEALTH_INTERVAL) saw it at the end of health_wait
    from gpustack_tpu.worker.serve_manager import HEALTH_INTERVAL

    ready_at = s["t0"] + s["ready_s"]
    wait_end = span["started_at"] + (wait["offset_ms"] + wait["duration_ms"]) / 1e3
    assert ready_at <= wait_end + 0.05
    listening = s["t0"] + sum(s["phases"].values())
    assert listening <= ready_at <= listening + HEALTH_INTERVAL + 3.0


def test_running_came_before_the_first_token(started):
    assert started["before"]["sealed"] is False
    assert started["before"]["summary"]["first_token_s"] is None
    s = started["after"]["summary"]
    assert started["after"]["sealed"] is True
    assert s["t0"] + s["first_token_s"] >= started["seen_running"] - 1.0
    assert s["first_token_s"] > s["ready_s"]
    assert started["health"]["startup"]["first_token_s"] == s["first_token_s"]
    serving = [r["name"] for r in started["after"]["programs"] if r["phase"] == "step"]
    assert any(n.startswith("jit(prefill_") for n in serving), serving


def test_the_engines_log_lines_carry_a_time(started):
    lines = [l for l in started["logs"].splitlines() if " gpustack_tpu." in l]
    stamped = re.compile(r"^\d{4}-\d\d-\d\d \d\d:\d\d:\d\d,\d{3} [A-Z]+ \S+: ")
    assert lines and all(stamped.match(l) for l in lines), lines[:3]
    trace_id = _instance_start(started)["trace_id"]
    assert any(f"engine_start trace={trace_id} listening after" in l for l in lines)
    assert any(f"engine_start trace={trace_id} first token after" in l for l in lines)
