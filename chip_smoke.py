#!/usr/bin/env python3
"""Chip smoke: the serving path, end to end, on real TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four replicas, then one tp4 replica

Drives what a user drives: ``python -m gpustack_tpu start`` (server +
embedded worker, the real detector) as a child process, ``POST
/v2/models`` with Qwen3-8B at full width and depth (int8, random weights
from the seed, 2048 context, 8 slots), and ``/v1/chat/completions`` on
the server's port (proxy -> worker reverse proxy -> engine process).

This script never imports JAX: a parent that touched JAX would hold the
chip its engine child needs. The device in the last line is what the
engine process that served the requests reports in its health. There is
no CPU mode: without a chip the detector finds nothing (or the engine
cannot open a TPU) and the run fails with ``"ok": false``.

One JSON line per phase; the last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
on success. Exit code 0 only then. Every wait has a deadline, and every
process the script starts is stopped before it returns.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import random
import signal
import socket
import sqlite3
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))

MODEL_NAME = "qwen3-8b-int8"
MODEL_SPEC: Dict[str, Any] = {
    "name": MODEL_NAME,
    "preset": "qwen3-8b",
    "quantization": "int8",
    "max_seq_len": 2048,
    "max_slots": 8,
}
TP4_PLAN = "dp1xsp1xep1xtp4"
PLATFORM = "tpu"
# the engine logs its kernel choice once per prefill bucket (runner.py)
FLASH_LOG_LINE = "prefill bucket 2048: attention impl flash"
SEED = 0
# the whole run must end inside the driver's 1200 s
RUN_DEADLINE_S = 1100.0
# tp4 vs one chip: int8 weights, bf16 activations, another reduction
# order across four chips — first-token top log-probabilities agree to
# this many nats
TP4_LOGPROB_TOL = 0.25
ADMIN_PASSWORD = "chip-smoke-admin"
SERVER_ARGS: Tuple[str, ...] = ()   # real detector, no forced platform


class SmokeFailure(Exception):
    pass


def emit(record: Dict[str, Any]) -> None:
    print(json.dumps(record), flush=True)


class Phase:
    """``with Phase("name") as p: ...; p["key"] = value`` — one JSON line
    with the phase's seconds, also when the phase fails."""

    def __init__(self, name: str):
        self.record: Dict[str, Any] = {"phase": name}

    def __enter__(self) -> Dict[str, Any]:
        self.t0 = time.time()
        return self.record

    def __exit__(self, exc_type, exc, tb) -> None:
        self.record["seconds"] = round(time.time() - self.t0, 3)
        if exc is not None:
            self.record["error"] = f"{exc_type.__name__}: {exc}"[:2000]
        emit(self.record)


# ---------------------------------------------------------------------------
# HTTP (stdlib only)
# ---------------------------------------------------------------------------


def http(
    method: str,
    url: str,
    body: Any = None,
    headers: Optional[Dict[str, str]] = None,
    timeout: float = 30.0,
) -> Tuple[int, Any]:
    """(status, parsed JSON or text). Never raises on an HTTP status."""
    data = None
    hdrs = dict(headers or {})
    if body is not None:
        data = json.dumps(body).encode()
        hdrs["Content-Type"] = "application/json"
    req = urllib.request.Request(url, data=data, headers=hdrs, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status, raw = r.status, r.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode("utf-8", errors="replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def expect(status_body: Tuple[int, Any], want: int, what: str) -> Any:
    status, body = status_body
    if status != want:
        raise SmokeFailure(f"{what}: HTTP {status}: {str(body)[:800]}")
    return body


def poll(what: str, deadline: float, fn: Callable[[], Any], every: float = 1.0):
    """Call ``fn`` until it returns something truthy or the deadline."""
    last_err = None
    while time.time() < deadline:
        try:
            got = fn()
            if got:
                return got
        except (OSError, urllib.error.URLError) as e:
            last_err = e
        time.sleep(every)
    raise SmokeFailure(f"deadline passed waiting for {what} ({last_err})")


# ---------------------------------------------------------------------------
# The request-sending half (shared with tests/e2e/test_deploy_flow.py)
# ---------------------------------------------------------------------------


def seeded_text(seed: int, n_chars: int) -> str:
    """Deterministic plain-ASCII prose of exactly ``n_chars`` characters.
    A preset without a checkpoint tokenizes bytes, so characters are
    tokens."""
    rng = random.Random(seed)
    words = (
        "tensor mesh shard chip batch token cache slice host queue "
        "prefill decode kernel layer vector scalar weight logit"
    ).split()
    out: List[str] = []
    size = 0
    while size <= n_chars:
        w = rng.choice(words)
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_chars]


def chat(
    base: str,
    hdrs: Dict[str, str],
    model: str,
    content: str,
    max_tokens: int = 8,
    timeout: float = 600.0,
    **extra: Any,
) -> Dict[str, Any]:
    body = {
        "model": model,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0,
        **extra,
    }
    t0 = time.time()
    data = expect(
        http("POST", f"{base}/v1/chat/completions", body, hdrs, timeout),
        200, "chat completion",
    )
    if data.get("object") != "chat.completion":
        raise SmokeFailure(f"not a chat.completion: {str(data)[:400]}")
    data["_seconds"] = round(time.time() - t0, 3)
    return data


def chat_stream(
    base: str,
    hdrs: Dict[str, str],
    model: str,
    content: str,
    max_tokens: int = 8,
    timeout: float = 600.0,
) -> Dict[str, Any]:
    """One streamed completion: counts SSE chunks, returns the usage."""
    body = {
        "model": model,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0,
        "stream": True,
        "stream_options": {"include_usage": True},
    }
    req = urllib.request.Request(
        f"{base}/v1/chat/completions",
        data=json.dumps(body).encode(),
        headers={**hdrs, "Content-Type": "application/json"},
        method="POST",
    )
    chunks, usage, done = 0, None, False
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            for raw in r:
                line = raw.decode("utf-8", errors="replace").strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    done = True
                    break
                event = json.loads(payload)
                chunks += 1
                usage = event.get("usage") or usage
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"stream: HTTP {e.code}: {e.read()[:400]!r}"
        ) from e
    if not (chunks >= 2 and done):
        raise SmokeFailure(f"stream: {chunks} chunks, done={done}")
    if not usage or usage.get("completion_tokens", 0) < 1:
        raise SmokeFailure(f"stream: no usage in the chunks: {usage}")
    return {"chunks": chunks, "usage": usage}


def chat_many(
    base: str,
    hdrs: Dict[str, str],
    model: str,
    prompts: List[str],
    max_tokens: int = 8,
    timeout: float = 600.0,
    **extra: Any,
) -> List[Dict[str, Any]]:
    """All ``prompts`` at once, one thread each; raises on the first
    request that failed, naming it."""
    results: List[Any] = [None] * len(prompts)

    def one(i: int) -> None:
        try:
            results[i] = chat(
                base, hdrs, model, prompts[i], max_tokens, timeout, **extra
            )
        except Exception as e:  # reported below, with its index
            results[i] = e

    threads = [
        threading.Thread(target=one, args=(i,)) for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 30)
    for i, r in enumerate(results):
        if not isinstance(r, dict):
            raise SmokeFailure(f"concurrent request {i}: {r!r}")
    return results


def answer_of(data: Dict[str, Any]) -> Dict[str, Any]:
    """What two greedy runs must agree on, token for token: the text and
    every sampled token's log-probability (the API carries no token ids;
    a preset's byte tokenizer decodes most of a 150k vocabulary to
    nothing, so the text alone would prove little)."""
    choice = data["choices"][0]
    lps = [
        e["logprob"]
        for e in (choice.get("logprobs") or {}).get("content", [])
    ]
    return {
        "text": choice["message"]["content"],
        "finish_reason": choice.get("finish_reason"),
        "completion_tokens": data["usage"]["completion_tokens"],
        "logprobs": lps,
    }


def first_token_top_logprobs(data: Dict[str, Any]) -> List[float]:
    content = data["choices"][0]["logprobs"]["content"]
    return sorted(
        (e["logprob"] for e in content[0]["top_logprobs"]), reverse=True
    )


def check_finite(data: Dict[str, Any], what: str) -> None:
    lps = answer_of(data)["logprobs"]
    if not lps or any(
        not isinstance(x, (int, float)) or x != x or x > 1e-3 or x < -1e4
        for x in lps
    ):
        raise SmokeFailure(f"{what}: log-probabilities not finite: {lps}")


GREEDY = {"logprobs": True, "top_logprobs": 5}


def exercise_chat(
    base: str,
    hdrs: Dict[str, str],
    model: str,
    long_prompt_chars: int,
    min_long_prompt_tokens: int,
    max_tokens: int = 8,
    concurrent: int = 4,
    timeout: float = 600.0,
) -> Dict[str, Any]:
    """The walk every deployment must survive, through the server's
    ``/v1/chat/completions``: a short greedy request, the same again
    (identical token for token), a streamed request, one long prompt and
    ``concurrent`` requests at once. Emits one line per phase; raises
    SmokeFailure on the first thing that is wrong."""
    prompt = "Say hello to the chip."
    out: Dict[str, Any] = {}
    with Phase("chat_first") as p:
        first = chat(base, hdrs, model, prompt, max_tokens, timeout, **GREEDY)
        check_finite(first, "first request")
        if first["usage"]["completion_tokens"] < 1:
            raise SmokeFailure(f"no completion tokens: {first['usage']}")
        p["usage"] = first["usage"]
        # first request of these shapes: compilation is inside it
        p["includes_compile"] = True
    with Phase("chat_repeat") as p:
        again = chat(base, hdrs, model, prompt, max_tokens, timeout, **GREEDY)
        a, b = answer_of(first), answer_of(again)
        p["identical"] = a == b
        p["completion_tokens"] = b["completion_tokens"]
        if a != b:
            raise SmokeFailure(f"greedy answers differ: {a} vs {b}")
    with Phase("chat_stream") as p:
        p.update(chat_stream(base, hdrs, model, prompt, max_tokens, timeout))
    with Phase("chat_long_prompt") as p:
        long = chat(
            base, hdrs, model, seeded_text(SEED, long_prompt_chars),
            max_tokens, timeout, **GREEDY,
        )
        check_finite(long, "long prompt")
        p["usage"] = long["usage"]
        p["includes_compile"] = True
        if long["usage"]["prompt_tokens"] < min_long_prompt_tokens:
            raise SmokeFailure(
                f"long prompt came back with {long['usage']} — wanted "
                f">= {min_long_prompt_tokens} prompt tokens"
            )
    with Phase(f"chat_concurrent_{concurrent}") as p:
        results = chat_many(
            base, hdrs, model,
            [f"{prompt} Request number {i}." for i in range(concurrent)],
            max_tokens, timeout, **GREEDY,
        )
        for i, r in enumerate(results):
            check_finite(r, f"concurrent request {i}")
        p["completion_tokens"] = [
            r["usage"]["completion_tokens"] for r in results
        ]
        p["request_seconds"] = [r["_seconds"] for r in results]
    out["first"] = first
    out["long"] = long
    return out


# ---------------------------------------------------------------------------
# Control plane
# ---------------------------------------------------------------------------


def login(base: str, password: str) -> Dict[str, str]:
    body = expect(
        http("POST", f"{base}/auth/login",
             {"username": "admin", "password": password}),
        200, "login",
    )
    return {"Authorization": f"Bearer {body['token']}"}


def worker_endpoints(data_dir: str) -> Dict[int, Tuple[str, str]]:
    """{worker id: (base URL, proxy secret)} from the server's own
    database — the management API redacts the secret, the operator who
    owns the data directory can read it."""
    con = sqlite3.connect(
        f"file:{os.path.join(data_dir, 'state.db')}?mode=ro", uri=True,
        timeout=10,
    )
    try:
        rows = con.execute("SELECT id, data FROM worker").fetchall()
    finally:
        con.close()
    out = {}
    for wid, data in rows:
        w = json.loads(data)
        out[wid] = (f"http://{w['ip']}:{w['port']}", w["proxy_secret"])
    return out


def engine_health(
    workers: Dict[int, Tuple[str, str]], inst: Dict[str, Any]
) -> Dict[str, Any]:
    """The engine's own /healthz, read through its worker's proxy."""
    url, secret = workers[inst["worker_id"]]
    return expect(
        http("GET", f"{url}/proxy/instances/{inst['id']}/healthz",
             headers={"Authorization": f"Bearer {secret}"}),
        200, f"engine health of instance {inst['id']}",
    )


def instances_of(base: str, hdrs: Dict[str, str], model_id: int):
    items = expect(
        http("GET", f"{base}/v2/model-instances", headers=hdrs),
        200, "list instances",
    )["items"]
    return [i for i in items if i["model_id"] == model_id]


def deploy(
    base: str, hdrs: Dict[str, str], spec: Dict[str, Any], deadline: float
) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """POST /v2/models, wait until every replica is ``running``."""
    model = expect(
        http("POST", f"{base}/v2/models", spec, hdrs), 201, "create model"
    )
    want = spec.get("replicas", 1)

    def all_running():
        insts = instances_of(base, hdrs, model["id"])
        for i in insts:
            if i["state"] == "error":
                raise SmokeFailure(
                    f"instance {i['id']} in error: {i['state_message']}"
                )
        running = [i for i in insts if i["state"] == "running"]
        return running if len(running) == want else None

    return model, poll(
        f"{want} instance(s) of {spec['name']} running", deadline, all_running
    )


def delete_model(
    base: str, hdrs: Dict[str, str], model_id: int, deadline: float
) -> None:
    status, body = http("DELETE", f"{base}/v2/models/{model_id}", headers=hdrs)
    if status not in (200, 204):
        raise SmokeFailure(f"delete model: HTTP {status}: {body}")
    poll(
        "instances retired", deadline,
        lambda: not instances_of(base, hdrs, model_id),
    )


def instance_logs(base: str, hdrs: Dict[str, str], inst_id: int) -> str:
    status, body = http(
        "GET", f"{base}/v2/model-instances/{inst_id}/logs?tail=4000",
        headers=hdrs,
    )
    return body if isinstance(body, str) else json.dumps(body)


def check_device(health: Dict[str, Any], count: int) -> Dict[str, Any]:
    dev = health.get("device") or {}
    if health.get("error") or health.get("status") != "ok":
        raise SmokeFailure(f"engine reports an error: {health.get('error')}")
    if dev.get("platform") != PLATFORM or dev.get("count") != count:
        raise SmokeFailure(
            f"engine runs on {dev}, wanted platform {PLATFORM!r} "
            f"x{count}"
        )
    return dev


# ---------------------------------------------------------------------------
# Processes
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def descendants(pid: int) -> Dict[int, str]:
    """{pid: cmdline} of every live descendant of ``pid`` (from /proc)."""
    parent: Dict[int, int] = {}
    cmd: Dict[int, str] = {}
    for path in glob.glob("/proc/[0-9]*"):
        try:
            with open(f"{path}/stat") as f:
                stat = f.read()
            with open(f"{path}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ").decode().strip()
        except OSError:
            continue
        rest = stat[stat.rindex(")") + 2:].split()
        if rest[0] == "Z":
            continue
        p = int(os.path.basename(path))
        parent[p] = int(rest[1])
        cmd[p] = cmdline
    out: Dict[int, str] = {}
    frontier = [pid]
    while frontier:
        cur = frontier.pop()
        for p, pp in parent.items():
            if pp == cur and p not in out:
                out[p] = cmd[p]
                frontier.append(p)
    return out


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            stat = f.read()
    except OSError:
        return False
    return stat[stat.rindex(")") + 2:].split()[0] != "Z"


def tail(path: str, n_bytes: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n_bytes))
            return f.read().decode("utf-8", errors="replace")
    except OSError as e:
        return f"<{e}>"


def native_tools() -> Dict[str, str]:
    """Which of the optional native tools the start path will find. They
    are built by ``make -C native`` and never committed; without them the
    Python fallbacks run."""
    out = {}
    for tool in ("model-meta", "sysinfo"):
        path = os.path.join(ROOT, "native", "bin", tool)
        out[tool] = path if os.path.exists(path) else "python fallback"
    return out


class Cluster:
    """``python -m gpustack_tpu start`` as a child process."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.data_dir = os.path.join(out_dir, "data")
        self.log_path = os.path.join(out_dir, "server.log")
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: Optional[subprocess.Popen] = None
        self.engine_pids: Dict[int, str] = {}

    def start(self) -> None:
        os.makedirs(self.data_dir, exist_ok=True)
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "gpustack_tpu", "start",
                "--host", "127.0.0.1", "--port", str(self.port),
                "--worker-port", "0", "--worker-ip", "127.0.0.1",
                "--data-dir", self.data_dir,
                "--registration-token", "chip-smoke-token",
                "--bootstrap-password", ADMIN_PASSWORD,
                *SERVER_ARGS,
            ],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def check_alive(self) -> None:
        if self.proc is None or self.proc.poll() is not None:
            rc = self.proc.returncode if self.proc else None
            raise SmokeFailure(f"server process exited with code {rc}")

    def note_engines(self) -> Dict[int, str]:
        found = {
            pid: cmd for pid, cmd in descendants(self.proc.pid).items()
            if "gpustack_tpu.engine" in cmd
        }
        self.engine_pids.update(found)
        return found

    def stop(self, grace: float = 60.0) -> None:
        """SIGTERM, wait; then see that no engine process is left."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.note_engines()
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(
                    f"server did not exit {grace:.0f}s after SIGTERM"
                )
        deadline = time.time() + 20
        while time.time() < deadline:
            left = [p for p in self.engine_pids if alive(p)]
            if not left:
                return
            time.sleep(0.5)
        raise SmokeFailure(
            "engine process(es) outlived the server (would hold the "
            f"chip): {[(p, self.engine_pids[p][:120]) for p in left]}"
        )

    def kill(self) -> None:
        """Last resort, always run: nothing this script started stays."""
        if self.proc is not None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                pass
        for pid in self.engine_pids:
            if alive(pid):
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        if getattr(self, "log", None):
            self.log.close()

    def dump_logs(self) -> None:
        sys.stderr.write(
            f"\n===== tail of {self.log_path} =====\n{tail(self.log_path)}\n"
        )
        logs = glob.glob(
            os.path.join(self.data_dir, "**", "*.log"), recursive=True
        )
        for path in sorted(logs, key=os.path.getmtime)[-4:]:
            sys.stderr.write(f"\n===== tail of {path} =====\n{tail(path)}\n")


def start_cluster(cluster: Cluster, chips: int, deadline: float):
    """Start the server, log in, wait for the worker and its chips."""
    with Phase("start_server") as p:
        p["native_tools"] = native_tools()
        cluster.start()

        def up():
            cluster.check_alive()
            return http("GET", f"{cluster.base}/healthz", timeout=5)[0] == 200

        poll("server /healthz", min(deadline, time.time() + 120), up, 0.5)
        hdrs = login(cluster.base, ADMIN_PASSWORD)
    with Phase("detect_chips") as p:
        def ready():
            cluster.check_alive()
            items = expect(
                http("GET", f"{cluster.base}/v2/workers", headers=hdrs),
                200, "list workers",
            )["items"]
            return [w for w in items if w["state"] == "ready"]

        worker = poll(
            "the embedded worker", min(deadline, time.time() + 90), ready
        )[0]
        found = worker["status"]["chips"]
        p["chips"] = [
            {k: c.get(k) for k in ("index", "chip_type", "hbm_bytes")}
            for c in found
        ]
        if len(found) != chips:
            raise SmokeFailure(
                f"the detector found {len(found)} TPU chip(s), this run "
                f"needs {chips}"
            )
    return hdrs


def engine_chips(pids: Dict[int, str]) -> Dict[int, str]:
    """{engine pid: the TPU_VISIBLE_CHIPS its worker gave it}."""
    out = {}
    for pid in pids:
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = dict(
                    kv.split("=", 1)
                    for kv in f.read().decode(errors="replace").split("\0")
                    if "=" in kv
                )
        except OSError:
            env = {}
        out[pid] = env.get("TPU_VISIBLE_CHIPS", "")
    return out


def check_four_chips(chips: Dict[int, str], bytes_in_use: List[int]) -> None:
    """Four engine processes alive at once, each given another chip, and
    each holding its own copy of the model: an isolated one-chip process
    numbers its device 0, so the ids in the health cannot tell chips
    apart — four ~10 GB residents cannot share one 16 GB chip."""
    if sorted(chips.values()) != ["0", "1", "2", "3"]:
        raise SmokeFailure(
            f"wanted four engine processes on chips 0-3, got {chips}"
        )
    if len(bytes_in_use) != 4 or min(bytes_in_use) < 8e9:
        raise SmokeFailure(
            f"each replica should hold the ~8.2 GB int8 model on its own "
            f"chip; bytes in use: {bytes_in_use}"
        )


def check_memory_spread(in_use: List[int]) -> None:
    """The tp4 replica's ~8.2 GB int8 tree and its cache lie a quarter
    on each chip — "everything on the first chip" would show here."""
    if len(in_use) != 4 or max(in_use) > 2.5 * min(in_use) or (
        max(in_use) > 6e9
    ):
        raise SmokeFailure(
            f"the tp4 replica's memory is not spread: {in_use}"
        )


def claim_of(inst: Dict[str, Any]) -> Dict[str, Any]:
    """What the scheduler computed for this instance: chips, mesh plan,
    HBM bytes per chip, weight and KV bytes."""
    return {
        "instance": inst["id"],
        "chip_indexes": inst.get("chip_indexes"),
        **(inst.get("computed_resource_claim") or {}),
    }


def peak_memory(health: Dict[str, Any]) -> List[Dict[str, Any]]:
    return (health.get("device") or {}).get("memory") or []


# ---------------------------------------------------------------------------
# The runs
# ---------------------------------------------------------------------------


def run_one_chip(cluster: Cluster, deadline: float) -> Dict[str, Any]:
    hdrs = start_cluster(cluster, 1, deadline)
    base = cluster.base
    with Phase("deploy_to_running") as p:
        p["spec"] = MODEL_SPEC
        model, insts = deploy(
            base, hdrs, {**MODEL_SPEC, "replicas": 1}, deadline
        )
        inst = insts[0]
        workers = worker_endpoints(cluster.data_dir)
        health = engine_health(workers, inst)
        p["scheduler_claim"] = claim_of(inst)
        p["engine_device"] = health.get("device")
        check_device(health, 1)
        cluster.note_engines()
    exercise_chat(
        base, hdrs, MODEL_NAME,
        long_prompt_chars=1300, min_long_prompt_tokens=1100,
    )
    with Phase("engine_health") as p:
        health = engine_health(workers, inst)
        dev = check_device(health, 1)
        p["device"] = dev
        p["tokens_generated"] = health.get("tokens_generated")
        p["prompt_tokens"] = health.get("prompt_tokens")
        p["peak_device_memory"] = peak_memory(health)
        if not health.get("tokens_generated"):
            raise SmokeFailure("engine generated no tokens")
        logs = instance_logs(base, hdrs, inst["id"])
        flash_lines = [
            ln for ln in logs.splitlines() if "attention impl" in ln
        ]
        p["attention_log"] = [ln[-80:] for ln in flash_lines]
        if not any(FLASH_LOG_LINE in ln for ln in flash_lines):
            raise SmokeFailure(
                "the engine's log does not show the flash kernel chosen "
                f"for the 2048 bucket: {flash_lines}"
            )
    with Phase("shutdown") as p:
        p["engine_pids"] = sorted(cluster.note_engines())
        if not p["engine_pids"]:
            raise SmokeFailure("no engine process found under the server")
        cluster.stop()
        p["engines_gone"] = True
    return dev


def run_four_chips(cluster: Cluster, deadline: float) -> Dict[str, Any]:
    """(a) four one-chip replicas behind the router, (b) one tp4 replica,
    compared with (a)'s one-chip answer. Nothing else."""
    hdrs = start_cluster(cluster, 4, deadline)
    base = cluster.base
    prompt = "Say hello to the chips."
    with Phase("replicas4_deploy_to_running") as p:
        model, insts = deploy(
            base, hdrs, {**MODEL_SPEC, "replicas": 4}, deadline
        )
        workers = worker_endpoints(cluster.data_dir)
        healths = {i["id"]: engine_health(workers, i) for i in insts}
        p["scheduler_claims"] = [claim_of(i) for i in insts]
        p["engine_devices"] = {
            k: h.get("device") for k, h in healths.items()
        }
        for h in healths.values():
            check_device(h, 1)
        p["engine_chips"] = engine_chips(cluster.note_engines())
        p["bytes_in_use"] = [
            m.get("bytes_in_use")
            for h in healths.values() for m in peak_memory(h)
        ]
        check_four_chips(p["engine_chips"], p["bytes_in_use"])
    with Phase("replicas4_same_answer_from_each") as p:
        # straight to each replica through its worker's proxy, so that
        # every one of the four answers this exact request
        answers = {}
        for i in insts:
            url, secret = workers[i["worker_id"]]
            data = chat(
                f"{url}/proxy/instances/{i['id']}",
                {"Authorization": f"Bearer {secret}"},
                MODEL_NAME, prompt, 8, 600.0, **GREEDY,
            )
            check_finite(data, f"replica {i['id']}")
            answers[i["id"]] = data
        ref_id = insts[0]["id"]
        one_chip = answers[ref_id]
        p["request_seconds"] = {k: a["_seconds"] for k, a in answers.items()}
        for k, a in answers.items():
            if answer_of(a) != answer_of(one_chip):
                raise SmokeFailure(
                    f"replica {k} answered {answer_of(a)}, replica "
                    f"{ref_id} answered {answer_of(one_chip)}"
                )
        p["identical"] = True
    with Phase("replicas4_sixteen_requests_spread") as p:
        before = {
            i["id"]: engine_health(workers, i)["tokens_generated"]
            for i in insts
        }
        for wave in range(4):
            chat_many(
                base, hdrs, MODEL_NAME,
                [
                    f"{seeded_text(n + 1, 40)} ({n})"
                    for n in range(wave * 4, wave * 4 + 4)
                ],
            )
        served = {
            i["id"]: engine_health(workers, i)["tokens_generated"]
            - before[i["id"]]
            for i in insts
        }
        p["tokens_generated_by_replica"] = served
        if not all(v > 0 for v in served.values()):
            raise SmokeFailure(f"a replica served nothing: {served}")
    with Phase("replicas4_delete") as p:
        delete_model(base, hdrs, model["id"], min(deadline, time.time() + 120))
        poll(
            "the four engine processes to exit",
            min(deadline, time.time() + 60),
            lambda: not [x for x in cluster.engine_pids if alive(x)],
        )
    with Phase("tp4_deploy_to_running") as p:
        spec = {
            **MODEL_SPEC, "name": MODEL_NAME + "-tp4", "replicas": 1,
            "mesh_plan": TP4_PLAN,
        }
        p["spec"] = spec
        model, insts = deploy(base, hdrs, spec, deadline)
        inst = insts[0]
        workers = worker_endpoints(cluster.data_dir)
        health = engine_health(workers, inst)
        p["scheduler_claim"] = claim_of(inst)
        p["engine_device"] = health.get("device")
        check_device(health, 4)
        cluster.note_engines()
    with Phase("tp4_vs_one_chip") as p:
        data = chat(base, hdrs, spec["name"], prompt, 8, 600.0, **GREEDY)
        check_finite(data, "tp4")
        got = first_token_top_logprobs(data)
        ref = first_token_top_logprobs(one_chip)
        p["tp4_first_token_top_logprobs"] = got
        p["one_chip_first_token_top_logprobs"] = ref
        p["max_abs_diff"] = max(abs(a - b) for a, b in zip(got, ref))
        p["tolerance"] = TP4_LOGPROB_TOL
        p["same_tokens_as_one_chip"] = (
            answer_of(data)["text"] == answer_of(one_chip)["text"]
            and len(answer_of(data)["logprobs"])
            == len(answer_of(one_chip)["logprobs"])
        )
        if len(got) != len(ref) or p["max_abs_diff"] > TP4_LOGPROB_TOL:
            raise SmokeFailure(
                f"tp4 first-token top log-probabilities {got} are not "
                f"within {TP4_LOGPROB_TOL} of the one-chip {ref}"
            )
        health = engine_health(workers, inst)
        dev = check_device(health, 4)
        mem = peak_memory(health)
        p["device"] = dev
        p["per_device_memory"] = mem
        check_memory_spread([m["bytes_in_use"] for m in mem])
    with Phase("shutdown") as p:
        p["engine_pids"] = sorted(cluster.note_engines())
        cluster.stop()
        p["engines_gone"] = True
    return dev


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: the four-replica and tp4 phases, and nothing else",
    )
    ap.add_argument(
        "--out", default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
        help="run directory (data dir, logs)",
    )
    args = ap.parse_args(argv)

    out_dir = os.path.join(args.out, f"chips{args.chips}-{int(time.time())}")
    os.makedirs(out_dir, exist_ok=True)
    deadline = time.time() + RUN_DEADLINE_S
    cluster = Cluster(out_dir)

    def on_alarm(signum, frame):
        raise SmokeFailure(f"run deadline of {RUN_DEADLINE_S:.0f}s passed")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(int(RUN_DEADLINE_S) + 30)
    t0 = time.time()
    try:
        run = run_one_chip if args.chips == 1 else run_four_chips
        dev = run(cluster, deadline)
    except BaseException as e:
        cluster.dump_logs()
        emit({
            "ok": False, "error": f"{type(e).__name__}: {e}"[:4000],
            "seconds": round(time.time() - t0, 1),
        })
        return 1
    finally:
        signal.alarm(0)
        cluster.kill()
    emit({"phase": "total", "seconds": round(time.time() - t0, 1)})
    emit({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["count"],
        },
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
