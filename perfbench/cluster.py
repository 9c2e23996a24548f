"""The system under test as the benchmark starts it: ``python -m
gpustack_tpu start`` as a child process, and the calls of its public API
that bring one deployment to ``running``.

A copy of ``chip_smoke.py``'s lifecycle code (``Cluster``, ``login``,
``deploy``, ``engine_health``, ``worker_endpoints``, process clean-up),
which ran on the chip in PR 23 — copied, not imported, so that a later PR
cannot move the yardstick by editing that script. Stdlib only, and never
JAX: a parent that touched JAX would hold the chip the engine child needs.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import socket
import sqlite3
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ADMIN_PASSWORD = "perfbench-admin"


class BenchFailure(Exception):
    """The run cannot give a result; the process exits non-zero."""


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
        raise BenchFailure(f"{what}: HTTP {status}: {str(body)[:800]}")
    return body


def poll(what: str, deadline: float, fn: Callable[[], Any], every: float = 0.5):
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
    raise BenchFailure(f"deadline passed waiting for {what} ({last_err})")


def login(base: str, password: str = ADMIN_PASSWORD) -> Dict[str, str]:
    body = expect(
        http("POST", f"{base}/auth/login",
             {"username": "admin", "password": password}),
        200, "login",
    )
    return {"Authorization": f"Bearer {body['token']}"}


def worker_endpoints(data_dir: str) -> Dict[int, Tuple[str, str]]:
    """{worker id: (base URL, proxy secret)} from the server's own
    database: the management API redacts the secret, the operator who
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


def engine_url(
    workers: Dict[int, Tuple[str, str]], inst: Dict[str, Any]
) -> Tuple[str, Dict[str, str]]:
    """(base URL, headers) of one engine, through its worker's proxy."""
    url, secret = workers[inst["worker_id"]]
    return (
        f"{url}/proxy/instances/{inst['id']}",
        {"Authorization": f"Bearer {secret}"},
    )


def engine_get(
    workers: Dict[int, Tuple[str, str]], inst: Dict[str, Any], path: str,
    timeout: float = 30.0,
) -> Any:
    base, hdrs = engine_url(workers, inst)
    return expect(
        http("GET", f"{base}{path}", headers=hdrs, timeout=timeout),
        200, f"engine {path} of instance {inst['id']}",
    )


def engine_health(
    workers: Dict[int, Tuple[str, str]], inst: Dict[str, Any]
) -> Dict[str, Any]:
    return engine_get(workers, inst, "/healthz")


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
                raise BenchFailure(
                    f"instance {i['id']} in error: {i['state_message']}"
                )
        running = [i for i in insts if i["state"] == "running"]
        return running if len(running) == want else None

    return model, poll(
        f"{want} instance(s) of {spec['name']} running", deadline, all_running
    )


def check_device(
    health: Dict[str, Any], platform: str, count: int = 1
) -> Dict[str, Any]:
    """The engine's own word on where it runs; anything but the wanted
    platform and count fails the run (no fallback to the CPU)."""
    dev = health.get("device") or {}
    if health.get("error") or health.get("status") != "ok":
        raise BenchFailure(f"engine reports an error: {health.get('error')}")
    if dev.get("platform") != platform or dev.get("count") != count:
        raise BenchFailure(
            f"engine runs on {dev}, wanted platform {platform!r} x{count}"
        )
    return dev


def peak_memory_bytes(health: Dict[str, Any]) -> int:
    """Peak bytes in use on the engine's fullest device (0 where the
    backend reports no memory statistics, as the CPU's does not)."""
    peaks = [
        int(m.get("peak_bytes_in_use") or m.get("bytes_in_use") or 0)
        for m in (health.get("device") or {}).get("memory") or []
    ]
    return max(peaks, default=0)


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


class Cluster:
    """``python -m gpustack_tpu start`` as a child process."""

    def __init__(self, out_dir: str, server_args: Sequence[str] = ()):
        self.out_dir = out_dir
        self.data_dir = os.path.join(out_dir, "data")
        self.log_path = os.path.join(out_dir, "server.log")
        self.server_args = tuple(server_args)
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: Optional[subprocess.Popen] = None
        self.engine_pids: Dict[int, str] = {}
        self.log = None

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
                "--registration-token", "perfbench-token",
                "--bootstrap-password", ADMIN_PASSWORD,
                *self.server_args,
            ],
            cwd=ROOT, env=env, stdout=self.log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def check_alive(self) -> None:
        if self.proc is None or self.proc.poll() is not None:
            rc = self.proc.returncode if self.proc else None
            raise BenchFailure(f"server process exited with code {rc}")

    def wait_ready(self, chips: int, deadline: float) -> Dict[str, str]:
        """Server up, admin logged in, the embedded worker ``ready`` with
        exactly ``chips`` chips. Returns the admin's headers."""

        def up():
            self.check_alive()
            return http("GET", f"{self.base}/healthz", timeout=5)[0] == 200

        poll("server /healthz", min(deadline, time.time() + 120), up, 0.25)
        hdrs = login(self.base)

        def ready():
            self.check_alive()
            items = expect(
                http("GET", f"{self.base}/v2/workers", headers=hdrs),
                200, "list workers",
            )["items"]
            return [w for w in items if w["state"] == "ready"]

        worker = poll(
            "the embedded worker", min(deadline, time.time() + 90), ready,
            0.25,
        )[0]
        found = worker["status"]["chips"]
        if len(found) < chips:
            raise BenchFailure(
                f"the detector found {len(found)} TPU chip(s), this cell "
                f"needs {chips}"
            )
        return hdrs

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
                raise BenchFailure(
                    f"server did not exit {grace:.0f}s after SIGTERM"
                )
        deadline = time.time() + 20
        left: List[int] = []
        while time.time() < deadline:
            left = [p for p in self.engine_pids if alive(p)]
            if not left:
                return
            time.sleep(0.25)
        raise BenchFailure(
            "engine process(es) outlived the server (would hold the "
            f"chip): {[(p, self.engine_pids[p][:120]) for p in left]}"
        )

    def kill(self) -> None:
        """Last resort, always run: nothing this benchmark started stays.
        An engine runs in a session of its own, and once the server is
        dead it is nobody's child: so look for engines first, on every
        path, also when a run failed before it had noted them."""
        if self.proc is not None:
            if self.proc.poll() is None:
                self.note_engines()
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
        deadline = time.time() + 10
        while time.time() < deadline and any(
            alive(p) for p in self.engine_pids
        ):
            time.sleep(0.1)
        if self.log is not None:
            self.log.close()
            self.log = None

    def dump_logs(self) -> None:
        sys.stderr.write(
            f"\n===== tail of {self.log_path} =====\n{tail(self.log_path)}\n"
        )
        logs = glob.glob(
            os.path.join(self.data_dir, "**", "*.log"), recursive=True
        )
        for path in sorted(logs, key=os.path.getmtime)[-4:]:
            sys.stderr.write(f"\n===== tail of {path} =====\n{tail(path)}\n")
