"""An engine process started through ``api_server.main`` says how it
came up: ``/debug/startup``, the ``startup`` object of ``/healthz``, the
step records' ``programs``, the log's times. One real process on the
tiny preset serves every test of this file; its compile cache is its
own and empty, so every program it meets is a miss it has to name."""

import json
import os
import re
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from gpustack_tpu.observability.startup import PHASES
from gpustack_tpu.observability.tracing import TRACEPARENT_ENV

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_ID = "0af7651916cd43dd8448eb211c80319c"
PARENT_SPAN = "b7ad6b7169203331"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url, body=None, timeout=60):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def _env(cache_dir, **more):
    env = dict(os.environ, JAX_PLATFORMS="cpu", GPUSTACK_TPU_PLATFORM="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir), **more)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """What one engine process said, before and after its first request."""
    tmp = tmp_path_factory.mktemp("engine_start")
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    log_path = tmp / "engine.log"
    spawned = time.time()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "gpustack_tpu.engine.api_server",
             "--preset", "tiny", "--host", "127.0.0.1", "--port", str(port),
             "--max-slots", "2", "--max-seq-len", "128"],
            cwd=ROOT, stdout=log, stderr=log,
            env=_env(tmp / "jax_cache", **{
                TRACEPARENT_ENV: f"00-{TRACE_ID}-{PARENT_SPAN}-01"}),
        )
    try:
        deadline = time.time() + 240
        while True:
            try:
                before = json.loads(_get(f"{base}/healthz", timeout=3))
                break
            except OSError:
                assert proc.poll() is None, log_path.read_text()[-3000:]
                assert time.time() < deadline, log_path.read_text()[-3000:]
                time.sleep(0.2)
        answered = time.time()
        startup_before = json.loads(_get(f"{base}/debug/startup"))
        body = {"prompt": "hello", "max_tokens": 4, "temperature": 0}
        _get(f"{base}/v1/completions", body)
        flight_first = json.loads(_get(f"{base}/debug/flight?limit=2048"))
        _get(f"{base}/v1/completions", body)       # shapes it has seen
        yield {
            "spawned": spawned, "answered": answered, "before": before,
            "startup_before": startup_before,
            "after": json.loads(_get(f"{base}/healthz")),
            "healthz_text": _get(f"{base}/healthz"),
            "startup": json.loads(_get(f"{base}/debug/startup")),
            "first": flight_first["records"],
            "records": json.loads(
                _get(f"{base}/debug/flight?limit=2048"))["records"],
            "metrics": _get(f"{base}/metrics"),
            "log": log_path,
        }
    finally:
        proc.terminate()
        try:
            proc.wait(20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(20)


def test_the_phases_are_in_order_and_leave_no_gap(served):
    d = served["startup"]
    assert [p["phase"] for p in d["phases"]] == list(PHASES)
    at = 0.0
    for p in d["phases"]:
        assert p["offset_ms"] == pytest.approx(at, abs=0.01), d["phases"]
        assert p["duration_ms"] >= 0.0
        at = p["offset_ms"] + p["duration_ms"]
    s = d["summary"]
    # they end before the first /healthz is answered, which is ``ready``
    assert sum(s["phases"].values()) <= s["ready_s"] + 0.01
    assert at / 1e3 == pytest.approx(sum(s["phases"].values()), abs=0.01)


def test_the_span_starts_with_the_process_not_with_main(served):
    s = served["startup"]["summary"]
    # the OS made the process when the test spawned it (ticks of 10 ms)
    assert served["spawned"] - 0.05 <= s["t0"] <= served["spawned"] + 1.0
    # the interpreter and the imports (jax among them) are the first phase
    assert s["phases"]["import"] > 0.5
    assert s["t0"] + s["ready_s"] <= served["answered"] + 0.01
    # the weights' phase holds the tree's programs, by name
    in_weights = [r for r in served["startup"]["programs"] if r["phase"] == "weights"]
    assert in_weights and s["phases"]["weights"] >= max(
        r["load"][1] - r["load"][0] for r in in_weights)


def test_ready_is_the_first_200_and_the_first_token_comes_later(served):
    before, after = served["before"]["startup"], served["after"]["startup"]
    assert before["first_token_s"] is None
    assert before["ready_s"] == after["ready_s"] > 0
    assert after["first_token_s"] > after["ready_s"]
    assert [e["event"] for e in served["startup_before"]["events"]] == ["ready"]
    assert served["startup_before"]["sealed"] is False
    d = served["startup"]
    assert [e["event"] for e in d["events"]] == ["ready", "first_token"]
    assert d["sealed"] is True
    assert d["events"][1]["offset_ms"] == pytest.approx(
        after["first_token_s"] * 1e3, abs=1.0)


def test_healthz_has_every_key_of_the_startup_object_in_under_400_bytes(served):
    s = served["after"]["startup"]
    assert set(s) == {"t0", "ready_s", "first_token_s", "phases", "programs"}
    assert set(s["phases"]) == set(PHASES)
    assert set(s["programs"]) == {
        "lowered", "lower_s", "load_s", "cache_misses", "retrieval_s"}
    without = {k: v for k, v in served["after"].items() if k != "startup"}
    grew = len(served["healthz_text"]) - len(json.dumps(without))
    assert 0 < grew < 400, grew
    # the counters PR 26 gave /healthz are the same log's, from the start
    h = served["after"]
    assert h["programs_traced_total"] == s["programs"]["lowered"]
    assert h["programs_compiled_total"] == s["programs"]["cache_misses"]
    assert h["compile_seconds_total"] == pytest.approx(
        s["programs"]["lower_s"] + s["programs"]["load_s"], abs=0.002)


def test_the_trace_is_the_parents_it_was_handed(served):
    d = served["startup"]
    assert d["trace_id"] == TRACE_ID and d["parent_id"] == PARENT_SPAN
    assert d["name"] == "engine_start" and d["component"] == "engine"
    assert d["model"] == "tiny"


def test_every_program_has_a_record_and_the_empty_cache_had_none(served):
    d = served["startup"]
    totals, records = d["summary"]["programs"], d["programs"]
    assert totals["lowered"] == len(records) < 100
    assert totals["cache_misses"] == sum(not r["cached"] for r in records)
    assert totals["cache_misses"] == totals["lowered"] > 10
    assert totals["retrieval_s"] == 0.0
    names = [r["name"] for r in records]
    for fn in ("prefill_32", "_decode_impl", "_insert_impl", "_sample_first_impl"):
        assert f"jit({fn})" in names, names
    serving = next(r for r in records if r["name"] == "jit(prefill_32)")
    assert serving["phase"] == "step"
    assert serving["trace"][1] <= serving["lower"][0] <= serving["load"][0]
    # unions: no more than the process has lived, no less than the longest
    life = served["after"]["startup"]["first_token_s"] + 60
    assert 0 < totals["lower_s"] < life and 0 < totals["load_s"] < life
    # the start's phases hold the weights' programs, the steps the serving ones
    phases = {r["phase"] for r in records}
    assert "weights" in phases and "step" in phases
    assert phases <= set(PHASES) | {"step"}


def test_a_step_that_lowers_carries_programs_and_a_steady_one_does_not(served):
    first = served["first"]
    lowering = [r for r in first if "programs" in r]
    assert lowering and lowering[0]["mode"] == "prefill"
    named = [p[0] for r in lowering for p in r["programs"]]
    assert "jit(prefill_32)" in named and "jit(_decode_impl)" in named
    for name, lower_ms, load_ms, cached in lowering[0]["programs"]:
        assert lower_ms >= 0 and load_ms >= 0 and cached is False
    assert sum(r["traced"] for r in lowering) >= len(named)
    # the second request met no new shape: the same keys as ever
    steady = [r for r in served["records"] if r["ts"] > first[-1]["ts"]]
    assert steady and all("programs" not in r for r in steady)
    assert all(r["traced"] == 0 for r in steady)
    # (a step's other keys depend on what it dispatched: ``kv_live_pct``
    # only with a decode step, which the first steady step may lack)
    assert set(lowering[0]) - set().union(*steady) == {"programs"}
    # and whatever a step carries, /debug/startup has by the same name
    known = {r["name"] for r in served["startup"]["programs"]}
    assert set(named) <= known


def test_the_gauge_family_is_on_metrics(served):
    lines = [l for l in served["metrics"].splitlines()
             if l.startswith("gpustack_engine_start_seconds")]
    got = {re.search(r'phase="(\w+)"', l).group(1): float(l.split()[-1])
           for l in lines}
    s = served["after"]["startup"]
    assert got == {**s["phases"], "ready": s["ready_s"],
                   "first_token": s["first_token_s"]}
    assert "# TYPE gpustack_engine_start_seconds gauge" in served["metrics"]


def test_the_log_lines_carry_a_time_and_two_state_the_phases(served):
    lines = served["log"].read_text().splitlines()
    stamped = re.compile(r"^\d{4}-\d\d-\d\d \d\d:\d\d:\d\d,\d{3} (INFO|WARNING) \S+: ")
    ours = [l for l in lines if " gpustack_tpu." in l]
    assert ours and all(stamped.match(l) for l in ours), ours[:3]
    listening = [l for l in ours if "engine_start" in l and "listening after" in l]
    first = [l for l in ours if "engine_start" in l and "first token after" in l]
    assert len(listening) == 1 and len(first) == 1
    for line in listening + first:
        assert f"trace={TRACE_ID}" in line
        for phase in PHASES:
            assert re.search(rf"{phase} \d+\.\d{{3}}", line), line
    assert lines.index(listening[0]) < lines.index(first[0])


SCRIPT = """
import json, sys
import numpy as np
from gpustack_tpu.observability.startup import process_programs
log = process_programs()
import jax
from gpustack_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()

def scale_and_shift(x):
    return x * 3.0 - 1.0

# read on the host: indexing a device array would be programs of its own
assert np.asarray(jax.jit(scale_and_shift)(np.ones(5, np.float32)))[0] == 2.0
print(json.dumps({"totals": log.totals(), "records": log.records()}))
"""


@pytest.mark.parametrize("run, misses", [("first", 1), ("second", 0)])
def test_the_cache_has_the_program_in_a_second_process(run, misses, tmp_path_factory):
    """The same jitted function, two processes, one cache directory:
    compiled in the first (``cached`` false, one miss), loaded in the
    second. Each case makes the runs it needs."""
    cache = tmp_path_factory.mktemp(f"cache_{run}")
    outs = []
    for _ in range(1 if run == "first" else 2):
        proc = subprocess.run(
            [sys.executable, "-c", SCRIPT], cwd=ROOT, env=_env(cache),
            capture_output=True, text=True, timeout=180,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    got = outs[-1]
    (rec,) = [r for r in got["records"] if r["name"] == "jit(scale_and_shift)"]
    assert rec["cached"] is (misses == 0)
    assert got["totals"]["cache_misses"] == misses
    assert got["totals"]["lowered"] == 1
    assert rec["lower"][0] <= rec["lower"][1] <= rec["load"][0] <= rec["load"][1]
    if misses == 0:
        assert 0 < rec["retrieval_s"] <= rec["load"][1] - rec["load"][0]
        assert got["totals"]["retrieval_s"] > 0
    else:
        assert rec["retrieval_s"] == 0.0
