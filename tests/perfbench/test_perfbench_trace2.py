"""``--trace 2``: one process measures a window untraced, then traces a
tail of the same traffic. The readers that came with it, on hand-made
step records; ``drive`` with a tail against a server that answers at
once; and the whole path through the CPU rehearsal."""

import asyncio
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from perfbench import loadgen
from perfbench.cluster import BenchFailure

PB = os.path.dirname(os.path.abspath(loadgen.__file__))
ROOT = os.path.dirname(PB)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(PB, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def step(mode="decode", dur=42.0, wait=38.0, **more):
    return {"mode": mode, "dur_ms": dur, "wait_ms": wait, "admitted": [],
            "first_tokens": [], "traced": 0, **more}


RECORDS = [
    step(dur=42.0, wait=40.0),
    step("prefill", dur=280.0, wait=1.0,
         admitted=[["a", 3.0], ["b", 250.0]], first_tokens=[["z", 300.0]]),
    step(dur=42.5, wait=37.5, first_tokens=[["a", 320.0], ["b", 700.0]]),
    step(dur=41.0, wait=40.0, admitted=[["c", 90.0]], traced=2),
]
# what an engine from before these fields reports
OLD = [{"mode": "decode", "dur_ms": 42.0}, {"mode": "prefill", "dur_ms": 80.0}]


@pytest.mark.parametrize("name,want", [
    ("sched.host_ms_per_step_p50", 2.0),       # decode steps: 2.0, 5.0, 1.0
    ("sched.admit_wait_ms_p50", 90.0),         # 3, 250, 90
    ("sched.first_token_ms_p50", 320.0),       # 300, 320, 700
    ("runner.programs_traced", 2.0),
])
def test_readers_of_the_step_records(name, want):
    read = reader(name)
    # two engines' records, as a fleet cell hands them over
    assert read({"flights": [RECORDS[:2], RECORDS[2:]]}) == want
    assert read({"flights": [[]]}) is None and read({}) is None
    # a program without the fields gives nothing to read, and no error
    assert read({"flights": [OLD]}) is None


def test_programs_traced_is_a_count_that_may_be_zero():
    assert reader("runner.programs_traced")({"flights": [RECORDS[:1]]}) == 0


def test_prefill_device_time_is_of_the_largest_bucket_traced():
    read = reader("runner.prefill_device_ms_p50")
    events = [
        ["jit__decode_impl", 1.0, 41.9e6], ["jit_prefill_1024", 5.0, 108e6],
        ["jit_prefill_2048", 9.0, 240e6], ["jit_prefill_2048", 11.0, 230e6],
        ["jit_prefill_2048", 12.0, 236e6], ["jit_prefill_embeds_4096", 13.0, 9e9],
        ["jit__insert_impl", 14.0, 1e6],
    ]
    ctx = {"traces": [{"devices": [{"module_events": events, "window_s": 20.0}]}]}
    assert read(ctx) == pytest.approx(236.0)
    # the largest bucket the plan's requests reach, if the run says which
    assert read({**ctx, "buckets": [1024, 2048, 4096]}) is None
    # the engine ran a bucket the plan does not reach: no capture of this
    # run would ever serve, and the run says so at the first
    with pytest.raises(BenchFailure, match=r"\[1024, 2048\].*reach \[512, 1024\]"):
        read({**ctx, "buckets": [512, 1024]})
    # the parent's trace names every prefill program jit__unknown
    old = [["jit__unknown", 1.0, 236e6], ["jit__decode_impl", 2.0, 41.9e6]]
    assert read({"traces": [{"devices": [{"module_events": old, "window_s": 1.0}]}]}) is None
    assert read({"traces": []}) is None and read({}) is None


def test_prefill_device_time_leaves_out_a_program_the_trace_cuts():
    """A trace begins and ends inside a program (the chip is never idle
    under load): PR 27's runs read 208.18, 83.35 and 0.01 ms where the
    whole program takes 268.1."""
    read = reader("runner.prefill_device_ms_p50")
    events = [
        ["jit_prefill_2048", -0.0, 264.2e6], ["jit__decode_impl", 264.3e6, 24.1e6],
        ["jit_prefill_2048", 288.4e6, 268.1e6], ["jit__decode_impl", 556.5e6, 24.1e6],
        ["jit_prefill_2048", 580.6e6, 100.0e6],
    ]
    device = {"module_events": events, "window_s": 0.6806}
    assert read({"traces": [{"devices": [device]}]}) == pytest.approx(268.1)
    # nothing whole: nothing to read
    cut = {"module_events": [events[0][:2] + [680.6e6]], "window_s": 0.6806}
    assert read({"traces": [{"devices": [cut]}]}) is None


def test_every_new_metric_has_its_reader_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["trace_in_run"] is True
    by_name = {m["name"]: m for m in bench["per_layer"]}
    closed = {w["name"] for w in bench["workloads"] if w["traffic"] == "rag-closed"}
    opened = {w["name"] for w in bench["workloads"] if w["traffic"] == "chat-open"}
    for name, cells, moves in [
        ("sched.host_ms_per_step_p50.open", opened, "itl_ms_p99"),
        ("sched.host_ms_per_step_p50.closed", closed, "output_tok_s"),
        ("sched.admit_wait_ms_p50.closed", closed, "ttft_ms_p50"),
        ("sched.first_token_ms_p50.closed", closed, "ttft_ms_p50"),
        ("runner.prefill_device_ms_p50.closed", closed, "ttft_ms_p50"),
        ("runner.programs_traced.open", opened, "itl_ms_p99"),
        ("runner.programs_traced.closed", closed, "ttft_ms_p50"),
    ]:
        m = by_name[name]
        assert set(m["workloads"]) == cells and m["moves"] == moves
        reader(name.rsplit(".", 1)[0])


# ---- drive with a tail ----------------------------------------------------


async def _serve(handler):
    from aiohttp import web

    app = web.Application()
    app.router.add_post("/v1/chat/completions", handler)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


async def _answer(request):
    """``max_tokens`` one-character chunks, 2 ms apart, then usage."""
    from aiohttp import web

    body = await request.json()
    resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
    await resp.prepare(request)
    n = int(body["max_tokens"])
    for _ in range(n):
        chunk = {"choices": [{"delta": {"content": "x"}}]}
        await resp.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
        await asyncio.sleep(0.002)
    usage = {"choices": [], "usage": {"completion_tokens": n}}
    await resp.write(b"data: " + json.dumps(usage).encode() + b"\n\n")
    await resp.write(b"data: [DONE]\n\n")
    return resp


OPEN = {"loop": "open", "arrivals": {"process": "poisson"}, "tail_s": 0.1,
        "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.1, "min": 30, "max": 60},
        "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.1, "min": 3, "max": 6}}
CLOSED = {**OPEN, "loop": "closed", "clients": 2, "pool": 8, "round": 4}


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_a_tail_carries_the_traffic_on_and_scores_nothing_of_it(mix):
    seconds, tail_s = 0.6, 0.5
    planned = (
        loadgen.plan_open(mix, 20.0, seconds, 7) if mix["loop"] == "open"
        else loadgen.plan_requests(mix, mix["pool"], 7)
    )
    seen = {}

    async def tail(window):
        seen["results_at_close"] = len(window.results)
        await asyncio.sleep(tail_s)
        seen["results_at_cut"] = len(window.results)

    async def go():
        runner, base = await _serve(_answer)
        try:
            with_tail = await loadgen.drive(
                base, {}, "m", mix, planned, seconds, None, tail)
            without = await loadgen.drive(base, {}, "m", mix, planned, seconds)
        finally:
            await runner.cleanup()
        return with_tail, without

    with_tail, without = asyncio.run(go())
    t_end = with_tail.t0 + seconds
    late = [r for r in with_tail.results if r.due >= t_end]
    # the traffic went on through the tail: requests due after the close
    assert late and seen["results_at_cut"] > seen["results_at_close"]
    if mix["loop"] == "open":
        # the open loop began its plan again at the window's end
        first_round = sorted(r.due - with_tail.t0 for r in with_tail.results if r.due < t_end)
        again = sorted(r.due - t_end for r in late)
        assert first_round == pytest.approx([p.due_s for p in planned])
        assert again == pytest.approx(first_round[:len(again)], abs=1e-6)
    red = loadgen.reduce_window(with_tail, mix)
    assert red["attempted"] == len(with_tail.results) - len(late)
    assert red["seconds"] == seconds and red["failed"] == 0
    # nothing after the close is in any number of the window
    in_window = [r for r in with_tail.results if r.due < t_end]
    assert red["tokens"] == sum(
        1 for r in in_window for t in r.chunk_times if t <= t_end)
    assert red["completed"] == sum(1 for r in in_window if r.done and r.end <= t_end)
    # and a run without a tail counts its window the same way
    ref = loadgen.reduce_window(without, mix)
    assert not [r for r in without.results if r.due >= without.t0 + seconds]
    if mix["loop"] == "open":
        assert ref["attempted"] == red["attempted"] == len(planned)
        assert ref["completed"] == red["completed"]


# ---- the whole path, on the CPU ---------------------------------------------


def rehearse(cell, trace, cache_dir, seconds="3"):
    # a compile cache of the run's own, as in test_perfbench_contract.py
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "run.py"), "--workload", cell,
         "--rehearse", "--seed", "3000000001", "--seconds", seconds,
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    return lines[-1], lines[:-1]


@pytest.mark.parametrize("which", ["open", "closed"])
def test_rehearsal_of_trace_2_prints_the_contracts_last_line(which, tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(
        w for w in bench["workloads"]
        if loadgen.load_traffic(w["traffic"], PB)["loop"] == which
    )
    last, log = rehearse(cell["name"], 2, tmp_path / "jax_cache")
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0, (last, log[-4:])
    assert last["metrics"] == {} and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["compiled_in_window"] == 0
    counts = last["counts"]
    # counts of both kinds: the client's window and the program's records
    assert counts["tokens"] > 0 and counts["ttft_samples"] >= 1
    assert counts["flight_records"] > 0 and counts["hops"] >= 1
    # the capture came after the window and ran its steps
    assert counts["profiles"] == [16]
    phases = [l.get("phase") for l in log]
    assert phases.index("profiler_first_start") < phases.index("window")
    # every per-layer metric the cell declares went through its reader:
    # it either found something in this run's records (a CPU run prints
    # no number, the log names the metric) or the log says it was left out
    declared = {
        m["name"] for m in bench["per_layer"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    }
    read = set(next(l for l in log if l.get("phase") == "metrics_read")["names"])
    left_out = {l["name"] for l in log if l.get("phase") == "metric_left_out"}
    assert read | left_out == declared and not read & left_out
    # what needs the chip's plane of the trace, and nothing else
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert {n for n in declared if by_name[n]["source"] == "device_trace"} <= left_out
    assert all(by_name[n]["source"] == "device_trace" or n.startswith("device.") for n in left_out)
    # a CPU run's one capture stands (its trace has no chip's plane to judge)
    assert "capture_retaken" not in phases
    window = next(l for l in log if l.get("phase") == "window")
    assert window["seconds"] == 3.0           # whole, not cut at the capture
    assert last["attempted"] == window["attempted"] >= last["completed"] >= 1
    if which == "open":
        # what --trace 0 counts for this seed: every request of the plan
        # is due inside the window, none of the tail's is scored
        dep = json.load(open(os.path.join(PB, "rehearsal", "deployment.json")))
        mix = loadgen.load_traffic(cell["traffic"], PB)
        planned = loadgen.plan_open(
            mix, dep["rate_rps"], 3.0, 3000000001, dep["length_scale"])
        assert last["attempted"] == len(planned) == counts["hops"]
