"""The readers of a capture's own summary (ISSUE 58): five shares of the
traced stretch, from ``last_capture`` of the engines' ``/healthz``, and
the scheduler's time off the CPU, from the step records. Hand-made
``ctx``; the twelve entries of ``BENCHMARK.json`` with their cells."""

import importlib.util
import json
import os

import pytest

from perfbench import capture_read, loadgen
from perfbench.run import reader_path

PB = os.path.dirname(os.path.abspath(loadgen.__file__))
ROOT = os.path.dirname(PB)

SHARES = {
    "device.idle_in_drain_pct": ("drain",),
    "device.idle_in_admit_pct": ("admit",),
    "device.idle_in_dispatch_pct": ("dispatch",),
    "device.idle_in_wait_pct": ("wait",),
    "device.idle_elsewhere_pct": (
        "chunk", "step_other", "between_steps", "unannotated",
    ),
}
OFFCPU = "sched.offcpu_ms_per_step_p50"
OPEN = ["qwen3-8b-int8.chat-open"]
CLOSED = [
    "qwen3-30b-a3b-int8-l12.rag-closed", "qwen3-8b-int8.rag-closed",
    "ax-k1-int8-ep16-l12.longdoc-closed",
]

# what an engine keeps of a capture of 16 steps of the MoE rag cell
CAPTURE = {
    "steps": 19, "devices": 1, "window_ms": 200.0, "idle_pct": 10.0,
    "idle_ms": {
        "drain": 1.0, "admit": 6.0, "chunk": 0.5, "dispatch": 9.0,
        "wait": 0.25, "step_other": 1.25, "between_steps": 0.5,
        "unannotated": 1.5,
    },
}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(PB, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def healths(*captures):
    return {"healths": [
        {"status": "ok", "last_capture": c} for c in captures
    ]}


@pytest.mark.parametrize("name,want", [
    ("device.idle_in_drain_pct", 0.5),
    ("device.idle_in_admit_pct", 3.0),
    ("device.idle_in_dispatch_pct", 4.5),
    ("device.idle_in_wait_pct", 0.125),
    ("device.idle_elsewhere_pct", 1.875),
])
def test_a_share_is_its_parts_over_the_window(name, want):
    assert reader(name)(healths(CAPTURE)) == want
    assert capture_read.share(healths(CAPTURE), *SHARES[name]) == want


def test_the_five_shares_add_up_to_the_capture_s_idle_share():
    ctx = healths(CAPTURE)
    assert sorted(p for parts in SHARES.values() for p in parts) == sorted(
        CAPTURE["idle_ms"]
    )
    assert sum(reader(name)(ctx) for name in SHARES) == pytest.approx(
        CAPTURE["idle_pct"]
    )


@pytest.mark.parametrize("name", sorted(SHARES))
def test_a_share_reads_nothing_where_the_program_says_nothing(name):
    read = reader(name)
    # the parent's /healthz: no such key; an engine that never captured
    assert read({"healths": [{"status": "ok"}]}) is None
    assert read(healths(None)) is None
    assert read({}) is None and read({"healths": []}) is None
    # a summary that failed, and a CPU run's (no chip's plane)
    assert read(healths({"error": "TimeoutExpired: ..."})) is None
    assert read(healths({"devices": 0, "steps": 17})) is None
    # a fleet: the mean over the replicas that have the numbers
    twice = dict(CAPTURE, idle_ms={k: 2 * v for k, v in CAPTURE["idle_ms"].items()})
    assert read(healths(CAPTURE, twice, {"error": "x"})) == pytest.approx(
        1.5 * read(healths(CAPTURE))
    )


def step(mode="decode", dur=6.0, wait=3.0, cpu=2.5):
    return {"mode": mode, "dur_ms": dur, "wait_ms": wait, "cpu_ms": cpu}


def test_time_off_the_cpu_is_the_median_step_less_wait_and_mean_cpu_time():
    read = reader(OFFCPU)
    records = [
        step(dur=6.0, wait=3.0, cpu=2.5),
        step("prefill", dur=300.0, wait=1.0, cpu=20.0),
        step(dur=2147.0, wait=1.0, cpu=6.0),        # held elsewhere, once
        step(dur=6.5, wait=3.0, cpu=3.5),
    ]
    # the decode steps' median host time, 3.5, less their mean CPU time,
    # 4.0; two engines' records, as a fleet cell hands them over
    assert read({"flights": [records[:2], records[2:]]}) == -0.5
    assert read({"flights": [[]]}) is None and read({}) is None
    # an engine from before the field gives nothing to read, and no error
    old = [{k: v for k, v in r.items() if k != "cpu_ms"} for r in records]
    assert read({"flights": [old]}) is None
    # a mixed ring reads the records that have it
    assert read({"flights": [old, records[3:]]}) == 0.0


def test_a_clock_that_ticks_coarser_than_a_step_still_reads_the_mean():
    """The chip's host keeps a thread's CPU time in ticks of 10 ms: a
    step of 6 ms reads 0.0 or 10.0. Thirty steps that each used 1 ms, three
    of them charged a tick: the median of the steps' own differences would
    read 3.0 (no CPU time at all); the reader reads 2.0."""
    records = [step(dur=6.0, wait=3.0, cpu=0.0) for _ in range(27)]
    records += [step(dur=6.0, wait=3.0, cpu=10.0) for _ in range(3)]
    assert reader(OFFCPU)({"flights": [records]}) == pytest.approx(2.0)


def test_the_twelve_are_declared_last_with_a_reader_and_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    names = [
        f"{name}{suffix}"
        for name in list(SHARES) + [OFFCPU] for suffix in (".open", ".closed")
    ]
    assert [m["name"] for m in bench["per_layer"][-12:]] == names
    for m in bench["per_layer"][-12:]:
        assert m["source"] == "program_span"    # never device_trace: run.py
        # would ask it inside capture_served, before healths exists
        assert m["layer"] == "scheduler (engine/engine.py)"
        assert m["better"] == "lower"
        assert m["unit"] == ("ms" if m["name"].startswith(OFFCPU) else "%")
        if m["name"].endswith(".open"):
            assert m["workloads"] == OPEN and m["moves"] == "itl_ms_p99"
        else:
            assert m["workloads"] == CLOSED and m["moves"] == "output_tok_s"
        assert set(m["workloads"]) <= cells
        # one reader serves both entries, by reader_path's fallback
        path = reader_path(m["name"])
        assert os.path.basename(path) == m["name"].rsplit(".", 1)[0] + ".py"
    # the end-to-end metric each names is one its cells report
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"][-12:]:
        assert set(m["workloads"]) <= set(by_name[m["moves"]]["workloads"])
