"""A capture's idle time by host span (ISSUE 58): hand-worked intervals
through ``observability/capture.py``, and one small capture cut on the
chip with its host plane, which the program's summary and the
benchmark's reduction read alike."""

import json
import os
import subprocess
import sys

import pytest

from gpustack_tpu.observability import capture
from gpustack_tpu.observability.flight import PHASES

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixtures", "v5e_moe_rag_3steps.xplane.pb")
TPU0 = "/device:TPU:0"


def _ops(*intervals):
    return [(f"%op.{i}", s, e - s) for i, (s, e) in enumerate(intervals)]


def _chip(ops, modules=()):
    return {TPU0: {"ops": ops, "modules": list(modules)}}


def _ns(summary):
    """The parts in nanoseconds (the summary's are milliseconds)."""
    return {
        k: round(v * 1e6, 3) for k, v in summary["idle_ms_by_span"].items()
    }


def _parts(**given):
    return {**dict.fromkeys(capture.PARTS, 0.0), **given}


def test_the_parts_are_the_phases_and_three_more():
    assert capture.PARTS == PHASES + (
        "step_other", "between_steps", "unannotated",
    )


def test_a_gap_inside_one_phase_is_that_phase_s():
    got = capture.summarize(
        _chip(_ops((0, 100), (140, 200))),
        [("sched.step", 50, 140), ("sched.dispatch", 90, 80)], {50: 3},
    )
    assert got["devices"] == 1 and got["steps"] == 1
    assert _ns(got) == _parts(dispatch=40.0)
    assert got["window_ms"] == 200e-6 and got["idle_ms"] == 40e-6
    assert got["idle_pct"] == 20.0
    assert got["gaps"] == [{
        "at_ms": 100e-6, "ms": 40e-6, "span": "dispatch", "step_num": 3,
        "after": "start", "before": "end",
    }]


def test_a_gap_is_cut_at_a_phase_boundary():
    got = capture.summarize(
        _chip(_ops((0, 100), (200, 300))),
        [("sched.step", 50, 200), ("sched.drain", 60, 70),
         ("sched.admit", 130, 40), ("sched.dispatch", 170, 60)],
    )
    # drain [100,130) admit [130,170) dispatch [170,200)
    assert _ns(got) == _parts(drain=30.0, admit=40.0, dispatch=30.0)
    # named by its longest piece
    assert got["gaps"][0]["span"] == "admit"
    assert got["gaps"][0]["step_num"] is None


def test_wait_inside_drain_takes_its_piece():
    got = capture.summarize(
        _chip(_ops((0, 100), (200, 300))),
        [("sched.step", 0, 300), ("sched.drain", 90, 120),
         ("sched.wait", 120, 50)],
    )
    # drain [100,120) wait [120,170) drain [170,200)
    assert _ns(got) == _parts(drain=50.0, wait=50.0)


def test_spans_that_open_together_nest_by_length():
    spans = [("sched.wait", 100, 50), ("sched.drain", 100, 100),
             ("sched.step", 100, 200)]
    for order in (spans, spans[::-1]):
        got = capture.summarize(_chip(_ops((0, 100), (300, 400))), order)
        assert _ns(got) == _parts(wait=50.0, drain=50.0, step_other=100.0)


def test_a_gap_across_two_steps():
    got = capture.summarize(
        _chip(_ops((0, 100), (400, 500))),
        [("sched.step", 0, 180), ("sched.dispatch", 50, 100),
         ("sched.step", 250, 200), ("sched.drain", 260, 100)],
        {0: 7, 250: 8},
    )
    # dispatch [100,150) step_other [150,180) between [180,250)
    # step_other [250,260) drain [260,360) step_other [360,400)
    assert _ns(got) == _parts(
        dispatch=50.0, step_other=80.0, between_steps=70.0, drain=100.0,
    )
    assert got["gaps"][0]["span"] == "drain"
    assert got["gaps"][0]["step_num"] == 8


def test_before_the_first_step_and_after_the_last_is_unannotated():
    got = capture.summarize(
        _chip(_ops((0, 100), (200, 300), (500, 600))),
        [("sched.step", 150, 100), ("sched.dispatch", 160, 20)],
    )
    # gap 1: unannotated [100,150) step_other [150,160) dispatch
    # [160,180) step_other [180,200); gap 2 [300,500) after the last step
    assert _ns(got) == _parts(
        unannotated=250.0, step_other=30.0, dispatch=20.0,
    )
    # and with no span at all every gap is
    bare = capture.summarize(_chip(_ops((0, 100), (200, 300))), [])
    assert bare["steps"] == 0 and _ns(bare) == _parts(unannotated=100.0)


@pytest.mark.parametrize("seed", range(8))
def test_the_parts_add_up_to_the_idle_time(seed):
    import random

    rng = random.Random(seed)
    t, ops = 0, []
    for _ in range(200):
        t += rng.randrange(0, 40)
        d = rng.randrange(1, 60)
        ops.append(("%op", float(t), float(d)))
        t += d
    spans, s = [], rng.randrange(0, 500)
    while s < t:
        d = rng.randrange(100, 900)
        spans.append(("sched.step", float(s), float(d)))
        a = s + rng.randrange(0, 30)
        for phase in ("drain", "admit", "dispatch"):
            w = rng.randrange(5, max(6, d // 4))
            if a + w > s + d:
                break
            spans.append((f"sched.{phase}", float(a), float(w)))
            if phase == "drain" and w > 10:
                spans.append(("sched.wait", float(a + 2), float(w - 5)))
            a += w + rng.randrange(0, 20)
        s += d + rng.randrange(0, 200)
    got = capture.summarize(_chip(ops), spans)
    parts = _ns(got)
    assert set(parts) == set(capture.PARTS)
    assert sum(parts.values()) == round(got["idle_ms"] * 1e6, 3)
    busy = sum(e - s for s, e in capture.merge_intervals(ops))
    assert got["idle_ms"] * 1e6 == pytest.approx(
        got["window_ms"] * 1e6 - busy
    )
    assert len(got["gaps"]) == capture.GAPS_KEPT
    assert got["gaps"] == sorted(got["gaps"], key=lambda g: -g["ms"])


def test_two_chips_are_averaged_and_the_gaps_are_the_first_chip_s():
    devices = {
        "/device:TPU:1": {"ops": _ops((0, 100), (300, 400)), "modules": []},
        TPU0: {"ops": _ops((0, 100), (200, 400)), "modules": []},
        "/host:CPU": {"ops": _ops((0, 1)), "modules": []},
    }
    got = capture.summarize(
        devices, [("sched.step", 0, 400), ("sched.dispatch", 100, 150)]
    )
    assert got["devices"] == 2
    assert got["idle_ms"] == 150e-6 and got["window_ms"] == 400e-6
    assert got["idle_pct"] == 37.5       # the mean of 25 and 50
    assert _ns(got) == _parts(dispatch=125.0, step_other=25.0)
    assert [g["ms"] for g in got["gaps"]] == [100e-6]


def test_no_chip_s_plane_gives_no_idle_number():
    spans = [("sched.step", 0, 10), ("sched.step", 20, 10)]
    assert capture.summarize({}, spans) == {"devices": 0, "steps": 2}
    # nor does a chip's plane without operations
    assert capture.summarize(_chip([]), spans) == {"devices": 0, "steps": 2}


def test_a_gap_is_laid_beside_the_programs_on_either_side():
    modules = [
        ("jit__decode_impl(11)", 0, 100), ("jit_prefill_512(22)", 140, 500),
        ("jit__sample_first_impl(33)", 700, 100),
    ]
    got = capture.summarize(
        _chip(_ops((0, 100), (140, 300), (400, 640), (700, 800)), modules), []
    )
    named = {g["at_ms"]: (g["after"], g["before"]) for g in got["gaps"]}
    assert named == {
        100e-6: ("jit__decode_impl", "jit_prefill_512"),
        # inside a program's own event: the program is on both sides
        300e-6: ("jit_prefill_512", "jit_prefill_512"),
        640e-6: ("jit_prefill_512", "jit__sample_first_impl"),
    }


def test_the_digest_keeps_totals_and_parts_and_is_small():
    summary = capture.summarize(
        _chip(_ops((0, 123456789), (223456789.5, 323456789))),
        [("sched.step", 0, 323456789), ("sched.dispatch", 1e8, 1e8)],
    )
    digest = capture.digest(summary)
    assert set(digest) == {"steps", "devices", "window_ms", "idle_pct", "idle_ms"}
    assert set(digest["idle_ms"]) == set(capture.PARTS)
    assert digest["idle_pct"] == summary["idle_pct"]
    worst = dict(digest, steps=10000, window_ms=123456.789, idle_ms={
        part: 12345.678 for part in capture.PARTS
    })
    assert len(json.dumps(worst)) < 400
    assert capture.digest({"devices": 0, "steps": 4}) == {"devices": 0, "steps": 4}
    assert capture.digest({"error": "x" * 1000}) == {"error": "x" * 200}


# ---- the fixture: three steps of the MoE rag cell, cut on the chip -----


@pytest.fixture(scope="module")
def fixture_summary():
    return capture.summarize(**capture.read_xplane(FIXTURE))


def test_the_fixture_is_small_and_holds_the_scheduler_s_thread():
    assert os.path.getsize(FIXTURE) < 300_000
    got = capture.read_xplane(FIXTURE)
    assert list(got["devices"]) == [TPU0]
    steps = [e for e in got["spans"] if e[0] == capture.STEP_SPAN]
    assert 3 <= len(steps) <= 4
    assert sorted(got["step_nums"]) == sorted(s for _, s, _ in steps)
    assert {e[0] for e in got["spans"]} >= {"sched.step", "sched.dispatch"}


def test_summary_and_reduction_read_the_same_idle_share(fixture_summary):
    sys.path.insert(0, ROOT)
    from perfbench import trace_reduce

    reduced = trace_reduce.reduce_planes(trace_reduce.read_xplane(FIXTURE))
    (device,) = reduced["devices"]
    assert fixture_summary["devices"] == 1
    assert fixture_summary["idle_pct"] == pytest.approx(
        device["idle_pct"], abs=1e-4
    )
    assert fixture_summary["window_ms"] == pytest.approx(
        device["window_s"] * 1e3, abs=1e-6
    )
    # and its longest gap is the reduction's, between the same programs
    start_ns, dur_ns = device["gaps"][0]
    longest = fixture_summary["gaps"][0]
    assert longest["ms"] == pytest.approx(dur_ns / 1e6, abs=1e-6)
    assert longest["at_ms"] == pytest.approx(start_ns / 1e6, abs=1e-6)


def test_the_fixture_s_parts_add_up_and_its_gaps_have_names(fixture_summary):
    parts = fixture_summary["idle_ms_by_span"]
    assert sum(parts.values()) == pytest.approx(
        fixture_summary["idle_ms"], abs=1e-5
    )
    assert fixture_summary["steps"] >= 3
    for gap in fixture_summary["gaps"]:
        assert gap["span"] in capture.PARTS
        assert gap["after"].startswith("jit_") or gap["after"] == "start"


def test_the_module_prints_one_line_and_the_child_never_raises(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "gpustack_tpu.observability.capture", FIXTURE],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    (line,) = proc.stdout.splitlines()
    printed = json.loads(line)
    assert printed == capture.summarize_in_child(FIXTURE)
    assert printed["devices"] == 1 and len(printed["gaps"]) == capture.GAPS_KEPT
    # a directory with no trace, and a time limit that cuts the child
    assert "FileNotFoundError" in capture.summarize_in_child(str(tmp_path))["error"]
    assert "TimeoutExpired" in capture.summarize_in_child(FIXTURE, 0.01)["error"]
