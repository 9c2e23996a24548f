"""A capture says what the host was doing while the chip stood idle
(ISSUE 58): every step of a trace carries its spans, the capture's answer
and ``/healthz`` carry the program's own reading of the trace, a summary
that fails never fails the capture, and a step's record holds its
thread's CPU time. Hermetic: tiny model, CPU (whose trace has no chip's
plane: ``devices: 0`` and no idle number)."""

import threading
import time

import jax
import pytest

import gpustack_tpu.engine.engine as engine_mod
from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.models import init_params
from gpustack_tpu.models.config import get_config
from gpustack_tpu.observability import capture
from gpustack_tpu.observability.flight import PHASES, aggregate_records


def _engine():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    return LLMEngine(cfg, params, max_slots=4, max_seq_len=128)


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    eng.start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def stepped():
    """An engine nobody started: the test is its scheduler."""
    return _engine()


def _req(n=5, prompt=(5, 17, 42, 99, 7)):
    return GenRequest(
        prompt_ids=list(prompt), max_tokens=n, temperature=0.0,
        stop_ids=frozenset(),
    )


class _Traffic:
    """Requests one after another until told to stop, so that the engine
    steps before, through and after a capture."""

    def __init__(self, eng):
        self.eng, self.stop = eng, threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self.stop.is_set():
            reqs = [self.eng.submit(_req(n=40)) for _ in range(2)]
            for r in reqs:
                r.done.wait(timeout=60)

    def __enter__(self):
        self.thread.start()
        deadline = time.time() + 60
        while not self.eng.health()["tokens_generated"] and time.time() < deadline:
            time.sleep(0.01)
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self.thread.join(timeout=120)
        assert not self.thread.is_alive()


def _spans_by_step(trace_dir):
    """``[(step span, [the phase spans inside it])]`` of a trace."""
    got = capture.read_xplane(trace_dir)
    steps = sorted(
        (e for e in got["spans"] if e[0] == capture.STEP_SPAN),
        key=lambda e: e[1],
    )
    return got, [
        (step, [
            e for e in got["spans"]
            if e[0] != capture.STEP_SPAN
            and step[1] <= e[1] and e[1] + e[2] <= step[1] + step[2]
        ])
        for step in steps
    ]


def test_the_steps_round_a_capture_s_start_and_stop_carry_their_spans(
    stepped, tmp_path, monkeypatch
):
    """Two steps run after the profiler has started and before the
    capture is armed, two are counted, two run while the profiler is
    being stopped: all six are in the trace with their phases."""
    counted = threading.Event()
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def two_steps():
        assert stepped._profile is None
        assert stepped.step() and stepped.step()

    def start_then_step(out_dir):
        start(out_dir)
        two_steps()

    def step_then_stop():
        assert counted.wait(timeout=60)
        two_steps()
        stop()

    monkeypatch.setattr(jax.profiler, "start_trace", start_then_step)
    monkeypatch.setattr(jax.profiler, "stop_trace", step_then_stop)
    req = stepped.submit(_req(n=60))
    assert stepped.step()       # before the capture: no span
    result = {}
    thread = threading.Thread(
        target=lambda: result.update(
            stepped.capture_profile(2, out_dir=str(tmp_path), timeout_s=60)
        )
    )
    thread.start()
    deadline = time.time() + 60
    while stepped._profile is None and time.time() < deadline:
        time.sleep(0.001)
    while stepped._profile is not None:
        stepped.step()
    counted.set()
    thread.join(timeout=120)
    assert not thread.is_alive()
    assert result["profiler"] == "jax" and result["steps_captured"] == 2
    got, by_step = _spans_by_step(str(tmp_path))
    assert len(by_step) == 6
    for step, inside in by_step:
        names = {e[0] for e in inside}
        assert {"sched.drain", "sched.admit", "sched.chunk",
                "sched.dispatch"} <= names, step
        assert names <= {f"sched.{p}" for p in PHASES}
    nums = [got["step_nums"][step[1]] for step, _ in by_step]
    assert nums == list(range(nums[0], nums[0] + 6))
    assert result["idle"] == {"devices": 0, "steps": 6}
    # the capture is over: a later step enters no span
    assert stepped._phases.annotate is not None
    stepped.step()
    assert stepped._phases.annotate is None
    while not req.done.is_set():
        stepped.step()


def test_the_answer_and_healthz_carry_the_summary(engine, tmp_path):
    assert engine.health()["last_capture"] is None
    with _Traffic(engine):
        result = engine.capture_profile(4, out_dir=str(tmp_path), timeout_s=60)
    assert result["profiler"] == "jax" and result["steps_captured"] == 4
    got, by_step = _spans_by_step(str(tmp_path))
    # a step in flight when the profiler starts or stops leaves no
    # sched.step in the trace; every one that is there has its phases
    assert by_step
    for step, inside in by_step:
        assert {"sched.drain", "sched.admit", "sched.chunk"} <= {
            e[0] for e in inside
        }, step
    # the answer carries the program's own reading of that trace: a CPU
    # run's has no chip's plane
    assert result["idle"] == {"devices": 0, "steps": len(by_step)}
    assert engine.health()["last_capture"] == result["idle"]


def test_a_summary_that_fails_leaves_the_capture_whole(
    engine, tmp_path, monkeypatch
):
    real = capture.summarize_in_child
    monkeypatch.setattr(
        engine_mod._capture, "summarize_in_child",
        lambda path: real(path, timeout_s=0.01),
    )
    with _Traffic(engine):
        result = engine.capture_profile(2, out_dir=str(tmp_path), timeout_s=60)
    assert result["profiler"] == "jax" and result["steps_captured"] == 2
    assert result["artifact"] == str(tmp_path) and len(result["records"]) == 2
    assert capture.find_xplane(str(tmp_path)).endswith(".xplane.pb")
    assert set(result["idle"]) == {"error"}
    assert "TimeoutExpired" in result["idle"]["error"]
    assert engine.health()["last_capture"] == result["idle"]
    # and the next capture is not refused
    monkeypatch.setattr(engine_mod._capture, "summarize_in_child", real)
    with _Traffic(engine):
        again = engine.capture_profile(
            1, out_dir=str(tmp_path / "again"), timeout_s=60
        )
    assert again["idle"]["devices"] == 0 and "steps" in again["idle"]
    assert engine.health()["last_capture"] == again["idle"]


def test_a_flight_only_capture_is_not_summarised(engine, monkeypatch):
    monkeypatch.setattr(
        engine_mod._capture, "summarize_in_child",
        lambda path: pytest.fail("a capture without a trace was summarised"),
    )
    before = engine.health()["last_capture"]
    assert before is not None
    with _Traffic(engine):
        result = engine.capture_profile(2, out_dir="", timeout_s=60)
    assert result["profiler"] == "flight-only" and "idle" not in result
    assert engine.health()["last_capture"] == before


def test_each_gap_is_laid_beside_its_step_s_record(
    stepped, tmp_path, monkeypatch
):
    """The scheduler is this test: every step from the arming on has a
    record, in the order of the trace's ``sched.step`` spans."""
    def fake(path):
        got, by_step = _spans_by_step(path)
        nums = [got["step_nums"][step[1]] for step, _ in by_step]
        fake.nums = nums
        return {
            "devices": 1, "steps": len(nums),
            "gaps": [{"step_num": nums[1]}, {"step_num": None},
                     {"step_num": 10 ** 9}, {"step_num": nums[0]}],
        }

    monkeypatch.setattr(engine_mod._capture, "summarize_in_child", fake)
    result = {}
    thread = threading.Thread(
        target=lambda: result.update(
            stepped.capture_profile(3, out_dir=str(tmp_path), timeout_s=60)
        )
    )
    thread.start()
    deadline = time.time() + 30
    while stepped._profile is None and time.time() < deadline:
        time.sleep(0.005)
    req = stepped.submit(_req(n=12))
    for _ in range(400):
        if not stepped.step() and req.done.is_set():
            break
    thread.join(timeout=60)
    assert not thread.is_alive() and result["steps_captured"] == 3
    assert len(set(fake.nums)) == len(fake.nums) >= 3
    assert [g["record"] for g in result["idle"]["gaps"]] == [1, None, None, 0]
    # a summary without idle numbers still leaves its digest
    assert stepped.health()["last_capture"] == {
        "devices": 1, "steps": len(fake.nums),
    }


def test_every_record_has_its_thread_s_cpu_time(stepped):
    req = stepped.submit(_req(n=10))
    before = len(stepped.flight.snapshot(2048))
    for _ in range(400):
        if not stepped.step() and req.done.is_set():
            break
        if not req.done.is_set():
            time.sleep(0.0005)
    records = stepped.flight.snapshot(2048)[before:]
    assert len(records) >= 5
    for r in records:
        # read inside the step's wall interval
        assert 0.0 <= r["cpu_ms"] <= r["dur_ms"] + 0.05
    agg = aggregate_records(records, 4)
    decode = [r for r in records if r["mode"] == "decode"]
    assert agg["modes"]["decode"]["cpu_ms_mean"] == pytest.approx(
        sum(r["cpu_ms"] for r in decode) / len(decode), abs=1e-3
    )
    # records from before the field: no number, and no error
    old = [{k: v for k, v in r.items() if k != "cpu_ms"} for r in records]
    assert "cpu_ms_mean" not in aggregate_records(old, 4)["modes"]["decode"]


def test_a_step_that_sleeps_reads_its_sleep_as_time_off_the_cpu(
    stepped, monkeypatch
):
    admit = stepped._admit

    def slow_admit():
        time.sleep(0.05)       # off the CPU, and in no wait phase
        return admit()

    monkeypatch.setattr(stepped, "_admit", slow_admit)
    req = stepped.submit(_req(n=3))
    before = len(stepped.flight.snapshot(2048))
    for _ in range(400):
        if not stepped.step() and req.done.is_set():
            break
    records = stepped.flight.snapshot(2048)[before:]
    assert records
    for r in records:
        off = r["dur_ms"] - r["wait_ms"] - r["cpu_ms"]
        assert 45.0 <= off <= r["dur_ms"]
        assert r["admit_ms"] >= 50.0


def test_a_slow_step_s_warning_names_its_cpu_time(stepped, monkeypatch, caplog):
    monkeypatch.setattr(engine_mod, "_SLOW_STEP_S", 0.0)
    req = stepped.submit(_req(n=2))
    with caplog.at_level("WARNING", logger=engine_mod.__name__):
        for _ in range(400):
            if not stepped.step() and req.done.is_set():
                break
    lines = [
        r.getMessage() for r in caplog.records
        if "slow scheduler step" in r.getMessage()
    ]
    assert lines and " ms (cpu " in lines[0] and "wait " in lines[0]
