"""Engine flight recorder + on-demand profiler capture (ISSUE 7, 26):
the scheduler feeds one record per step with honest mode/token
accounting, the phases of the step, the requests it admitted and gave a
first token, and the programs lowered in it; the recorder's measured
overhead stays under 1% of step wall time on the CPU smoke; and
capture_profile wraps N steps in jax.profiler from its own thread, with
the step's spans in the trace while it is open. Hermetic: tiny model,
CPU."""

import os
import threading
import time

import jax
import pytest

from gpustack_tpu.observability.flight import PHASES

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.models import init_params
from gpustack_tpu.models.config import get_config
from gpustack_tpu.testing import promtext


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    eng = LLMEngine(cfg, params, max_slots=4, max_seq_len=64)
    eng.start()
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def stepped():
    """An engine nobody started: the test is its scheduler and calls
    ``step()`` itself, so what each step did is known."""
    cfg = get_config("tiny")
    params = init_params(cfg, jax.random.key(0))
    return LLMEngine(cfg, params, max_slots=4, max_seq_len=64)


def _step_until_done(eng, reqs, limit=400):
    """Step by hand until every request is done and the engine idle;
    the records of those steps."""
    before = eng.flight.tokens_out_total, len(eng.flight.snapshot(2048))
    for _ in range(limit):
        busy = eng.step()
        if not busy and all(r.done.is_set() for r in reqs):
            break
        if not busy:
            time.sleep(0.002)     # the detok worker sets ``done``
    assert all(r.done.is_set() for r in reqs)
    records = eng.flight.snapshot(2048)
    return records[before[1]:]


def _req(trace_id="", n=5, prompt=(5, 17, 42, 99, 7)):
    return GenRequest(
        prompt_ids=list(prompt), max_tokens=n, temperature=0.0,
        trace_id=trace_id,
    )


def test_phases_add_up_to_no_more_than_the_step(stepped):
    req = stepped.submit(_req())
    records = _step_until_done(stepped, [req])
    assert len(records) >= 3
    for r in records:
        phases = [r[f"{name}_ms"] for name in PHASES]
        assert all(p >= 0.0 for p in phases)
        # every field is rounded to 0.1 us on its own
        assert sum(phases) <= r["dur_ms"] + 1e-3, r
    # the first step admitted, prefilled and dispatched one decode with
    # nothing to fetch yet (the pipeline is two steps deep)
    first = records[0]
    assert first["mode"] == "prefill" and first["wait_ms"] == 0.0
    assert first["admit_ms"] > 0.0 and first["dispatch_ms"] > 0.0
    # later steps fetched: the sync point is where a step may wait
    assert any(r["wait_ms"] > 0.0 for r in records[1:])
    host = stepped.flight.aggregate()["modes"]["decode"]["host_ms_p50"]
    assert 0.0 < host <= stepped.flight.aggregate()["modes"]["decode"]["step_ms_p95"]


def test_each_request_is_admitted_and_first_served_once_under_its_id(stepped):
    reqs = [stepped.submit(_req(f"trace-{i}", n=3 + i)) for i in range(6)]
    records = _step_until_done(stepped, reqs)
    admitted = [a for r in records for a in r["admitted"]]
    firsts = [f for r in records for f in r["first_tokens"]]
    want = sorted(f"trace-{i}" for i in range(6))
    assert sorted(a[0] for a in admitted) == want
    assert sorted(f[0] for f in firsts) == want
    waits = dict(map(tuple, admitted))
    for tid, ms in firsts:
        # a first token comes after admission, and both are measured
        # from the same submission
        assert ms >= waits[tid] >= 0.0
    # six requests, four slots: two waited for a slot to come free
    assert sum(1 for r in records if r["admitted"]) >= 2
    # most steps admit nobody and carry empty lists
    assert any(not r["admitted"] and not r["first_tokens"] for r in records)
    for req in reqs:
        assert req.ttft_ms == pytest.approx(
            dict(map(tuple, firsts))[req.trace_id], abs=1e-2
        )


def test_a_new_shape_inside_a_step_shows_in_its_record_and_in_health(stepped):
    warm = stepped.submit(_req())
    _step_until_done(stepped, [warm])
    before = stepped.health()
    again = stepped.submit(_req())
    records = _step_until_done(stepped, [again])
    # shapes the engine has seen: nothing is lowered or compiled
    assert sum(r["traced"] for r in records) == 0
    assert stepped.health()["programs_traced_total"] == before["programs_traced_total"]
    # a prompt of the next bucket meets its prefill program for the
    # first time inside a step
    long = stepped.submit(_req(prompt=tuple(range(3, 43))))
    records = _step_until_done(stepped, [long])
    assert records[0]["mode"] == "prefill" and records[0]["traced"] >= 1
    assert sum(r["traced"] for r in records[1:]) == 0
    after = stepped.health()
    assert after["programs_traced_total"] >= before["programs_traced_total"] + 1
    assert after["programs_compiled_total"] >= before["programs_compiled_total"]
    assert after["compile_seconds_total"] > before["compile_seconds_total"]


def test_prefill_programs_are_named_by_their_bucket(stepped):
    done = stepped.submit(_req())
    _step_until_done(stepped, [done])
    runner = stepped.runner
    assert {b: fn.__name__ for b, fn in runner._prefills.items()} == {
        b: f"prefill_{b}" for b in runner._prefills
    }
    lowered = runner._prefills[32].lower(
        runner.params, jax.numpy.zeros((1, 32), jax.numpy.int32),
        jax.numpy.int32(5),
    )
    assert "module @jit_prefill_32 " in lowered.as_text()[:200]
    # the programs the trace's readers find by name keep theirs
    assert runner._decode.__name__ == "_decode_impl"
    assert runner._sample_first.__name__ == "_sample_first_impl"
    assert {fn.__name__ for fn in runner._inserts.values()} == {"_insert_impl"}


class _Spans:
    """Stands in for jax.profiler's two annotation classes and keeps what
    was entered."""

    def __init__(self):
        self.entered = []

    def annotation(self, name, **kw):
        spans = self

        class _Ann:
            def __enter__(self):
                spans.entered.append((name, kw))

            def __exit__(self, *exc):
                return None

        return _Ann()


def test_spans_enter_the_trace_only_while_a_capture_is_open(
    stepped, monkeypatch
):
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans.annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", spans.annotation)
    req = stepped.submit(_req())
    _step_until_done(stepped, [req])
    assert spans.entered == []

    result = {}
    capture = threading.Thread(
        target=lambda: result.update(
            stepped.capture_profile(3, out_dir="", timeout_s=30)
        )
    )
    capture.start()
    deadline = time.time() + 10
    while stepped._profile is None and time.time() < deadline:
        time.sleep(0.005)
    req = stepped.submit(_req(n=8))
    _step_until_done(stepped, [req])
    capture.join(timeout=30)
    assert not capture.is_alive() and result["steps_captured"] == 3
    steps = [kw for name, kw in spans.entered if name == "sched.step"]
    # the three asked for, and whatever ran before the capture was
    # released (in a traced capture: while the profiler stopped)
    assert len(steps) >= 3
    assert [s["step_num"] for s in steps] == sorted(s["step_num"] for s in steps)
    names = {name for name, _ in spans.entered}
    assert names <= {"sched.step"} | {f"sched.{p}" for p in PHASES}
    assert {"sched.step", "sched.drain", "sched.admit", "sched.dispatch"} <= names
    # the capture is over: later steps add nothing
    n = len(spans.entered)
    req = stepped.submit(_req())
    _step_until_done(stepped, [req])
    assert len(spans.entered) == n


def test_a_slow_step_is_logged_with_its_phases(stepped, monkeypatch, caplog):
    import gpustack_tpu.engine.engine as engine_mod

    monkeypatch.setattr(engine_mod, "_SLOW_STEP_S", 0.0)
    req = stepped.submit(_req(n=2))
    with caplog.at_level("WARNING", logger=engine_mod.__name__):
        _step_until_done(stepped, [req])
    lines = [r.getMessage() for r in caplog.records if "slow scheduler step" in r.getMessage()]
    assert lines and "mode prefill" in lines[0]
    assert all(f"{p} " in lines[0] for p in PHASES)


def _gen(engine, n=6, prompt=(5, 17, 42, 99, 7)):
    return engine.generate(
        GenRequest(
            prompt_ids=list(prompt), max_tokens=n, temperature=0.0
        ),
        timeout=120,
    )


def test_flight_records_prefill_and_decode(engine):
    _gen(engine)
    agg = engine.flight.aggregate()
    assert agg["steps"] > 0
    assert "prefill" in agg["modes"] and "decode" in agg["modes"]
    # the 5-token prompt prefilled into a padded bucket: waste > 0
    assert agg["tokens_padded"] > agg["tokens_real"] > 0
    assert agg["tokens_out"] > 0
    assert agg["prompt_tokens"] >= 5
    # health carries the same counters the exporter serves
    h = engine.health()
    assert h["prompt_tokens"] == engine.flight.prompt_tokens_total
    assert h["flight_overhead_ratio"] < 0.5


def test_flight_overhead_under_one_percent(engine):
    """ISSUE 7 acceptance: recorder overhead <1% of step wall time on
    the CPU stub smoke (real steps dispatch jit computations; the
    recorder appends one tuple)."""
    for _ in range(3):
        _gen(engine)
    ratio = engine.flight.overhead_ratio()
    assert 0.0 < ratio < 0.01, ratio


def test_engine_exporter_serves_flight_families(engine):
    """The engine /metrics text stays strictly parseable with the
    flight families present (gpustack_engine_step_seconds histogram by
    mode, dispatched real/padded counters, occupancy gauge)."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from gpustack_tpu.engine.api_server import OpenAIServer

    _gen(engine)

    async def go():
        server = OpenAIServer(engine, model_name="tiny-flight")
        client = TestClient(TestServer(server.app))
        await client.start_server()
        try:
            resp = await client.get("/metrics")
            assert resp.status == 200
            text = await resp.text()
            samples, types = promtext.assert_well_formed(
                text,
                require_histograms=["gpustack_engine_step_seconds"],
            )
            names = {s.name for s in samples}
            assert "gpustack_engine_dispatched_tokens_total" in names
            assert "gpustack_engine_occupancy_ratio" in names
            assert "gpustack_engine_queue_depth" in names
            assert "gpustack_engine_programs_traced_total" in names
            assert "gpustack_engine_compile_seconds_total" in names

            # /healthz carries the compile counters, and a request that
            # arrives under a hop trace is in the step records under
            # that trace's id
            resp = await client.get("/healthz")
            health = await resp.json()
            assert health["programs_traced_total"] >= 1
            assert health["programs_compiled_total"] >= 0
            trace_id = "ab" * 16
            resp = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "max_tokens": 2, "temperature": 0},
                headers={"traceparent": f"00-{trace_id}-{'cd' * 8}-01"},
            )
            assert resp.status == 200, await resp.text()
            resp = await client.get("/debug/flight?limit=50")
            records = (await resp.json())["records"]
            assert [a[0] for r in records for a in r["admitted"]].count(trace_id) == 1
            assert [f[0] for r in records for f in r["first_tokens"]].count(trace_id) == 1
            assert (await resp.json())["aggregate"]["modes"]["decode"]["host_ms_p50"] >= 0

            # raw ring + aggregates over HTTP
            resp = await client.get("/debug/flight?limit=10")
            assert resp.status == 200
            payload = await resp.json()
            assert payload["model"] == "tiny-flight"
            assert payload["records"]
            assert payload["aggregate"]["steps"] > 0
            assert payload["overhead_ratio"] < 0.01
        finally:
            await client.close()

    asyncio.run(go())


def _background_traffic(engine, n_reqs=3):
    def go():
        for _ in range(n_reqs):
            _gen(engine, n=6)

    t = threading.Thread(target=go)
    t.start()
    return t


def test_capture_profile_with_jax_profiler(engine, tmp_path):
    out_dir = str(tmp_path / "prof")
    t = _background_traffic(engine)
    try:
        result = engine.capture_profile(8, out_dir=out_dir, timeout_s=30)
    finally:
        t.join()
    assert result["profiler"] == "jax", result["error"]
    assert result["artifact"] == out_dir
    assert result["steps_captured"] >= 1
    assert result["aggregate"]["steps"] == result["steps_captured"]
    # jax writes the trace tree under the artifact dir
    assert os.path.isdir(out_dir) and os.listdir(out_dir)
    # nothing is left armed or claimed
    assert engine._profile is None and engine._capturing is False


def test_the_scheduler_steps_while_the_trace_is_being_stopped(
    engine, tmp_path, monkeypatch
):
    """``stop_trace`` collects for seconds on the chip. It runs on the
    capturing thread with ``_profile_mu`` free, so the scheduler goes on
    recording steps meanwhile."""
    in_stop, release = threading.Event(), threading.Event()
    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )

    def held_open():
        calls.append(("stop", threading.current_thread().name))
        in_stop.set()
        assert release.wait(30)

    monkeypatch.setattr(jax.profiler, "stop_trace", held_open)
    result = {}
    capture = threading.Thread(
        name="capturing",
        target=lambda: result.update(
            engine.capture_profile(2, out_dir=str(tmp_path), timeout_s=30)
        ),
    )
    capture.start()
    traffic = _background_traffic(engine, n_reqs=1)
    try:
        assert in_stop.wait(30)
        traffic.join(timeout=60)
        steps_before = engine.health()["steps"]
        # the stop is still held open: a whole request runs through
        _gen(engine, n=4)
        assert engine.health()["steps"] > steps_before
        assert engine._profile_mu.acquire(timeout=1.0)
        engine._profile_mu.release()
        # and a second capture is refused until the first has finished
        with pytest.raises(ValueError):
            engine.capture_profile(1, out_dir="", timeout_s=0.1)
    finally:
        release.set()
        capture.join(timeout=30)
    assert not capture.is_alive()
    assert calls[0] == ("start", str(tmp_path))
    assert calls[1] == ("stop", "capturing") and len(calls) == 2
    assert result["profiler"] == "jax" and result["steps_captured"] == 2


def test_a_profiler_that_will_not_start_is_reported_not_raised(
    engine, tmp_path, monkeypatch
):
    def refuse(log_dir):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    t = _background_traffic(engine, n_reqs=1)
    try:
        result = engine.capture_profile(
            2, out_dir=str(tmp_path / "x"), timeout_s=30
        )
    finally:
        t.join()
    assert result["profiler"] == "flight-only" and result["artifact"] == ""
    assert "profiler busy" in result["error"]
    assert result["steps_captured"] >= 1


def test_capture_profile_idle_times_out_gracefully(engine):
    """No traffic: the capture returns empty at its deadline instead
    of blocking forever. The overlapped engine may still be sealing a
    previous request's final step (done is set by the detok worker
    before the scheduler's step record lands) — wait for quiescence so
    'idle' is actually idle."""
    deadline = time.time() + 10
    while (
        (engine._pending or engine._slots) and time.time() < deadline
    ):
        time.sleep(0.01)
    time.sleep(0.1)   # let the in-flight step seal its record
    result = engine.capture_profile(3, out_dir="", timeout_s=0.3)
    assert result["profiler"] == "flight-only"
    assert result["steps_captured"] == 0


def test_capture_profile_concurrent_captures_rejected(engine):
    t = threading.Thread(
        target=lambda: engine.capture_profile(
            1000, out_dir="", timeout_s=1.0
        )
    )
    t.start()
    time.sleep(0.05)
    with pytest.raises(ValueError):
        engine.capture_profile(1, out_dir="", timeout_s=0.1)
    t.join()
