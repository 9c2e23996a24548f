"""A replica's start in spans (observability/startup.py): the union of
intervals, the program log against what ``jax.monitoring`` tells it, and
the span ``engine_start``. The real processes are in
tests/engine/test_engine_start.py and tests/e2e/."""

import json
import threading
import time

import pytest

from gpustack_tpu.observability import tracing
from gpustack_tpu.observability.startup import (
    BACKEND_COMPILE_EVENT,
    CACHE_HIT_EVENT,
    CACHE_RETRIEVAL_EVENT,
    LOWERING_EVENT,
    PHASES,
    TRACE_EVENT,
    EngineStart,
    ProgramLog,
    _Union,
    brief,
    process_created_at,
    process_programs,
)
from gpustack_tpu.testing import promtext


@pytest.mark.parametrize("spans, seconds", [
    ([(0, 1), (2, 3)], 2.0),                     # apart
    ([(0, 2), (1, 3)], 3.0),                     # two threads at once
    ([(0, 10), (2, 5)], 10.0),                   # nested
    ([(2, 5), (0, 10)], 10.0),                   # the inner one told first
    ([(0, 1), (1, 2)], 2.0),                     # end to end
    ([(5, 6), (0, 1), (0.5, 5.5)], 6.0),         # a late one joins two
    ([(3, 3), (4, 2)], 0.0),                     # nothing, and backwards
])
def test_union_counts_every_second_once(spans, seconds):
    u = _Union()
    for s, e in spans:
        u.add(s, e)
    assert u.seconds == pytest.approx(seconds)


def test_union_keeps_its_seconds_when_it_forgets_old_intervals():
    u = _Union()
    for i in range(500):
        u.add(2 * i, 2 * i + 1)
    assert u.seconds == pytest.approx(500.0)
    assert len(u._spans) <= _Union._KEEP


class _Jax:
    """What jax.monitoring tells a log for one program, on a clock of
    its own (0.1 s between spans)."""

    def __init__(self, log, at=100.0):
        self.log, self.at = log, at

    def span(self, event, seconds, name):
        t0, self.at = self.at, self.at + seconds + 0.1
        self.log.on_time_span(event, t0, t0 + seconds, fun_name=name)
        return [round(t0, 6), round(t0 + seconds, 6)]

    def program(self, fn, trace=0.3, lower=0.2, load=1.0, hit=False,
                retrieval=0.0):
        spans = {}
        if trace:
            spans["trace"] = self.span(TRACE_EVENT, trace, fn)
        spans["lower"] = self.span(LOWERING_EVENT, lower, f"jit({fn})")
        if hit:
            self.log.on_event(CACHE_HIT_EVENT)
            self.log.on_duration(CACHE_RETRIEVAL_EVENT, retrieval)
        spans["load"] = self.span(BACKEND_COMPILE_EVENT, load, f"jit({fn})")
        return spans


def test_a_program_is_one_record_with_its_name_and_three_spans():
    log = ProgramLog()
    log.set_place("weights")
    spans = _Jax(log).program("prefill_1024")
    (rec,) = log.records()
    assert rec == {
        "name": "jit(prefill_1024)", "phase": "weights", "cached": False,
        "retrieval_s": 0.0, **spans,
    }
    assert log.totals() == {
        "lowered": 1, "lower_s": 0.5, "load_s": 1.0, "cache_misses": 1,
        "retrieval_s": 0.0,
    }
    assert brief(rec) == ["jit(prefill_1024)", 500.0, 1000.0, False]


def test_a_cache_hit_is_a_load_and_no_miss():
    log = ProgramLog()
    jax = _Jax(log)
    jax.program("decode", load=2.0)
    jax.program("decode", trace=0, load=0.4, hit=True, retrieval=0.31)
    cold, warm = log.records()
    assert (cold["cached"], warm["cached"]) == (False, True)
    assert warm["retrieval_s"] == 0.31 and "trace" not in warm
    # the hit was that load's: the next program compiles again
    jax.program("insert", load=0.5)
    assert [r["cached"] for r in log.records()] == [False, True, False]
    totals = log.totals()
    assert totals["cache_misses"] == 2 and totals["lowered"] == 3
    assert totals["retrieval_s"] == 0.31
    assert totals["load_s"] == pytest.approx(2.9)


def test_the_trace_is_the_named_functions_not_a_lowering_rules():
    log = ProgramLog()
    jax = _Jax(log)
    jax.span(TRACE_EVENT, 0.01, "_where")            # nested: ends first
    outer = jax.span(TRACE_EVENT, 0.5, "_decode_impl")
    # a rule's small jit traced while the module is lowered
    log.on_time_span(TRACE_EVENT, jax.at + 0.05, jax.at + 0.06,
                     fun_name="_decode_impl")
    jax.span(LOWERING_EVENT, 0.2, "jit(_decode_impl)")
    jax.span(BACKEND_COMPILE_EVENT, 1.0, "jit(_decode_impl)")
    (rec,) = log.records()
    assert "trace" not in rec       # the later one overwrote it by name...
    # ...and is inside the lowering, so it is no part of the Python's
    # time either; the seconds still count once, in the union
    assert log.totals()["lower_s"] == pytest.approx(0.01 + 0.5 + 0.2)
    assert outer[1] <= rec["lower"][0]


def test_a_program_lowered_and_never_compiled_closes_at_the_next_lowering():
    log = ProgramLog()
    jax = _Jax(log)
    jax.span(LOWERING_EVENT, 0.2, "jit(aot_only)")
    assert log.records() == [] and log.counts() == (1, 0, 0)
    jax.program("next")
    first, second = log.records()
    assert first["name"] == "jit(aot_only)" and "load" not in first
    assert second["name"] == "jit(next)" and "load" in second
    assert brief(first) == ["jit(aot_only)", 200.0, 0.0, False]


def test_a_compile_on_another_thread_is_a_record_of_its_own():
    log = ProgramLog()
    jax = _Jax(log)
    jax.span(LOWERING_EVENT, 0.2, "jit(f)")
    t = threading.Thread(
        target=jax.span, args=(BACKEND_COMPILE_EVENT, 1.0, "jit(f)")
    )
    t.start()
    t.join(10)
    assert not t.is_alive()
    (rec,) = log.records()
    assert rec["name"] == "jit(f)" and "lower" not in rec and "load" in rec
    assert log.totals()["cache_misses"] == 1


def test_since_gives_what_closed_after_and_the_version_moves():
    log = ProgramLog(capacity=16)
    jax = _Jax(log)
    v0 = log.version
    jax.program("a")
    assert log.version > v0
    seen = log.counts()[2]
    v1 = log.version
    assert log.since(seen) == [] and log.version == v1
    jax.program("b")
    jax.program("c")
    assert [r["name"] for r in log.since(seen)] == ["jit(b)", "jit(c)"]
    for i in range(40):                 # the ring forgets, the totals not
        jax.program(f"p{i}")
    assert len(log.records()) == 16 and len(log.since(0)) == 16
    assert log.totals()["lowered"] == 43


def test_listeners_from_many_threads_lose_no_program():
    """More threads than cores, a short switch interval: every lowering
    and every load is counted once."""
    import sys

    log = ProgramLog(capacity=4096)
    n_threads, n_each = 16, 100

    def work(k):
        jax = _Jax(log, at=1000.0 * k)
        for i in range(n_each):
            jax.program(f"f{k}_{i}", trace=0.01, lower=0.01, load=0.01,
                        hit=i % 2 == 0)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    total = n_threads * n_each
    assert log.counts() == (total, total // 2, total)
    recs = log.records()
    assert len(recs) == total
    assert all({"trace", "lower", "load"} <= set(r) for r in recs)
    assert sum(r["cached"] for r in recs) == total // 2


def test_the_real_listeners_name_a_jitted_function():
    import jax
    import numpy as np

    log = process_programs()
    assert process_programs() is log            # one a process
    seen = log.counts()[2]

    def twice_and_one(x):
        return x * 2.0 + 1.0

    out = jax.jit(twice_and_one)(np.arange(7, dtype=np.float32))
    assert float(out[1]) == 3.0
    mine = [r for r in log.since(seen) if r["name"] == "jit(twice_and_one)"]
    assert len(mine) == 1, log.since(seen)
    rec = mine[0]
    for key in ("trace", "lower", "load"):
        assert rec[key][0] <= rec[key][1] <= time.time()
    assert rec["trace"][1] <= rec["lower"][0] <= rec["load"][0]
    assert isinstance(rec["cached"], bool)
    # the same shapes again: nothing is lowered
    version = log.version
    jax.jit(twice_and_one)  # a new wrapper of the same function
    assert log.version == version


def test_process_creation_is_before_any_import_and_not_long_ago():
    from gpustack_tpu.observability import startup

    created = process_created_at()
    assert created <= startup._IMPORTED_AT + 0.02
    assert time.time() - created < 3600.0
    assert abs(process_created_at() - created) < 0.05      # the same instant


@pytest.fixture
def started():
    log = ProgramLog()
    parent = tracing.TraceContext(tracing.make_trace_id())
    start = EngineStart(
        log, model="tiny",
        environ={tracing.TRACEPARENT_ENV: parent.traceparent()},
    )
    return start, log, parent


def _walk(start, log):
    for phase in PHASES[1:]:
        start.enter(phase)
        if phase == "weights":
            _Jax(log, at=time.time()).program("init")
        time.sleep(0.005)
    start.listening()


def test_the_phases_follow_one_another_without_a_gap(started):
    start, log, parent = started
    _walk(start, log)
    d = start.describe()
    assert [p["phase"] for p in d["phases"]] == list(PHASES)
    at = 0.0
    for p in d["phases"]:
        assert p["offset_ms"] == pytest.approx(at, abs=0.002)
        assert p["duration_ms"] >= 0.0
        at = p["offset_ms"] + p["duration_ms"]
    # the import phase began with the process, before this test did
    assert d["phases"][0]["duration_ms"] > 0.0
    assert (d["trace_id"], d["parent_id"]) == (parent.trace_id, parent.span_id)
    assert d["span_id"] not in ("", parent.span_id)
    assert [r["phase"] for r in d["programs"]] == ["weights"]
    assert d["sealed"] is False and d["events"] == []
    # what comes now falls in a scheduler step
    _Jax(log, at=time.time()).program("prefill_32")
    assert start.describe()["programs"][-1]["phase"] == "step"


def test_the_healthz_object_has_every_key_and_stays_small(started):
    start, log, _ = started
    _walk(start, log)
    s = start.summary()
    assert set(s) == {"t0", "ready_s", "first_token_s", "phases", "programs"}
    assert set(s["phases"]) == set(PHASES)
    assert set(s["programs"]) == {
        "lowered", "lower_s", "load_s", "cache_misses", "retrieval_s",
    }
    assert s["ready_s"] is None and s["first_token_s"] is None
    start.mark_ready()
    start.mark_first_token()
    s = start.summary()
    assert sum(s["phases"].values()) <= s["ready_s"] + 0.002 <= s["first_token_s"] + 0.004
    # a process that has run for a day and lowered thousands of programs
    big = json.dumps({
        **s, "ready_s": 12345.678, "first_token_s": 12345.678,
        "phases": {p: 1234.567 for p in PHASES},
        "programs": {"lowered": 123456, "lower_s": 12345.678,
                     "load_s": 12345.678, "cache_misses": 12345,
                     "retrieval_s": 1234.567},
    })
    assert len(json.dumps(s)) <= len(big) < 400


def test_ready_and_first_token_count_once_and_the_first_token_seals(started):
    start, log, parent = started
    _walk(start, log)
    start.mark_ready()
    ready = start.summary()["ready_s"]
    time.sleep(0.01)
    start.mark_ready()
    assert start.summary()["ready_s"] == ready
    store = tracing.get_store("engine")
    assert not store.query(trace_id=parent.trace_id)
    start.mark_first_token(time.time())
    first = start.summary()["first_token_s"]
    start.mark_first_token(time.time() + 5)
    assert start.summary()["first_token_s"] == first >= ready
    (entry,) = store.query(trace_id=parent.trace_id)
    assert entry["name"] == "engine_start" and entry["component"] == "engine"
    assert entry["parent_id"] == parent.span_id and entry["outcome"] == "ok"
    assert [p["phase"] for p in entry["spans"]] == list(PHASES)
    assert [e["event"] for e in entry["events"]] == ["ready", "first_token"]
    assert entry["attrs"]["programs"]["lowered"] == 1
    # dated from the process's creation, not from the span's making
    assert entry["started_at"] == pytest.approx(start.t0)
    assert entry["duration_ms"] == pytest.approx(first * 1e3, abs=50)
    assert start.describe()["sealed"] is True


def test_a_start_without_a_parent_mints_its_own_trace():
    start = EngineStart(ProgramLog(), environ={})
    d = start.describe()
    assert len(d["trace_id"]) == 32 and d["parent_id"] == ""
    start = EngineStart(ProgramLog(), environ={tracing.TRACEPARENT_ENV: "junk"})
    assert start.describe()["parent_id"] == ""


def test_the_gauge_family_is_declared_and_well_formed(started):
    start, log, _ = started
    _walk(start, log)
    start.mark_ready()
    text = "\n".join(start.metrics_lines()) + "\n"
    samples, types = promtext.assert_well_formed(text)
    assert types == {"gpustack_engine_start_seconds": "gauge"}
    labels = [s.labels["phase"] for s in samples]
    assert labels == list(PHASES) + ["ready"]         # no first token yet


def test_a_span_dated_in_the_past_measures_from_there():
    t0 = time.time() - 5.0
    tr = tracing.RequestTrace(
        tracing.TraceContext(tracing.make_trace_id()), "engine", "x",
        started_at=t0,
    )
    tr.event("now")
    assert tr.events[0]["offset_ms"] == pytest.approx(5000.0, abs=100)
    assert tr.started_at == t0


def test_an_unobserved_span_stays_out_of_the_request_histogram():
    from gpustack_tpu.observability.metrics import get_registry

    def total():
        return sum(
            1 for line in get_registry("worker").render_lines()
            if line.startswith("gpustack_worker_request_duration_seconds_count")
            and 'model="start-model"' in line
        )

    tr = tracing.RequestTrace(
        tracing.TraceContext(tracing.make_trace_id()), "worker",
        "instance_start", model="start-model",
    )
    tr.finish(status=200, observe=False, log=False)
    assert total() == 0
    assert tracing.get_store("worker").query(trace_id=tr.ctx.trace_id)
    tr = tracing.RequestTrace(
        tracing.TraceContext(tracing.make_trace_id()), "worker", "GET /x",
        model="start-model",
    )
    tr.finish(status=200, log=False)
    assert total() >= 1
