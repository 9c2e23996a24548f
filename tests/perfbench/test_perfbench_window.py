"""What a run's log says about its window: every client-side number, and
where a window lost time if it did."""

import importlib.util
import json
import os

import pytest

from perfbench import loadgen, run as bench

PB = os.path.dirname(os.path.abspath(loadgen.__file__))


def _result(chunks, due=0.0):
    p = loadgen.Planned(0, 40, len(chunks), "x", 1)
    return loadgen.Result(planned=p, due=due, sent=due, chunk_times=list(chunks))


def test_stalls_by_hand():
    t0 = 50.0
    w = loadgen.Window(t0=t0, t0_wall=1000.0, seconds=10.0, results=[
        _result([t0 + 1.0, t0 + 1.1, t0 + 4.0]),
        _result([t0 + 1.05, t0 + 2.0, t0 + 11.0]),   # the last is outside
    ])
    flights = [
        [{"ts": 1000.0, "dur_ms": 42.0}, {"ts": 1000.042, "dur_ms": 41.0},
         {"ts": 1003.0, "dur_ms": 2900.0}],
        [{"ts": 1001.0, "dur_ms": 40.0}, {"ts": 1001.5, "dur_ms": 40.0}],
    ]
    got = bench.stalls(w, flights, 10.0)
    # all streams silent from 2.0 s to 4.0 s
    assert got["client_silence_ms_max"] == pytest.approx(2000.0)
    assert got["engine_step_ms_max"] == pytest.approx(2900.0)
    # per engine, never across two engines
    assert got["engine_step_to_step_ms_max"] == pytest.approx(2958.0)


def test_stalls_without_records_or_tokens():
    w = loadgen.Window(t0=0.0, t0_wall=0.0, seconds=5.0, results=[_result([])])
    assert bench.stalls(w, [[]], 5.0) == {"client_silence_ms_max": 0.0}


def test_client_numbers_are_all_there_whatever_the_cell_reports():
    red = {"tokens": 500, "seconds": 50.0,
           "ttft_ms": [float(x) for x in range(1, 101)],
           "gaps_ms": [float(x) for x in range(1, 1001)]}
    assert bench.client_numbers(red) == {
        "output_tok_s": 10.0, "ttft_ms_p50": 50.0, "ttft_ms_p90": 90.0,
        "itl_ms_p99": 990.0,
    }
    assert bench.client_numbers(
        {"tokens": 0, "seconds": 50.0, "ttft_ms": [], "gaps_ms": []}
    ) == {"output_tok_s": 0.0}


@pytest.mark.parametrize("name,want", [
    ("client.ttft_ms_p50", 50.0), ("client.ttft_ms_p90", 90.0),
    ("client.itl_ms_p99", 99.0), ("client.output_tok_s", 4.0),
])
def test_client_readers(name, want):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(PB, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    xs = [float(x) for x in range(1, 101)]
    ctx = {"loadgen": {"ttft_ms": xs, "gaps_ms": xs, "tokens": 200, "seconds": 50.0}}
    assert mod.read(ctx) == want
    assert mod.read({}) is None


@pytest.mark.parametrize("mix", [
    {"loop": "open"},
    {"loop": "open", "arrivals": {"process": "weibull"}},
    {"loop": "closed", "clients": 8, "pool": 4},
    {"loop": "closed", "clients": 8, "pool": 60, "round": 8},
    {"loop": "half-open"},
], ids=["no-arrivals", "unknown-process", "pool-under-clients",
        "pool-of-broken-rounds", "unknown-loop"])
def test_a_traffic_file_that_cannot_be_run_is_refused(tmp_path, mix):
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "traffic" / "bad.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError):
        loadgen.load_traffic("bad", str(tmp_path))


def _spread_module():
    spec = importlib.util.spec_from_file_location(
        "perfbench_spread", os.path.join(PB, "spread.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spread_is_the_quartile_distance_over_the_median():
    sp = _spread_module()
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 150.0]
    # statistics.quantiles(n=4) of six values: 100.75 and 115.5
    assert sp.spread(values) == pytest.approx((115.5 - 100.75) / 102.5)
    assert sp.trimmed(values) == [100.0, 101.0, 102.0, 103.0, 104.0]
    assert sp.spread(sp.trimmed(values)) == pytest.approx(3.0 / 102.0)
    # a count that is 0 in every run, and one that is 0 in most
    assert sp.spread([0.0] * 6) == 0.0
    assert sp.spread([0.0, 0.0, 0.0, 0.0, 3.0, 4.0]) == float("inf")


def test_spread_reads_the_sets_a_run_left(tmp_path, capsys):
    sp = _spread_module()
    for s, vals in (("1", [10.0, 10.2, 10.1]), ("2", [10.0, 10.4, 10.2])):
        for i, v in enumerate(vals, 1):
            (tmp_path / f"a.b.S{s}.{i}.out").write_text(
                json.dumps({"phase": "window", "client": {
                    "itl_ms_p99": v, "ttft_ms_p90": 5 * v},
                    "stalls": {"client_silence_ms_max": 3.0}}) + "\n"
                + json.dumps({"correct": True, "failed": 0, "metrics": {
                    "itl_ms_p99": {"value": v, "unit": "ms"},
                    "setup_s": {"value": 30.0 + i, "unit": "s"}}}) + "\n")
    assert sp.main(["spread.py", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "== a.b: set 1: 3 runs, set 2: 3 runs" in text
    row = next(ln for ln in text.splitlines() if ln.startswith("itl_ms_p99"))
    assert "medians 10.1000 / 10.2000" in row
    assert "log:ttft_ms_p90" in text and "log:itl_ms_p99" not in text
    # the first run of a set may compile: not in setup_s
    row = next(ln for ln in text.splitlines() if ln.startswith("setup_s"))
    assert "medians 32.5000 / 32.5000" in row
