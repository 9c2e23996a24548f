"""The seeded generator and the client-side arithmetic (no server, no JAX)."""

import math
import statistics

import pytest

from perfbench import loadgen

CHAT = loadgen.load_traffic("chat-open")
RAG = loadgen.load_traffic("rag-closed")


def lengths(planned):
    return [(p.prompt_tokens, p.output_tokens) for p in planned]


def test_same_seed_same_requests_and_schedule():
    a = loadgen.plan_open(CHAT, 0.9, 50.0, 3000000001)
    b = loadgen.plan_open(CHAT, 0.9, 50.0, 3000000001)
    assert lengths(a) == lengths(b)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert [p.text for p in a] == [p.text for p in b]
    assert [p.sample_seed for p in a] == [p.sample_seed for p in b]


@pytest.mark.parametrize("other", [2, 2147483999, 3000000007])
def test_another_seed_another_schedule_of_the_same_work(other):
    """The seed draws the order of the lengths and of the gaps, the text
    and the sampling seeds; every seed offers the same multisets."""
    a = loadgen.plan_open(CHAT, 0.9, 50.0, 1)
    b = loadgen.plan_open(CHAT, 0.9, 50.0, other)
    assert lengths(a) != lengths(b)
    assert [p.due_s for p in a] != [p.due_s for p in b]
    assert [p.text for p in a] != [p.text for p in b]
    assert [p.sample_seed for p in a] != [p.sample_seed for p in b]
    for attr in ("prompt_tokens", "output_tokens"):
        assert sorted(getattr(p, attr) for p in a) == sorted(
            getattr(p, attr) for p in b)
    assert sorted(p.prompt_tokens for p in a) == loadgen.stratified(
        CHAT["prompt_tokens"], len(a))
    pool = sorted(loadgen.arrival_gaps(CHAT["arrivals"], len(a), 0.9))
    for plan in (a, b):
        gaps = [y.due_s - x.due_s for x, y in zip(plan, plan[1:])]
        # all gaps but the last (the time left after the last request)
        rest = list(pool)
        for g in gaps:
            near = min(rest, key=lambda x: abs(x - g))
            assert abs(near - g) < 1e-6
            rest.remove(near)
        assert len(rest) == 1


def test_closed_pool_is_reordered_by_the_seed():
    c = loadgen.plan_requests(RAG, RAG["pool"], 5)
    d = loadgen.plan_requests(RAG, RAG["pool"], 6)
    assert lengths(c) != lengths(d)
    assert sorted(p.prompt_tokens for p in c) == sorted(
        p.prompt_tokens for p in d)
    assert sorted(p.output_tokens for p in c) == sorted(
        p.output_tokens for p in d)
    assert lengths(c) == lengths(loadgen.plan_requests(RAG, RAG["pool"], 5))


@pytest.mark.parametrize("seed", [1, 2147483999, 3000000007])
def test_every_round_of_a_closed_pool_holds_the_same_work(seed):
    """Whatever part of the sequence a window reaches, it has met the
    same lengths but for a part of one round."""
    size = RAG["round"]
    planned = loadgen.plan_requests(RAG, RAG["pool"], seed)
    prompts = loadgen.stratified(RAG["prompt_tokens"], size)
    outputs = loadgen.stratified(RAG["output_tokens"], size)
    rounds = [planned[i:i + size] for i in range(0, len(planned), size)]
    assert len(rounds) == RAG["pool"] // size
    for r in rounds:
        assert sorted(p.prompt_tokens for p in r) == prompts
        assert sorted(p.output_tokens for p in r) == outputs
    assert len({tuple(lengths(r)) for r in rounds}) > 1


@pytest.mark.parametrize("rate,seconds", [(0.9, 50.0), (2.0, 20.0), (4.4, 50.0)])
def test_open_schedule_fills_the_window(rate, seconds):
    planned = loadgen.plan_open(CHAT, rate, seconds, 7)
    assert len(planned) == round(rate * seconds)
    dues = [p.due_s for p in planned]
    assert dues == sorted(dues) and dues[0] == 0.0
    assert dues[-1] < seconds
    gaps = loadgen.arrival_gaps(CHAT["arrivals"], len(planned), rate)
    assert sum(gaps) == pytest.approx(len(planned) / rate)


@pytest.mark.parametrize("arrivals,cv", [
    ({"process": "poisson"}, 1.0),
    ({"process": "gamma", "cv": 1.0}, 1.0),
    ({"process": "gamma", "cv": 2.5}, 2.5),
], ids=["poisson", "gamma-cv1", "gamma-cv2.5"])
def test_arrival_processes_by_name(arrivals, cv):
    """A mix names its arrival process; the gaps add up to n / rate and
    spread as the process says (mid-quantiles cut the far tail, so the
    coefficient of variation comes out a little under the one asked)."""
    n, rate = 200, 2.0
    gaps = loadgen.arrival_gaps(arrivals, n, rate)
    assert len(gaps) == n and min(gaps) > 0
    assert sum(gaps) == pytest.approx(n / rate)
    mean = sum(gaps) / n
    got = math.sqrt(sum((g - mean) ** 2 for g in gaps) / n) / mean
    assert got == pytest.approx(cv, rel=0.12, abs=1e-9)
    assert got <= cv + 1e-9


def test_gamma_of_cv_one_is_the_exponential():
    a = loadgen.arrival_gaps({"process": "gamma", "cv": 1.0}, 50, 1.0)
    b = loadgen.arrival_gaps({"process": "poisson"}, 50, 1.0)
    assert a == pytest.approx(b, rel=1e-6)


@pytest.mark.parametrize("call", [
    lambda: loadgen.stratified({"dist": "zipf"}, 4),
    lambda: loadgen.arrival_gaps({"process": "weibull"}, 4, 1.0),
], ids=["dist", "arrivals"])
def test_an_unknown_name_is_an_error_that_lists_the_known(call):
    with pytest.raises(ValueError, match="one of"):
        call()


@pytest.mark.parametrize("mix", [CHAT, RAG], ids=["chat-open", "rag-closed"])
def test_lengths_follow_the_mix(mix):
    planned = loadgen.plan_requests(mix, 64, 5)
    for key, attr in (("prompt_tokens", "prompt_tokens"),
                      ("output_tokens", "output_tokens")):
        xs = sorted(getattr(p, attr) for p in planned)
        assert xs[0] >= mix[key]["min"] and xs[-1] <= mix[key]["max"]
        assert statistics.median(xs) == pytest.approx(mix[key]["median"], rel=0.06)
    # one ASCII character is one token; the chat template adds its own
    for p in planned:
        assert len(p.text) == p.prompt_tokens - mix["template_tokens"]
        assert p.text.isascii()


def test_rag_reaches_only_the_flash_buckets():
    planned = loadgen.plan_requests(RAG, RAG["pool"], 11)
    assert loadgen.buckets_of(planned, 2048) == [1024, 2048]


def test_bias_is_64_one_byte_printable_tokens():
    assert len(loadgen.LOGIT_BIAS) == 64
    for tid in loadgen.LOGIT_BIAS:
        assert chr(int(tid) - 1) in loadgen.BIAS_ALPHABET


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 0.5, 5.0),
    ([1, 2, 3, 4], 0.5, 2),
    ([1, 2, 3, 4], 0.9, 4),
    (list(range(1, 101)), 0.99, 99),
    (list(range(1, 101)), 0.90, 90),
    ([3, 1, 2], 1.0, 3),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert loadgen.percentile(values, q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        loadgen.percentile([], 0.5)


def _result(due, sent, chunks, done=True, want=None, error=""):
    p = loadgen.Planned(0, 40, want if want is not None else len(chunks), "x", 1)
    r = loadgen.Result(planned=p, due=due, sent=sent, chunk_times=list(chunks))
    r.done, r.error = done, error
    r.usage = {"completion_tokens": len(chunks)}
    return r


def test_reduce_window_times_from_due_and_counts_failures():
    t0 = 100.0
    results = [
        # due at 0 s, sent 30 ms late, first token 0.5 s after it was DUE
        _result(t0 + 0.0, t0 + 0.03, [t0 + 0.5, t0 + 0.6, t0 + 0.9]),
        # scored, no first token inside the window: a failure
        _result(t0 + 1.0, t0 + 1.0, [], done=False),
        # due in the tail: sent, its tokens count, its TTFT does not
        _result(t0 + 9.0, t0 + 9.0, [t0 + 9.5, t0 + 10.5], done=False),
        # refused
        _result(t0 + 2.0, t0 + 2.001, [], done=False, error="http 429"),
        # completed, but a token went missing on the way: not correct
        _result(t0 + 3.0, t0 + 3.0, [t0 + 3.2, t0 + 3.3], want=3),
    ]
    w = loadgen.Window(t0=t0, t0_wall=0.0, seconds=10.0, results=results)
    red = loadgen.reduce_window(w, {"tail_s": 2.0})
    assert red["attempted"] == 5 and red["scored"] == 4
    assert red["failed"] == 2
    assert red["mismatched"] == 1 and red["completed"] == 2
    assert sorted(red["ttft_ms"]) == pytest.approx([200.0, 500.0])
    # the chunk at 10.5 s is outside the window: 3 + 1 + 2 tokens
    assert red["tokens"] == 6
    assert sorted(red["gaps_ms"]) == pytest.approx([100.0, 100.0, 300.0])
    assert red["late_ms_max"] == pytest.approx(30.0)


def test_tail_is_at_most_a_quarter_of_the_window():
    t0 = 0.0
    r = _result(2.5, 2.5, [2.6])
    w = loadgen.Window(t0=t0, t0_wall=0.0, seconds=4.0, results=[r])
    assert loadgen.reduce_window(w, {"tail_s": 8.0})["scored"] == 1
    r2 = _result(3.5, 3.5, [3.6])
    w2 = loadgen.Window(t0=t0, t0_wall=0.0, seconds=4.0, results=[r2])
    assert loadgen.reduce_window(w2, {"tail_s": 8.0})["scored"] == 0


def test_stratified_is_the_same_multiset_every_time():
    a = loadgen.stratified(CHAT["prompt_tokens"], 45)
    assert a == loadgen.stratified(CHAT["prompt_tokens"], 45)
    assert a == sorted(a)
    assert not any(math.isnan(x) for x in a)
