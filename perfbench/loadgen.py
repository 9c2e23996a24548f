"""Seeded traffic and the client that times it. One general generator
reads a traffic mix (``perfbench/traffic/<name>.json``); nothing here
knows a mix or a cell by name.

What was copied from ``gpustack_tpu/benchmark/loadgen.py`` and what was
repaired (PERF.md, inventory): the SSE reading and the nearest-rank
percentile are copies. Repaired: a request is timed from when it was
*due*, not from when it was sent; the open loop has no semaphore, so a
slow server gets no less load; arrivals are exponential gaps, not a fixed
interval; a stream without a content chunk has no first token (it does
not silently get ``first_token = end``). Prompts are plain ASCII, one
character a token under the engine's byte tokenizer, so lengths are
exact.

The run's seed draws the schedule: which prompt length meets which output
length, the order of the requests, the order of the arrival gaps, the
text and the sampling seeds. What it does not draw is the *amount* of
work: every seed offers the same multiset of lengths and the same
multiset of gaps (the mid-quantiles of the mix's distributions), so two
seeds differ by where the bursts fall and not by how much was asked. A
statistic that depends on where two or three bursts fall (a TTFT tail
over 60 requests) therefore spreads from seed to seed, and says so.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import os
import random
import statistics
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))

# 64 one-byte, printable tokens of the engine's byte tokenizer (id = byte
# + 1; engine/tokenizer.py). Biased by +100 (the engine's MAX_BIAS is 64
# entries), every sampled token is one of them: one character, one
# non-empty piece, one SSE chunk; EOS (id 0) is never sampled, so a
# request runs to its max_tokens exactly.
BIAS_ALPHABET = (
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789 ."
)
LOGIT_BIAS = {str(ord(c) + 1): 100 for c in BIAS_ALPHABET}

_WORDS = (
    "tensor mesh shard chip batch token cache slice host queue "
    "prefill decode kernel layer vector scalar weight logit"
).split()


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (the repo's ``_pct``): the smallest value
    with at least ``q`` of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    idx = min(len(s) - 1, int(math.ceil(q * len(s))) - 1)
    return s[max(0, idx)]


def _lognormal_quantile(dist: Dict[str, Any], q: float) -> float:
    return dist["median"] * math.exp(
        dist["sigma"] * statistics.NormalDist().inv_cdf(q)
    )


# a length distribution of a mix, by its ``dist``: the quantile function
LENGTH_DISTS = {"lognormal": _lognormal_quantile}


def stratified(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` whole numbers at the mid-quantiles of the distribution,
    clipped to its ``min`` and ``max``: the same multiset every time,
    which a seed then only reorders."""
    quantile = LENGTH_DISTS.get(dist.get("dist"))
    if quantile is None:
        raise ValueError(
            f"unknown distribution {dist.get('dist')!r}: "
            f"one of {sorted(LENGTH_DISTS)}"
        )
    lo = dist.get("min", 1)
    hi = dist.get("max", float("inf"))
    return [
        int(round(min(hi, max(lo, quantile(dist, (i + 0.5) / n)))))
        for i in range(n)
    ]


def _gamma_cdf(k: float, x: float) -> float:
    """Regularised lower incomplete gamma P(k, x), by its series."""
    if x <= 0:
        return 0.0
    term = total = 1.0 / k
    n = 0
    while abs(term) > 1e-15 * abs(total) and n < 10000:
        n += 1
        term *= x / (k + n)
        total += term
    return min(1.0, total * math.exp(k * math.log(x) - x - math.lgamma(k)))


def _exponential_quantiles(arrivals: Dict[str, Any], n: int) -> List[float]:
    return [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def _gamma_quantiles(arrivals: Dict[str, Any], n: int) -> List[float]:
    """Mid-quantiles of a gamma of shape 1 / cv**2 (bisection on the
    series above): cv 1 is the exponential, cv 2-3 is bursty."""
    k = 1.0 / float(arrivals["cv"]) ** 2
    out = []
    for i in range(n):
        q, lo, hi = (i + 0.5) / n, 0.0, 1.0
        while _gamma_cdf(k, hi) < q:
            hi *= 2.0
        for _ in range(80):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if _gamma_cdf(k, mid) < q else (lo, mid)
        out.append(0.5 * (lo + hi))
    return out


# an open loop's arrival process, by ``arrivals.process``: n gaps of any
# scale, which ``arrival_gaps`` scales to the cell's rate
ARRIVALS = {"poisson": _exponential_quantiles, "gamma": _gamma_quantiles}


def arrival_gaps(arrivals: Dict[str, Any], n: int, rate: float) -> List[float]:
    """``n`` inter-arrival gaps at the mid-quantiles of the mix's arrival
    process, scaled so that they add up to ``n / rate``: the same
    multiset every time, which a seed then only reorders."""
    quantiles = ARRIVALS.get(arrivals.get("process"))
    if quantiles is None:
        raise ValueError(
            f"unknown arrival process {arrivals.get('process')!r}: "
            f"one of {sorted(ARRIVALS)}"
        )
    raw = quantiles(arrivals, n)
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def load_traffic(name: str, root: str = HERE) -> Dict[str, Any]:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") == "open":
        process = (mix.get("arrivals") or {}).get("process")
        if process not in ARRIVALS:
            raise ValueError(
                f"{path}: 'arrivals.process' is {process!r}, "
                f"not one of {sorted(ARRIVALS)}"
            )
    elif mix.get("loop") == "closed":
        if not 1 <= int(mix.get("clients", 0)) <= int(mix.get("pool", 0)):
            raise ValueError(f"{path}: needs 1 <= 'clients' <= 'pool'")
        if int(mix["pool"]) % int(mix.get("round", mix["pool"])):
            raise ValueError(f"{path}: 'pool' must be whole 'round's")
    else:
        raise ValueError(f"{path}: 'loop' must be 'open' or 'closed'")
    return mix


def seeded_text(rng: random.Random, n_chars: int) -> str:
    """Plain ASCII prose of exactly ``n_chars`` characters."""
    out: List[str] = []
    size = 0
    while size <= n_chars:
        w = rng.choice(_WORDS)
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_chars]


@dataclasses.dataclass
class Planned:
    """One request as the seed drew it."""
    index: int
    prompt_tokens: int      # as the engine counts them, template included
    output_tokens: int
    text: str
    sample_seed: int
    due_s: Optional[float] = None   # open loop: offset into the window


def plan_requests(
    mix: Dict[str, Any], n: int, seed: int, scale: float = 1.0
) -> List[Planned]:
    """``n`` requests as the seed drew them: the mix's multiset of prompt
    lengths and of output lengths, each in an order of the seed's own.

    A mix with a ``round`` (a closed loop's) is made of rounds of that
    many requests: every round holds the same lengths, the mid-quantiles
    of the distributions, and the seed draws the order inside each. A
    closed loop serves as many requests as fit into the window, so the
    requests it reaches are a prefix of the sequence, and under one
    shuffle of the whole pool the seed chose *which* lengths that prefix
    held: tokens per second then followed the seed by 4 % (PERF.md,
    PR 25). With rounds every prefix holds the same work but for a part
    of one round.

    ``scale`` < 1 shrinks every length for the CPU rehearsal; the chip
    always runs the mix as written."""
    rng = random.Random(seed)
    size = int(mix.get("round", n))
    prompts: List[int] = []
    outputs: List[int] = []
    for start in range(0, n, size):
        k = min(size, n - start)
        for dist, into in (
            (mix["prompt_tokens"], prompts), (mix["output_tokens"], outputs)
        ):
            block = stratified(dist, k)
            rng.shuffle(block)
            into.extend(block)
    template = int(mix.get("template_tokens", 0))
    planned = []
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        p = max(template + 4, int(p * scale))
        o = max(2, int(o * scale))
        planned.append(Planned(
            index=i, prompt_tokens=p, output_tokens=o,
            text=seeded_text(rng, p - template),
            sample_seed=rng.randrange(1, 2**31 - 1),
        ))
    return planned


def plan_open(
    mix: Dict[str, Any], rate: float, seconds: float, seed: int,
    scale: float = 1.0,
) -> List[Planned]:
    n = max(1, int(round(rate * seconds)))
    planned = plan_requests(mix, n, seed, scale)
    gaps = arrival_gaps(mix["arrivals"], n, rate)
    random.Random(seed ^ 0x5EED).shuffle(gaps)
    # the first request is due as the window opens; the last gap is the
    # time left after the last request
    t = 0.0
    for p, g in zip(planned, gaps):
        p.due_s = t
        t += g
    return planned


def buckets_of(planned: List[Planned], max_seq_len: int) -> List[int]:
    """The prefill buckets these requests reach, as the runner cuts them
    (powers of two from 32, capped by the context; engine/runner.py)."""
    buckets, b = [], 32
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq_len)
    hit = set()
    for p in planned:
        hit.add(next(b for b in buckets if p.prompt_tokens <= b))
    return sorted(hit)


def chat_body(model: str, p: Planned, temperature: float) -> Dict[str, Any]:
    return {
        "model": model,
        "messages": [{"role": "user", "content": p.text}],
        "max_tokens": p.output_tokens,
        "temperature": temperature,
        "seed": p.sample_seed,
        "stream": True,
        "stream_options": {"include_usage": True},
        "logit_bias": LOGIT_BIAS,
    }


@dataclasses.dataclass
class Result:
    planned: Planned
    due: float = 0.0          # perf_counter seconds
    sent: float = 0.0
    chunk_times: List[float] = dataclasses.field(default_factory=list)
    end: float = 0.0
    done: bool = False        # [DONE] seen
    error: str = ""
    usage: Optional[Dict[str, int]] = None


async def stream_request(session, url, headers, body, res: Result) -> None:
    """POST one streamed chat completion and stamp every non-empty
    content chunk as its line is read."""
    import aiohttp

    res.sent = time.perf_counter()
    try:
        async with session.post(
            url, json=body, headers=headers,
            timeout=aiohttp.ClientTimeout(total=600),
        ) as resp:
            if resp.status != 200:
                res.error = f"http {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                payload = line[5:].strip()
                if payload == b"[DONE]":
                    res.done = True
                    break
                now = time.perf_counter()
                try:
                    chunk = json.loads(payload)
                except ValueError:
                    continue
                if "error" in chunk:
                    res.error = str(chunk["error"])[:200]
                    return
                if chunk.get("usage"):
                    res.usage = chunk["usage"]
                choice = (chunk.get("choices") or [{}])[0]
                if (choice.get("delta") or {}).get("content"):
                    res.chunk_times.append(now)
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
        res.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        res.end = time.perf_counter()


@dataclasses.dataclass
class Window:
    t0: float                 # perf_counter at the window's start
    t0_wall: float            # time.time() at the same moment
    seconds: float
    results: List[Result]


async def drive(
    base: str,
    headers: Dict[str, str],
    model: str,
    mix: Dict[str, Any],
    planned: List[Planned],
    seconds: float,
    during=None,
    tail=None,
) -> Window:
    """Offer the mix for ``seconds``. Open loop: each request goes out
    when it is due, whatever the server is doing. Closed loop:
    ``mix["clients"]`` clients, each sending its next request when its
    last has finished, taking requests in order from ``planned`` (round
    again when it runs out). ``during(window)`` is an optional coroutine
    run beside the traffic (the traced run's profile call). When the
    window closes, requests still running are cut.

    ``tail(window)`` is an optional coroutine started when the window
    closes (``--trace 2``: the profile call). Until it returns the same
    traffic goes on, uncut: the closed loop's clients simply continue,
    the open loop starts its plan again at the window's end (a longer
    plan would be another multiset of lengths and gaps). Up to the
    window's close a run with a tail does what a run without one does;
    what is sent after it is due after it, and ``reduce_window`` scores
    nothing that was."""
    import aiohttp

    url = f"{base}/v1/chat/completions"
    temperature = float(mix.get("temperature", 1.0))
    results: List[Result] = []
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        t0 = time.perf_counter()
        window = Window(t0, time.time(), seconds, results)
        t_end = t0 + seconds

        async def one(p: Planned, due: float) -> None:
            res = Result(planned=p, due=due)
            results.append(res)
            await stream_request(
                session, url, headers, chat_body(model, p, temperature), res
            )

        # every task that sends requests, so that closing the window can
        # cut exactly those
        senders: List[asyncio.Task] = []

        async def open_loop() -> None:
            start = t0
            while True:
                for p in planned:
                    due = start + p.due_s
                    delay = due - time.perf_counter()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    senders.append(asyncio.ensure_future(one(p, due)))
                if tail is None:
                    return
                start += seconds

        async def client(queue: List[Planned]) -> None:
            while True:
                p = queue.pop(0)
                queue.append(p)
                await one(p, time.perf_counter())

        if mix["loop"] == "open":
            senders.append(asyncio.ensure_future(open_loop()))
        else:
            queue = list(planned)
            senders.extend(
                asyncio.ensure_future(client(queue))
                for _ in range(int(mix["clients"]))
            )
        side = asyncio.ensure_future(during(window)) if during else None
        await asyncio.sleep(max(0.0, t_end - time.perf_counter()))
        if tail is not None:
            await tail(window)
        for t in list(senders):
            t.cancel()
        await asyncio.gather(*senders, return_exceptions=True)
        if side is not None:
            await side
    return window


def reduce_window(
    w: Window, mix: Dict[str, Any], cut_s: Optional[float] = None
) -> Dict[str, Any]:
    """Counts and client-side numbers of one window. Times in ms.

    A request is *scored* if it was due in the window's first ``seconds -
    tail_s`` (``tail_s`` is the mix's, at most a quarter of the window):
    it had at least ``tail_s`` to show a first token. TTFT
    percentiles are over all scored requests; one without a first token
    when the window closes is a failure. Gaps and tokens are taken from
    every request, over the whole window. ``cut_s`` shortens the window
    to its first ``cut_s`` seconds for every number here: the traced run's
    capture stalls the engine when it stops, so that run's client-side
    numbers are those of the window up to the capture."""
    seconds = w.seconds if cut_s is None else min(w.seconds, cut_s)
    t_end = w.t0 + seconds
    tail = min(float(mix.get("tail_s", 0.0)), 0.25 * seconds)
    scored_end = t_end - tail
    ttft: List[float] = []
    gaps: List[float] = []
    late: List[float] = []
    tokens = 0
    failed = 0
    mismatched = 0
    completed = 0
    scored = 0
    for r in w.results:
        if r.due >= t_end:
            continue
        late.append((r.sent - r.due) * 1e3)
        times = [t for t in r.chunk_times if t <= t_end]
        tokens += len(times)
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
        is_scored = r.due < scored_end
        scored += is_scored
        if r.error:
            failed += 1
            continue
        if is_scored:
            if times:
                ttft.append((times[0] - r.due) * 1e3)
            else:
                failed += 1
        if r.done and r.end <= t_end:
            completed += 1
            want = r.planned.output_tokens
            got = (r.usage or {}).get("completion_tokens")
            if not (len(r.chunk_times) == want == got):
                mismatched += 1
    return {
        "attempted": len(late),
        "scored": scored,
        "completed": completed,
        "failed": failed,
        "mismatched": mismatched,
        "tokens": tokens,
        "ttft_ms": ttft,
        "gaps_ms": gaps,
        "late_ms_max": max(late, default=0.0),
        "seconds": seconds,
    }


def flight_records(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The window's flight records of every engine, for the readers in
    ``perfbench/layer_metrics/``."""
    return [r for engine in (ctx.get("flights") or []) for r in engine]


def describe(planned: List[Planned]) -> Dict[str, Any]:
    """The drawn distribution, for the run's log."""
    def five(xs: List[int]) -> Dict[str, float]:
        return {
            "min": min(xs), "p50": percentile(xs, 0.5),
            "p90": percentile(xs, 0.9), "max": max(xs),
            "mean": round(sum(xs) / len(xs), 1),
        }
    out = {
        "requests": len(planned),
        "prompt_tokens": five([p.prompt_tokens for p in planned]),
        "output_tokens": five([p.output_tokens for p in planned]),
    }
    if planned and planned[0].due_s is not None:
        out["last_due_s"] = round(max(p.due_s for p in planned), 3)
    return out
