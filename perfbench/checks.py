"""The comparison that decides ``correct``: greedy and seeded requests
outside the window, through the same public API as the traffic.

1. Every token of a streamed request arrives as its own content chunk:
   chunks = ``usage.completion_tokens`` = ``max_tokens``.
2. The same seeded request twice gives the same text and the same, finite
   log-probabilities.
3. Prefill against the cache: eight greedy tokens from a prompt P, then P
   plus the first four of them again; the top log-probabilities of the
   next token (computed by prefill over P+4) agree with those of step
   five of the first answer (computed by decode through the cache). Made
   in the smallest and in the largest bucket the cell warmed, so that a
   cell whose prompts reach 1024 tokens checks the Pallas flash kernel's
   program too, with five prompts in each, and judged by the **median**
   of the five differences against the tolerance the configuration's
   ``deployment.json`` states (``prefill_vs_cache_tol``). Why a median,
   and where the tolerances come from: below, at ``PROMPTS``.
4. With several replicas, the same greedy request is answered alike by
   each.

What this does not decide: the engine is compared only with itself.
There is no independent reference, so a change that lowers the precision
of the prefill program and of the decode program alike passes all four.
A logit-level comparison with a float32 reference at published widths
needs a door into the engine that the public API does not have (PERF.md,
Open questions; the first ``model_config`` PR).
"""

from __future__ import annotations

import json
import math
import random
import statistics
import urllib.error
import urllib.request
from typing import Any, Dict, List, Tuple

from perfbench import loadgen
from perfbench.cluster import BenchFailure, expect, http

# Check 3 compares two programs that round in bf16 in another order, so a
# sound system does not read 0. How far it reads was measured with
# ``check_noise.py`` on the chip, 16-40 prompts a bucket and call (tables
# in ``perfbench/check_noise/``; my chip runs, PR 25, calls 15-16):
#
#   Qwen3-8B int8      192 readings in the buckets 256-2048: median 0.021
#                      nats, p90 0.031, largest 0.043 (0.055 once in the
#                      benchmark's own runs earlier in the PR)
#   Qwen3-30B-A3B l12  140 readings in 1024 and 2048: median 0.016, p90
#                      0.074, 21 over 0.06, 4 over 0.10, largest 0.123: a
#                      long tail, as a router that takes 8 of 128 experts
#                      must have, since a rounding flips a choice now and
#                      then
#   a wrong answer     (one prompt's decode against the next prompt's
#                      prefill) 92 readings: median 0.38-0.46, least
#                      0.099, 3 under 0.12
#
# One prompt a bucket cannot be judged on that. A tolerance of 0.06 failed
# the MoE on every seventh prompt (the driver's check of this PR refused
# the benchmark for it), and one above the MoE's tail (0.2, say) is passed
# by a wrong answer one time in ten. The median of five can be judged:
# with one sound MoE prompt in 140 over 0.12, three of five are so about
# once in 100,000 checks, and the median of five wrong answers is under
# 0.12 as rarely (drawn 100,000 times from the readings: least 0.114, one
# in a thousand under 0.17). Tolerances: 0.05 for the 8B, 0.12 for the
# MoE, 2.4 and 7.5 times the typical reading: a change that makes prefill
# and decode disagree that much more is caught, and a smaller one is not.
PROMPTS = 5


def stream_once(
    base: str, hdrs: Dict[str, str], body: Dict[str, Any],
    timeout: float = 900.0,
) -> Dict[str, Any]:
    """One streamed chat completion, read to ``[DONE]``."""
    req = urllib.request.Request(
        f"{base}/v1/chat/completions", data=json.dumps(body).encode(),
        headers={**hdrs, "Content-Type": "application/json"}, method="POST",
    )
    chunks, text, usage, done = 0, [], {}, False
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            for raw in r:
                line = raw.decode("utf-8", errors="replace").strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    done = True
                    break
                event = json.loads(payload)
                if "error" in event:
                    raise BenchFailure(f"stream error: {event['error']}")
                usage = event.get("usage") or usage
                choice = (event.get("choices") or [{}])[0]
                piece = (choice.get("delta") or {}).get("content")
                if piece:
                    chunks += 1
                    text.append(piece)
    except urllib.error.HTTPError as e:
        raise BenchFailure(f"stream: HTTP {e.code}: {e.read()[:400]!r}") from e
    if not done:
        raise BenchFailure("stream ended without [DONE]")
    return {
        "chunks": chunks, "text": "".join(text),
        "prompt_tokens": usage.get("prompt_tokens"),
        "completion_tokens": usage.get("completion_tokens"),
    }


def _finite(xs: List[float]) -> bool:
    return bool(xs) and all(
        isinstance(x, (int, float)) and math.isfinite(x) and x <= 1e-3
        for x in xs
    )


def _chat(base, hdrs, model, text, max_tokens, **extra) -> Dict[str, Any]:
    body = {
        "model": model, "messages": [{"role": "user", "content": text}],
        "max_tokens": max_tokens, "logit_bias": loadgen.LOGIT_BIAS,
        "logprobs": True, "top_logprobs": 5, **extra,
    }
    data = expect(
        http("POST", f"{base}/v1/chat/completions", body, hdrs, 600.0),
        200, "chat completion",
    )
    choice = data["choices"][0]
    return {
        "text": choice["message"]["content"],
        "logprobs": [e["logprob"] for e in choice["logprobs"]["content"]],
        "completion_tokens": data["usage"]["completion_tokens"],
    }


def _complete(base, hdrs, model, prompt, max_tokens) -> Dict[str, Any]:
    body = {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, "logprobs": 5, "logit_bias": loadgen.LOGIT_BIAS,
    }
    data = expect(
        http("POST", f"{base}/v1/completions", body, hdrs, 600.0),
        200, "completion",
    )
    choice = data["choices"][0]
    return {
        "text": choice["text"],
        "top": choice["logprobs"]["top_logprobs"],
        "prompt_tokens": data["usage"]["prompt_tokens"],
    }


def ranked_diff(a: Dict[str, float], b: Dict[str, float]) -> float:
    """The largest difference between the k-th largest log-probability of
    one answer and of the other, whichever tokens carry them."""
    return max(
        (abs(x - y) for x, y in zip(
            sorted(a.values(), reverse=True), sorted(b.values(), reverse=True)
        )),
        default=float("inf"),
    )


def too_far(diffs: List[float], tol: float) -> bool:
    """Check 3's verdict on one bucket: the median of the prompts'
    differences is over the tolerance (or is no number)."""
    return not statistics.median(diffs) <= tol


def prefill_vs_cache(
    base: str, hdrs: Dict[str, str], model: str, rng: random.Random,
    bucket: int, max_seq_len: int,
) -> Tuple[Dict[str, float], Dict[str, float], List[str]]:
    """Check 3 once in one bucket: the next token's top log-probabilities
    by decode and by prefill, and what is wrong with the answers' form.
    The prompt and its eight tokens stay inside the bucket and the
    context. How far the two may differ is the caller's to say."""
    problems: List[str] = []
    n_prompt = min(bucket - 6, max_seq_len - 12)
    prompt = loadgen.seeded_text(rng, n_prompt)
    first = _complete(base, hdrs, model, prompt, 8)
    again = _complete(base, hdrs, model, prompt + first["text"][:4], 1)
    if (
        len(first["text"]) != 8 or first["prompt_tokens"] != n_prompt
        or len(first["top"]) != 8 or len(again["top"]) != 1
    ):
        problems.append(f"completion in bucket {bucket}: {first}, {again}")
        return {}, {}, problems
    return first["top"][4], again["top"][0], problems


def run_checks(
    setup, base: str, hdrs: Dict[str, str],
    engines: List[Tuple[str, Dict[str, str]]], buckets: List[int],
) -> Dict[str, Any]:
    """All four checks with prompts that stay inside the buckets the
    warm-up has already compiled: the smallest for 1, 2 and 4, the
    smallest and the largest for 3. Returns ``{"ok": bool, ...}``; a
    check that cannot even run raises."""
    model = setup.spec["name"]
    template = int(setup.mix.get("template_tokens", 0))
    rng = random.Random(setup.seed + 1)
    bucket = min(buckets)
    out: Dict[str, Any] = {"bucket": bucket}
    problems: List[str] = []

    # 1. a chunk for every token
    n = 12
    p = loadgen.Planned(
        index=-1, prompt_tokens=bucket - 4, output_tokens=n,
        text=loadgen.seeded_text(rng, bucket - 4 - template), sample_seed=7,
    )
    got = stream_once(base, hdrs, loadgen.chat_body(model, p, 0.0))
    out["stream"] = got
    if not (got["chunks"] == got["completion_tokens"] == n):
        problems.append(f"stream: {got['chunks']} chunks for {n} tokens")
    if got["prompt_tokens"] != p.prompt_tokens:
        problems.append(
            f"prompt of {p.prompt_tokens} tokens counted as "
            f"{got['prompt_tokens']}"
        )

    # 2. seeded twice
    a = _chat(base, hdrs, model, p.text, 8, temperature=1.0, seed=1234)
    b = _chat(base, hdrs, model, p.text, 8, temperature=1.0, seed=1234)
    out["seeded_identical"] = a == b
    if a != b:
        problems.append(f"seeded request differs: {a} vs {b}")
    if not _finite(a["logprobs"]) or a["completion_tokens"] != 8:
        problems.append(f"seeded request: bad log-probabilities {a}")

    # 3. prefill against the cache, in the smallest bucket and in the
    # largest (for buckets >= 1024 the flash kernel's): the median of
    # PROMPTS differences against the configuration's own tolerance
    tol = float(setup.deployment["prefill_vs_cache_tol"])
    out["prefill_vs_cache_tolerance"] = tol
    out["prefill_vs_cache_diffs"] = {}
    for size in sorted({bucket, max(buckets)}):
        diffs = []
        for _ in range(PROMPTS):
            by_decode, by_prefill, wrong = prefill_vs_cache(
                base, hdrs, model, rng, size, int(setup.spec["max_seq_len"])
            )
            problems.extend(wrong)
            diffs.append(ranked_diff(by_decode, by_prefill))
        out["prefill_vs_cache_diffs"][str(size)] = diffs
        if too_far(diffs, tol):
            problems.append(
                f"prefill vs cache in bucket {size}: the top log-"
                f"probabilities of {PROMPTS} prompts differ by {diffs}, "
                f"median over the tolerance {tol}"
            )

    # 4. every replica alike
    if len(engines) > 1:
        answers = [
            _chat(eb, eh, model, p.text, 8, temperature=0) for eb, eh in engines
        ]
        out["replicas_identical"] = all(x == answers[0] for x in answers)
        if not out["replicas_identical"]:
            problems.append(f"replicas differ: {answers}")

    out["ok"] = not problems
    out["problems"] = problems
    return out
