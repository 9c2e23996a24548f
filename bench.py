#!/usr/bin/env python
"""Benchmark: flagship serving throughput on the local accelerator.

Default profile mirrors the reference's "Throughput" benchmark shape
(1024-token prompts / 128 output tokens, unlimited rate — reference
gpustack/assets/profiles_config/profiles_config.yaml:2-11) driven through
the in-repo engine on Llama-3-8B (int8 weight-only, random weights — zero
egress; token throughput is weight-content-independent).

Metric: output tokens/sec/chip. Baseline anchor (BASELINE.md): the
reference's closest published number for an 8B-dense model —
Qwen3-8B on Ascend 910B×8, 1512.21 output tok/s total → 189 output
tok/s/chip (docs/performance-lab/qwen3-8b/910b.md:95-98).

Env knobs:
  BENCH_PROFILE=throughput|longcontext|latency|multiturn|generation-heavy
      |long-context
      (default throughput; multiturn = ShareGPT-shaped conversations
      run twice over one seeded schedule — cache-off then cache-on —
      reporting paired cold vs prefix-hit TTFT + greedy token parity
      in detail.multiturn; generation-heavy = the reference
      Generation-Heavy shape: short prompts, long decode-bound outputs)
  BENCH_ROUND=0     skip writing the BENCH_r* round file (every run
      normally persists its full result as the next BENCH_rNN.json so
      the perf trajectory records tok/s, not just the final line)
  BENCH_OVERLAP_COMPARE=0  skip the CPU overlap-on vs overlap-off
      second pass (recorded in detail.overlap_comparison)
  BENCH_MODEL=<preset>                           (default llama3-8b)
  BENCH_SMOKE=1      the tiny preset on the CPU: exercises the profile's
      control flow and prints COUNTS only (tokens, requests, cache hits,
      parity) — never a time or a rate, and nothing under a per-chip name

bench.py measures on a TPU: without BENCH_SMOKE=1 it needs
``jax.devices()[0].platform == "tpu"`` in its own process and exits
non-zero, printing no rate, when it finds anything else. It never swaps
in another model or another platform.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N|null}
"""

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_OUT_TPS_PER_CHIP = 189.0  # Qwen3-8B, 910B x8: 1512.21/8

# ---- artifact emission ----------------------------------------------------
# The driver parses the LAST stdout line as the run's metric: one compact
# {"metric": ...} JSON, whatever happened before it.


def _emit(result) -> None:
    print(json.dumps(result))


def _emit_round_file(result) -> None:
    """Persist this run's FULL result as the next BENCH_rNN.json in the
    repo root, so every profile run lands in the perf trajectory (the
    driver's end-of-round capture only sees the final line of whatever
    single command it ran). The compact final metric line stays the
    machine-parsed artifact; BENCH_ROUND=0 opts out."""
    if os.environ.get("BENCH_ROUND", "1") != "1":
        return
    import re

    base = os.path.dirname(os.path.abspath(__file__))
    n = 0
    try:
        for name in os.listdir(base):
            m = re.match(r"BENCH_r(\d+)\.json$", name)
            if m:
                n = max(n, int(m.group(1)))
    except OSError:
        return
    path = os.path.join(base, f"BENCH_r{n + 1:02d}.json")
    payload = {
        "n": n + 1,
        "source": "bench.py",
        "cmd": (
            "BENCH_PROFILE="
            f"{os.environ.get('BENCH_PROFILE', 'throughput')} "
            "python bench.py"
        ),
        "rc": 0,
        "recorded_at": time.time(),
        "result": result,
    }
    try:
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"bench: round file written: {path}", file=sys.stderr)
    except OSError as e:
        print(f"bench: round file write failed: {e}", file=sys.stderr)


def prior_round_value(profile, smoke):
    """Most recent prior BENCH_r* round with the SAME profile and the
    same platform class (smoke vs real hardware) — the reference point
    for ``vs_baseline`` when the absolute 189 tok/s/chip anchor does
    not apply, so every round file is self-describing relative to its
    own trajectory instead of recording null. Returns
    ``{"round": n, "value": v}`` or None."""
    import re

    base = os.path.dirname(os.path.abspath(__file__))
    try:
        names = os.listdir(base)
    except OSError:
        return None
    rounds = sorted(
        (int(m.group(1)), n)
        for n in names
        if (m := re.match(r"BENCH_r(\d+)\.json$", n))
    )
    for n, name in reversed(rounds):
        try:
            with open(os.path.join(base, name)) as f:
                rec = json.load(f)
            res = rec.get("result") or {}
            detail = res.get("detail") or {}
            if detail.get("profile") != profile:
                continue
            if bool(detail.get("tpu_unavailable", True)) != smoke:
                continue
            value = float(res.get("value") or 0)
            if value > 0:
                return {"round": n, "value": value}
        except (OSError, ValueError, TypeError, json.JSONDecodeError):
            continue
    return None


# ------------------------- profiles ---------------------------------------
# throughput: the reference Performance Lab shape (1024/128, unlimited rate)
# longcontext: scaled Long-Context shape — long prompt, few slots, chunked
#   prefill (reference profiles_config.yaml:29-38 is 32k on 8 chips; one
#   v5e chip with 8 GB of int8 weights carries 16k cleanly)
# latency: low-concurrency TTFT/TPOT shape (profiles_config.yaml:12-20)
PROFILES = {
    "throughput": dict(
        prompt_len=1000, output_len=128, num_requests=48,
        max_slots=16, max_seq_len=1280, prefill_chunk=0,
    ),
    "longcontext": dict(
        prompt_len=16000, output_len=64, num_requests=4,
        max_slots=2, max_seq_len=16640, prefill_chunk=2048,
    ),
    "latency": dict(
        prompt_len=2000, output_len=128, num_requests=8,
        max_slots=1, max_seq_len=2304, prefill_chunk=0,
        # closed loop: one request in flight at a time, so ttft_ms is
        # actual time-to-first-token, not queue wait behind other
        # requests sharing the slot
        closed_loop=True,
    ),
    # ShareGPT-shaped multi-turn chat/agent loop (reference
    # profiles_config.yaml lineage, synthetic — zero egress): every
    # turn's prompt is the full conversation so far (shared system
    # prompt + prior turns + the model's own replies), so with the host
    # block KV cache on, turn N+1's prefill is a prefix hit on the
    # blocks turn N decoded. Reported: cold vs prefix-hit TTFT, so the
    # cache win is phase-attributed instead of smeared into throughput.
    "multiturn": dict(
        conversations=8, turns=4, system_len=512, user_len=192,
        output_len=96, max_slots=4, max_seq_len=8192, prefill_chunk=0,
        host_kv_cache_mb=4096, kv_block_tokens=256, multiturn=True,
    ),
    # generation-heavy: the reference Generation-Heavy shape — short
    # prompts, long outputs (decode-bound; profiles_config.yaml
    # lineage). The profile where dispatch-ahead overlap matters most:
    # almost every step is a decode step.
    "generation-heavy": dict(
        prompt_len=128, output_len=768, num_requests=24,
        max_slots=16, max_seq_len=1024, prefill_chunk=0,
    ),
    # long-context DISAGGREGATED serving (reference Long-Context shape
    # 32000/100, profiles_config.yaml:29-38): two-turn conversations on
    # a long prompt, measured three ways over one seeded schedule —
    # colocated cold (cache detached), prefix-affinity warm (the REAL
    # PrefixAffinityMap routes turn 2 back to the KV-holding replica),
    # and disaggregated (turn 1 on a prefill-role engine, blocks handed
    # to a decode-role engine over the real kv_transfer wire codec,
    # turn 2 served there). detail.long_context records the TTFT
    # comparison, affinity hit rate, handoff bytes/latency, and greedy
    # token parity across all three passes.
    "long-context": dict(
        prompt_len=32000, followup_len=256, output_len=100,
        conversations=2, max_slots=2, max_seq_len=34816,
        prefill_chunk=2048, host_kv_cache_mb=16384,
        kv_block_tokens=256, long_context=True,
    ),
    # cold-fleet warmup (the cluster KV fabric profile): one replica
    # serves shared-prefix conversations and feeds its ConvIndex; a
    # SECOND replica starts completely cold and is warmed through the
    # fleet block directory — per turn the directory is consulted with
    # the proxy's conversation chain, the holder's blocks travel the
    # real wire codec into the cold replica (the /kv/pull path,
    # in-process), and the turn serves there. detail.cold_fleet
    # records cold vs affinity-warm vs directory-warm TTFT,
    # cross-replica hit count, pull bytes, and greedy token parity
    # across all three passes.
    "cold-fleet-warmup": dict(
        conversations=6, turns=3, system_len=2048, user_len=256,
        output_len=64, max_slots=2, max_seq_len=8192, prefill_chunk=0,
        host_kv_cache_mb=8192, kv_block_tokens=256, cold_fleet=True,
    ),
}


_PARAMS_CACHE = {}


def build_engine(
    cfg_name, max_slots, max_seq_len, prefill_chunk,
    host_kv_cache_mb=0, kv_block_tokens=0, kv_cache_int8=False,
    pipeline_depth=None,
):
    import jax

    from gpustack_tpu.engine.engine import LLMEngine
    from gpustack_tpu.models.config import get_config
    from gpustack_tpu.models.quant import init_params_int8

    cfg = get_config(cfg_name)
    params = _PARAMS_CACHE.get(cfg_name)
    if params is None:
        # the engine start's own int8 init: built on the device leaf by
        # leaf, so an 8B model's bf16 tree never has to fit the chip
        params = init_params_int8(cfg, jax.random.key(0))
        jax.block_until_ready(params)
        # cached so the overlap-off comparison engine reuses the same
        # weights (and jit warmup cost, on CPU) instead of re-initing
        _PARAMS_CACHE[cfg_name] = params
    kwargs = {}
    if pipeline_depth is not None:
        kwargs["pipeline_depth"] = pipeline_depth
    return LLMEngine(
        cfg, params, max_slots=max_slots, max_seq_len=max_seq_len,
        prefill_chunk=prefill_chunk,
        host_kv_cache_mb=host_kv_cache_mb,
        kv_block_tokens=kv_block_tokens,
        kv_cache_int8=kv_cache_int8,
        **kwargs,
    )


# ---------------------- multiturn profile flow ------------------------------


def _wait_for_cache_store(engine, history, deadline_s=15.0):
    """Model user think-time between turns: wait (bounded) until the
    finished turn's full history is actually matchable — the engine
    queues TWO async stores per request (prompt-time and finish-time),
    so a global block-count bump alone could be the prompt store with
    the reply blocks still in flight, racing the next turn's lookup.
    ``peek_prefix_len`` probes without touching hit/miss counters."""
    cache = getattr(engine, "host_kv_cache", None)
    if cache is None:
        return
    # the finish-time store covers prompt + reply minus the final token
    expected = (len(history) - 1) // cache.block_tokens \
        * cache.block_tokens
    if expected <= 0:
        return
    probe = list(history) + [0]   # proper-prefix probe
    t0 = time.time()
    while (
        cache.peek_prefix_len(probe) < expected
        and time.time() - t0 < deadline_s
    ):
        time.sleep(0.01)


def multiturn_schedule(seed, vocab, prof):
    """Seeded conversation schedule: one shared system prompt + per-
    conversation user turns. Pure in (seed, vocab, prof) so the cold
    (cache-off) and hit (cache-on) passes replay identical traffic."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(1, vocab, prof["system_len"]).tolist()
    users = [
        [
            rng.integers(1, vocab, prof["user_len"]).tolist()
            for _ in range(prof["turns"])
        ]
        for _ in range(prof["conversations"])
    ]
    return system, users


def run_multiturn(engine, prof, schedule):
    """Drive ShareGPT-shaped conversations closed-loop: per turn the
    prompt is the whole history (shared system prompt + user turns +
    the model's own greedy replies). Returns per-turn records
    ``{conv, turn, prompt_len, ttft_ms, reused, output_ids}``."""
    from gpustack_tpu.engine.engine import GenRequest

    system, users = schedule
    recs = []
    for c, conv in enumerate(users):
        history = list(system)
        for t, user in enumerate(conv):
            history += user
            req = engine.generate(
                GenRequest(
                    prompt_ids=list(history),
                    max_tokens=prof["output_len"],
                    temperature=0.0,
                    stop_ids=(),
                ),
                timeout=7200,
            )
            recs.append({
                "conv": c, "turn": t, "prompt_len": len(history),
                "ttft_ms": req.ttft_ms,
                "reused": req.prefix_tokens_reused,
                "output_ids": list(req.output_ids),
                "req": req,   # internal: not part of the JSON detail
            })
            history += req.output_ids
            _wait_for_cache_store(engine, history)
    return recs


# ---------------------- long-context (disaggregated) flow -------------------


def long_context_schedule(seed, vocab, prof):
    """Seeded two-turn conversations: a long base prompt + a short
    follow-up. Pure in (seed, vocab, prof) so every pass replays
    identical traffic."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [
        (
            rng.integers(1, vocab, prof["prompt_len"]).tolist(),
            rng.integers(1, vocab, prof["followup_len"]).tolist(),
        )
        for _ in range(prof["conversations"])
    ]


def _affinity_turn(affinity, model_name, conv, turn, replica_id):
    """Drive the REAL PrefixAffinityMap exactly as the proxy would:
    deterministic per-(conversation, turn) message chains, lookup then
    record. Returns the map's routing decision (replica id or None)."""
    if affinity is None:
        return None
    from gpustack_tpu.server.resilience import conversation_chain

    msgs = [{"role": "user", "content": f"conv-{conv}-turn-0"}]
    if turn == 1:
        msgs += [
            {"role": "assistant", "content": "reply-0"},
            {"role": "user", "content": "turn-1"},
        ]
    chain = conversation_chain(model_name, msgs)
    hit = affinity.lookup(chain)
    affinity.record(chain[-1], replica_id, 1)
    return hit


def run_long_context_pass(
    engine, prof, schedule, *, affinity=None, model_name="bench-lc",
    replica_id=1,
):
    """Drive the two-turn conversations closed-loop on one engine.
    Returns per-turn records; with ``affinity`` set, each turn also
    consults/records the affinity map (hit-rate accounting)."""
    from gpustack_tpu.engine.engine import GenRequest

    recs = []
    for c, (base, follow) in enumerate(schedule):
        hist = list(base)
        for t in range(2):
            if t == 1:
                hist = hist + follow
            routed = _affinity_turn(
                affinity, model_name, c, t, replica_id
            )
            req = engine.generate(
                GenRequest(
                    prompt_ids=list(hist),
                    max_tokens=prof["output_len"],
                    temperature=0.0, stop_ids=(),
                ),
                timeout=7200,
            )
            recs.append({
                "conv": c, "turn": t, "prompt_len": len(hist),
                "ttft_ms": req.ttft_ms,
                "reused": req.prefix_tokens_reused,
                "affinity_routed": routed,
                "output_ids": list(req.output_ids),
                "req": req,
            })
            hist = hist + req.output_ids
            _wait_for_cache_store(engine, hist)
    return recs


def run_long_context_disagg(pre, dec, prof, schedule):
    """The disaggregated pass: turn 1 runs on the PREFILL-role engine,
    its radix blocks travel the real wire codec (engine/kv_transfer.py
    — content-addressed frames, `have` dedup) into the DECODE-role
    engine's host cache, and turn 2 serves there warm. Returns
    (records, handoff accounting)."""
    from gpustack_tpu.engine import kv_transfer as kt
    from gpustack_tpu.engine.engine import GenRequest

    recs = []
    handoff = {"blocks": 0, "bytes": 0, "seconds": 0.0}
    for c, (base, follow) in enumerate(schedule):
        hist = list(base)
        r1 = pre.generate(
            GenRequest(
                prompt_ids=list(hist), max_tokens=prof["output_len"],
                temperature=0.0, stop_ids=(),
            ),
            timeout=7200,
        )
        recs.append({
            "conv": c, "turn": 0, "prompt_len": len(hist),
            "ttft_ms": r1.ttft_ms, "reused": r1.prefix_tokens_reused,
            "output_ids": list(r1.output_ids), "req": r1,
        })
        hist = hist + r1.output_ids
        _wait_for_cache_store(pre, hist)
        # the handoff: decode pulls exactly what it lacks
        t0 = time.time()
        probe = list(hist) + [0]
        have = dec.host_kv_cache.prefix_keys(probe)
        frames = kt.decode_stream(b"".join(
            kt.export_frames(pre.host_kv_cache, probe, have=have)
        ))
        attached, _, bytes_in = kt.import_frames(
            dec.host_kv_cache, frames
        )
        handoff["seconds"] += time.time() - t0
        handoff["blocks"] += attached
        handoff["bytes"] += bytes_in
        hist2 = hist + follow
        r2 = dec.generate(
            GenRequest(
                prompt_ids=list(hist2), max_tokens=prof["output_len"],
                temperature=0.0, stop_ids=(),
            ),
            timeout=7200,
        )
        recs.append({
            "conv": c, "turn": 1, "prompt_len": len(hist2),
            "ttft_ms": r2.ttft_ms, "reused": r2.prefix_tokens_reused,
            "output_ids": list(r2.output_ids), "req": r2,
        })
        _wait_for_cache_store(dec, hist2 + r2.output_ids)
    handoff["seconds"] = round(handoff["seconds"], 4)
    return recs, handoff


def summarize_long_context(cold_recs, warm_recs, disagg_recs, affinity,
                           handoff):
    """detail.long_context: warm-turn (turn 1) TTFT per pass against
    the colocated cold baseline, affinity hit rate, handoff cost, and
    greedy token parity across every pass."""
    def warm_ttfts(recs):
        return [r["ttft_ms"] for r in recs if r["turn"] == 1]

    parity = all(
        c["output_ids"] == w["output_ids"]
        for c, w in zip(cold_recs, warm_recs)
    )
    if disagg_recs is not None:
        parity = parity and all(
            c["output_ids"] == d["output_ids"]
            for c, d in zip(cold_recs, disagg_recs)
        )
    cold_p50 = _p50(warm_ttfts(cold_recs))
    warm_p50 = _p50(warm_ttfts(warm_recs))
    disagg_p50 = (
        _p50(warm_ttfts(disagg_recs))
        if disagg_recs is not None else None
    )
    lookups = affinity.hits + affinity.misses
    out = {
        "conversations": len(
            {r["conv"] for r in warm_recs}
        ),
        "cold_ttft_ms_p50": round(cold_p50, 1),
        "affinity_warm_ttft_ms_p50": round(warm_p50, 1),
        "disagg_warm_ttft_ms_p50": (
            round(disagg_p50, 1) if disagg_p50 is not None else None
        ),
        # the acceptance lever: warm-turn TTFT on the prefix-affinity-
        # routed replica vs the colocated cold baseline
        "ttft_improvement": (
            round(1.0 - warm_p50 / cold_p50, 3) if cold_p50 else None
        ),
        "disagg_vs_colocated_cold": (
            round(1.0 - disagg_p50 / cold_p50, 3)
            if disagg_p50 is not None and cold_p50 else None
        ),
        "affinity": {
            "hits": affinity.hits,
            "misses": affinity.misses,
            "hit_rate": (
                round(affinity.hits / lookups, 3) if lookups else None
            ),
        },
        "handoff": handoff,
        "token_parity": parity,
        "prefix_tokens_reused": sum(
            r["reused"] for r in warm_recs if r["turn"] == 1
        ),
    }
    return out


# ---------------------- cold-fleet warmup flow ------------------------------


def _fleet_msgs(conv, turn):
    """Deterministic proxy-side message list for (conversation, turn)
    in the multiturn shape — the chat-visible identity of the token
    schedule, so conversation_chain() yields the same keys the proxy
    and the ConvIndex bridge would use in production."""
    msgs = [{"role": "user", "content": f"conv-{conv}-turn-0"}]
    for t in range(1, turn + 1):
        msgs += [
            {"role": "assistant", "content": f"reply-{t - 1}"},
            {"role": "user", "content": f"turn-{t}"},
        ]
    return msgs


def run_cold_fleet_affinity(engine, prof, schedule, affinity,
                            model_name, replica_id=1):
    """Affinity-warm pass on the holder replica: every turn consults
    then records the REAL PrefixAffinityMap (proxy lookup-then-record
    semantics), and every finished turn is recorded into the engine's
    ConvIndex — the same feed /kv/summary scrapes — so the fleet
    directory built afterwards reflects what this replica holds."""
    from gpustack_tpu.engine.engine import GenRequest
    from gpustack_tpu.server.resilience import conversation_chain

    system, users = schedule
    recs = []
    for c, conv in enumerate(users):
        history = list(system)
        for t, user in enumerate(conv):
            history += user
            chain = conversation_chain(model_name, _fleet_msgs(c, t))
            routed = affinity.lookup(chain)
            affinity.record(chain[-1], replica_id, 1)
            req = engine.generate(
                GenRequest(
                    prompt_ids=list(history),
                    max_tokens=prof["output_len"],
                    temperature=0.0, stop_ids=(),
                ),
                timeout=7200,
            )
            recs.append({
                "conv": c, "turn": t, "prompt_len": len(history),
                "ttft_ms": req.ttft_ms,
                "reused": req.prefix_tokens_reused,
                "affinity_routed": routed,
                "output_ids": list(req.output_ids),
                "req": req,
            })
            history += req.output_ids
            _wait_for_cache_store(engine, history)
            if getattr(engine, "kv_conv", None) is not None:
                engine.kv_conv.record(chain, history)
    return recs


def run_cold_fleet_directory(src, dst, prof, schedule, directory,
                             model_name, src_id=1):
    """Directory-routed pass on a COLD second replica: per turn the
    fleet directory is consulted with the proxy's conversation chain;
    a hit names the holder replica, whose blocks travel the real wire
    codec (engine/kv_transfer.py, `have` dedup) into the cold
    replica's host cache before the turn runs there — the in-process
    equivalent of the /kv/pull prefetch path. Returns (records, pull
    accounting)."""
    from gpustack_tpu.engine import kv_transfer as kt
    from gpustack_tpu.engine.engine import GenRequest
    from gpustack_tpu.server.resilience import conversation_chain

    system, users = schedule
    recs = []
    pull = {"blocks": 0, "bytes": 0, "seconds": 0.0, "pulls": 0}
    for c, conv in enumerate(users):
        history = list(system)
        for t, user in enumerate(conv):
            history += user
            chain = conversation_chain(model_name, _fleet_msgs(c, t))
            hit = directory.lookup(chain)
            pulled = 0
            if hit is not None and hit.instance_id == src_id:
                t0 = time.time()
                probe = list(history) + [0]
                have = dst.host_kv_cache.prefix_keys(probe)
                frames = kt.decode_stream(b"".join(
                    kt.export_frames(
                        src.host_kv_cache, probe, have=have
                    )
                ))
                attached, _, bytes_in = kt.import_frames(
                    dst.host_kv_cache, frames
                )
                pull["seconds"] += time.time() - t0
                pull["blocks"] += attached
                pull["bytes"] += bytes_in
                pull["pulls"] += 1
                pulled = attached
            req = dst.generate(
                GenRequest(
                    prompt_ids=list(history),
                    max_tokens=prof["output_len"],
                    temperature=0.0, stop_ids=(),
                ),
                timeout=7200,
            )
            recs.append({
                "conv": c, "turn": t, "prompt_len": len(history),
                "ttft_ms": req.ttft_ms,
                "reused": req.prefix_tokens_reused,
                "pulled_blocks": pulled,
                "output_ids": list(req.output_ids),
                "req": req,
            })
            history += req.output_ids
            _wait_for_cache_store(dst, history)
    pull["seconds"] = round(pull["seconds"], 4)
    return recs, pull


def summarize_cold_fleet(cold_recs, aff_recs, dir_recs, affinity,
                         directory, pull):
    """detail.cold_fleet: warm-turn (turn > 0) TTFT for the affinity
    pass (holder replica, local cache) and the directory pass (cold
    replica warmed over the wire) against the colocated cold baseline;
    cross-replica shared-prefix hits; pull cost; greedy token parity
    across all three passes."""
    def warm_ttfts(recs):
        return [r["ttft_ms"] for r in recs if r["turn"] > 0]

    parity = all(
        c["output_ids"] == a["output_ids"]
        for c, a in zip(cold_recs, aff_recs)
    ) and all(
        c["output_ids"] == d["output_ids"]
        for c, d in zip(cold_recs, dir_recs)
    )
    cold_p50 = _p50(warm_ttfts(cold_recs))
    aff_p50 = _p50(warm_ttfts(aff_recs))
    dir_p50 = _p50(warm_ttfts(dir_recs))
    # a cross-replica hit: a turn on the cold replica that both pulled
    # blocks over the wire and actually reused prefix tokens
    cross = sum(
        1 for r in dir_recs
        if r.get("pulled_blocks", 0) > 0 and r["reused"] > 0
    )
    lookups = affinity.hits + affinity.misses
    snap = directory.snapshot()
    return {
        "conversations": len({r["conv"] for r in dir_recs}),
        "cold_ttft_ms_p50": round(cold_p50, 1),
        "affinity_warm_ttft_ms_p50": round(aff_p50, 1),
        "directory_warm_ttft_ms_p50": round(dir_p50, 1),
        # the acceptance lever: directory-routed warm turns on the
        # cold replica vs affinity-warm turns on the holder
        "directory_vs_affinity": (
            round(dir_p50 / aff_p50, 3) if aff_p50 else None
        ),
        "ttft_improvement": (
            round(1.0 - dir_p50 / cold_p50, 3) if cold_p50 else None
        ),
        "cross_replica_hits": cross,
        "pull": pull,
        "affinity": {
            "hits": affinity.hits,
            "misses": affinity.misses,
            "hit_rate": (
                round(affinity.hits / lookups, 3) if lookups else None
            ),
        },
        "directory": {
            "hits": snap["hits"],
            "misses": snap["misses"],
            "keys": snap["keys"],
            "stale_routes": snap["stale_routes"],
        },
        "token_parity": parity,
        "prefix_tokens_reused_remote": sum(
            r["reused"] for r in dir_recs
        ),
    }


def _run_profile_pass(engine, prof, warm_prompt, prompts, closed_loop):
    """Warm up (compile), then drive one timed pass of ``prompts``
    through ``engine``. Returns (wall_s, finished requests). Pure in
    its token-list inputs so the overlap-off comparison pass replays
    byte-identical traffic."""
    from gpustack_tpu.engine.engine import GenRequest

    def make_req(ids):
        return GenRequest(
            prompt_ids=list(ids),
            max_tokens=prof["output_len"],
            temperature=0.0,
            # random-weight models rarely emit eos, but make
            # termination deterministic regardless:
            stop_ids=(),
        )

    def wait_done(r):
        if not r.done.wait(7200):
            raise TimeoutError(
                f"bench request {r.request_id} unfinished"
            )

    # Warmup: compile prefill bucket + decode step.
    engine.generate(make_req(warm_prompt), timeout=3600)
    reqs = [make_req(p) for p in prompts]
    t0 = time.time()
    for r in reqs:
        engine.submit(r)
        if closed_loop:
            wait_done(r)
    if not closed_loop:
        for r in reqs:
            wait_done(r)
    return time.time() - t0, reqs


def _cmp_summary(overlap_out, overlap_wall, serial_out, serial_wall,
                 parity, depth):
    """detail.overlap_comparison shape: same-box overlap-on vs
    overlap-off tokens/s, so the BENCH_* trajectory shows the async
    engine's delta, not just an absolute number."""
    over_tps = overlap_out / max(1e-9, overlap_wall)
    ser_tps = serial_out / max(1e-9, serial_wall)
    return {
        "overlap_tok_per_s": round(over_tps, 2),
        "serial_tok_per_s": round(ser_tps, 2),
        "speedup": round(over_tps / max(1e-9, ser_tps), 3),
        "token_parity": parity,
        "pipeline_depth": depth,
    }


def _p50(xs):
    return sorted(xs)[len(xs) // 2] if xs else 0.0


def summarize_multiturn(cold_recs, hit_recs):
    """Cold-vs-hit TTFT attribution over PAIRED turns: the same
    (conversation, turn) measured on a cache-off engine and on a
    cache-on engine that actually reused blocks there — plus greedy
    token parity across the two passes (identical traffic must yield
    identical outputs whether or not the cache served the prefix)."""
    hit_ttfts, cold_ttfts = [], []
    parity = True
    for cold, hot in zip(cold_recs, hit_recs):
        parity = parity and cold["output_ids"] == hot["output_ids"]
        if hot["reused"] > 0:
            hit_ttfts.append(hot["ttft_ms"])
            cold_ttfts.append(cold["ttft_ms"])
    cold_p50, hit_p50 = _p50(cold_ttfts), _p50(hit_ttfts)
    return {
        "hit_turns": len(hit_ttfts),
        "total_turns": len(hit_recs),
        "cold_ttft_ms_p50": round(cold_p50, 1),
        "hit_ttft_ms_p50": round(hit_p50, 1),
        # the acceptance lever: prefix-hit TTFT vs cold TTFT, same turns
        "ttft_improvement": (
            round(1.0 - hit_p50 / cold_p50, 3) if cold_p50 else None
        ),
        "token_parity": parity,
        "prefix_tokens_reused": sum(r["reused"] for r in hit_recs),
    }


# Published bf16 dense peak per chip, TFLOP/s, keyed by what JAX reports as
# ``device_kind`` (Google Cloud TPU documentation, system architecture
# pages of v4 / v5e / v5p / v6e). A device that is not here is an error.
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
}

# keys of a result that carry a time or something derived from one: a
# CPU smoke prints counts only
_TIMING_KEY = re.compile(
    r"(_ms($|_)|_s$|_per_s$|ttft|wall|seconds|speedup|improvement|"
    r"^vs_|ratio|mfu|latency|^phases$|^modes$)"
)


def _counts_only(node):
    if isinstance(node, dict):
        return {
            k: _counts_only(v) for k, v in node.items()
            if not _TIMING_KEY.search(k)
        }
    if isinstance(node, list):
        return [_counts_only(v) for v in node]
    return node


def main() -> None:
    import jax

    from gpustack_tpu.utils.compile_cache import enable_compile_cache

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    device = jax.devices()[0]   # a backend that cannot start raises here
    on_tpu = device.platform == "tpu"
    if not smoke and not on_tpu:
        _emit({
            "metric": "error", "value": 0, "unit": "", "vs_baseline": None,
            "detail": {
                "error": (
                    "bench.py measures on a TPU and this process runs on "
                    f"{device.platform!r}; BENCH_SMOKE=1 runs the tiny "
                    "control-flow smoke on the CPU (counts only)"
                ),
            },
        })
        sys.exit(3)
    if on_tpu and device.device_kind not in PEAK_BF16_TFLOPS:
        raise RuntimeError(
            f"no published peak for device kind {device.device_kind!r} "
            f"(known: {sorted(PEAK_BF16_TFLOPS)})"
        )

    import numpy as np

    profile_name = os.environ.get("BENCH_PROFILE", "throughput")
    if profile_name not in PROFILES:
        _emit(
            {
                "metric": "error",
                "value": 0,
                "unit": "",
                "vs_baseline": 0,
                "detail": {
                    "error": f"unknown BENCH_PROFILE {profile_name!r}",
                    "valid": sorted(PROFILES),
                },
            }
        )
        return
    prof = dict(PROFILES[profile_name])
    cfg_name = "tiny" if smoke else os.environ.get("BENCH_MODEL", "llama3-8b")
    if smoke:
        if prof.get("multiturn"):
            # scaled multiturn smoke: small blocks so the tiny prompts
            # still span several cache blocks, prompts long enough that
            # prefill (not fixed overhead) dominates TTFT
            prof = dict(
                conversations=3, turns=3, system_len=384, user_len=128,
                output_len=12, max_slots=2, max_seq_len=2048,
                prefill_chunk=0, host_kv_cache_mb=64, kv_block_tokens=16,
                multiturn=True,
            )
        elif profile_name == "generation-heavy":
            # scaled decode-bound smoke: keep the output:prompt ratio
            # so decode steps still dominate the step mix
            prof = dict(
                prompt_len=16, output_len=48, num_requests=8,
                max_slots=4, max_seq_len=128, prefill_chunk=0,
            )
        elif prof.get("long_context"):
            # scaled disaggregated smoke: prompts span many small
            # blocks so the handoff moves real frames, long enough
            # that prefill dominates TTFT
            prof = dict(
                prompt_len=384, followup_len=96, output_len=12,
                conversations=3, max_slots=2, max_seq_len=2048,
                prefill_chunk=0, host_kv_cache_mb=64,
                kv_block_tokens=16, long_context=True,
            )
        elif prof.get("cold_fleet"):
            # scaled fleet-warmup smoke: small blocks so the shared
            # system prefix spans many blocks and the cross-replica
            # pull moves real frames; 3 turns → 2 warm-turn TTFT
            # samples per conversation on each pass
            prof = dict(
                conversations=3, turns=3, system_len=384, user_len=96,
                output_len=12, max_slots=2, max_seq_len=2048,
                prefill_chunk=0, host_kv_cache_mb=64,
                kv_block_tokens=16, cold_fleet=True,
            )
        else:
            prof = dict(
                prompt_len=56, output_len=16, num_requests=6,
                max_slots=4, max_seq_len=128, prefill_chunk=0,
            )

    engine = build_engine(
        cfg_name, prof["max_slots"], prof["max_seq_len"],
        prof["prefill_chunk"],
        host_kv_cache_mb=prof.get("host_kv_cache_mb", 0),
        kv_block_tokens=prof.get("kv_block_tokens", 0),
        kv_cache_int8=prof.get("kv_cache_int8", False),
    )
    engine.start()
    rng = np.random.default_rng(0)
    vocab = engine.cfg.vocab_size
    pipeline_depth = engine.pipeline_depth

    multiturn_detail = None
    long_context_detail = None
    cold_fleet_detail = None
    mt_ctx = prompts = warm_prompt = None
    closed_loop = bool(prof.get("closed_loop"))
    if prof.get("long_context"):
        # Three passes over ONE seeded schedule (see the profile
        # comment): colocated cold → prefix-affinity warm → fully
        # disaggregated (prefill engine → wire handoff → decode
        # engine). Warmup conversations compile every prefill bucket +
        # prefix-continuation key per engine first.
        from gpustack_tpu.server.resilience import PrefixAffinityMap

        schedule = long_context_schedule(0, vocab, prof)
        warm_sched = long_context_schedule(
            1, vocab, dict(prof, conversations=1)
        )
        cache = engine.host_kv_cache
        engine.host_kv_cache = None
        run_long_context_pass(engine, prof, warm_sched)
        cold_recs = run_long_context_pass(engine, prof, schedule)
        engine.host_kv_cache = cache
        run_long_context_pass(engine, prof, warm_sched)
        amap = PrefixAffinityMap()
        t0 = time.time()
        hit_recs = run_long_context_pass(
            engine, prof, schedule, affinity=amap
        )
        wall = time.time() - t0
        disagg_recs = handoff = None
        if not on_tpu:
            # the disaggregated pass needs a second engine (the decode
            # role); a real-TPU run skips it rather than double weight
            # HBM — the affinity-vs-cold comparison still lands
            dec_engine = build_engine(
                cfg_name, prof["max_slots"], prof["max_seq_len"],
                prof["prefill_chunk"],
                host_kv_cache_mb=prof.get("host_kv_cache_mb", 0),
                kv_block_tokens=prof.get("kv_block_tokens", 0),
                kv_cache_int8=prof.get("kv_cache_int8", False),
            )
            dec_engine.start()
            run_long_context_pass(dec_engine, prof, warm_sched)
            disagg_recs, handoff = run_long_context_disagg(
                engine, dec_engine, prof, schedule
            )
            dec_engine.stop()
        engine.stop()
        long_context_detail = summarize_long_context(
            cold_recs, hit_recs, disagg_recs, amap, handoff
        )
        reqs = [r["req"] for r in hit_recs]
    elif prof.get("cold_fleet"):
        # Three passes over ONE seeded schedule: colocated cold (cache
        # detached) → affinity-warm on the holder replica (REAL
        # PrefixAffinityMap, ConvIndex fed per turn) → directory-warm
        # on a SECOND replica built cold, warmed per turn through the
        # REAL ClusterKVDirectory + wire-codec pull. Warmups compile
        # every prefill bucket and prefix-continuation key per engine
        # (two warmup conversations: the second exercises the cross-
        # conversation match shape).
        from gpustack_tpu.server.kv_directory import ClusterKVDirectory
        from gpustack_tpu.server.resilience import PrefixAffinityMap

        schedule = multiturn_schedule(0, vocab, prof)
        warm_sched = multiturn_schedule(
            1, vocab,
            dict(prof, conversations=min(2, prof["conversations"])),
        )
        cache = engine.host_kv_cache
        engine.host_kv_cache = None
        run_multiturn(engine, prof, warm_sched)
        cold_recs = run_multiturn(engine, prof, schedule)
        engine.host_kv_cache = cache
        run_multiturn(engine, prof, warm_sched)
        amap = PrefixAffinityMap()
        t0 = time.time()
        aff_recs = run_cold_fleet_affinity(
            engine, prof, schedule, amap, "bench-cf", replica_id=1
        )
        wall = time.time() - t0
        # the fleet directory, fed exactly as the scrape loop feeds
        # it: the holder replica's ConvIndex summary with residency
        # re-checked against its cache NOW
        directory = ClusterKVDirectory()
        directory.update(
            1, 1, engine.kv_conv.summary(engine.host_kv_cache)
        )
        # replica 2: built completely cold (its own cache, its own
        # warmup on independent tokens — compile, not content)
        dst = build_engine(
            cfg_name, prof["max_slots"], prof["max_seq_len"],
            prof["prefill_chunk"],
            host_kv_cache_mb=prof.get("host_kv_cache_mb", 0),
            kv_block_tokens=prof.get("kv_block_tokens", 0),
            kv_cache_int8=prof.get("kv_cache_int8", False),
        )
        dst.start()
        run_multiturn(dst, prof, warm_sched)
        dir_recs, pull = run_cold_fleet_directory(
            engine, dst, prof, schedule, directory, "bench-cf",
            src_id=1,
        )
        dst.stop()
        engine.stop()
        cold_fleet_detail = summarize_cold_fleet(
            cold_recs, aff_recs, dir_recs, amap, directory, pull
        )
        reqs = [r["req"] for r in aff_recs]
    elif prof.get("multiturn"):
        # Two passes over the SAME seeded schedule: cache-off (cold)
        # then the cache-on engine built above (hit), pairing each
        # turn's TTFT so the cache win is measured like-for-like and
        # greedy outputs are parity-checked across the passes. Each
        # pass first runs a warmup conversation on independent tokens —
        # compiles every prefill bucket and the prefix-continuation jit
        # keys, so cold-vs-hit compares prefill work, not compile time.
        schedule = multiturn_schedule(0, vocab, prof)
        # two warmup conversations: the second exercises the CROSS-
        # conversation match shape (system prompt only), which is a
        # different prefix-continuation jit key than within-conversation
        # matches — one warmup conversation would leave it to compile
        # mid-measurement
        warm_sched = multiturn_schedule(
            1, vocab, dict(prof, conversations=min(2, prof["conversations"]))
        )
        # cold pass on the SAME engine with the cache detached: a second
        # engine would double weight HBM (an 8B model would not fit
        # twice on one chip), and same-engine passes share jit warmup
        cache = engine.host_kv_cache
        engine.host_kv_cache = None
        run_multiturn(engine, prof, warm_sched)
        cold_recs = run_multiturn(engine, prof, schedule)
        engine.host_kv_cache = cache
        run_multiturn(engine, prof, warm_sched)
        t0 = time.time()
        hit_recs = run_multiturn(engine, prof, schedule)
        wall = time.time() - t0
        engine.stop()
        h = engine.health()
        multiturn_detail = dict(
            summarize_multiturn(cold_recs, hit_recs),
            conversations=prof["conversations"],
            turns=prof["turns"],
            kv_cache_blocks=h["kv_cache_blocks"],
            kv_cache_host_mb=round(h["kv_cache_host_bytes"] / 2**20, 1),
        )

        reqs = [r["req"] for r in hit_recs]
        mt_ctx = (schedule, warm_sched, hit_recs, wall)
    else:
        warm_prompt = rng.integers(
            1, vocab, prof["prompt_len"]
        ).tolist()
        prompts = [
            rng.integers(1, vocab, prof["prompt_len"]).tolist()
            for _ in range(prof["num_requests"])
        ]
        wall, reqs = _run_profile_pass(
            engine, prof, warm_prompt, prompts, closed_loop
        )
        engine.stop()

    out_tokens = sum(len(r.output_ids) for r in reqs)
    in_tokens = sum(len(r.prompt_ids) for r in reqs)
    ttfts = sorted(r.ttft_ms for r in reqs)
    p50_ttft = ttfts[len(ttfts) // 2]

    # per-phase latency decomposition through the observability
    # histograms (gpustack_tpu/observability/metrics.py — the same
    # estimator the dashboards' histogram_quantile uses), so the bench
    # trajectory attributes a regression to prefill (ttft) vs decode
    # instead of one end-to-end number
    from gpustack_tpu.observability.metrics import Histogram

    phase_hists = {
        "ttft": Histogram("bench_ttft_seconds"),
        "decode": Histogram("bench_decode_seconds"),
        "e2e": Histogram("bench_e2e_seconds"),
    }
    for r in reqs:
        ttft_s = max(0.0, r.first_token_at - r.submitted_at)
        e2e_s = max(0.0, r.finished_at - r.submitted_at)
        phase_hists["ttft"].observe(ttft_s)
        phase_hists["decode"].observe(max(0.0, e2e_s - ttft_s))
        phase_hists["e2e"].observe(e2e_s)

    def _quantiles_ms(h):
        return {
            f"p{int(q * 100)}_ms": round((h.quantile(q) or 0.0) * 1e3, 1)
            for q in (0.5, 0.95, 0.99)
        }

    phases = {name: _quantiles_ms(h) for name, h in phase_hists.items()}

    # flight-derived utilization (observability/flight.py): what the
    # scheduler actually did per step — tokens/step, padding waste,
    # occupancy, per-mode step time — so BENCH_r* measures engine
    # efficiency, not just harness health. The recorder's own cost
    # rides along (overhead_ratio; tier-1 asserts <1%).
    fl = engine.flight.aggregate()
    flight_detail = {
        "steps": fl.get("steps", 0),
        "tokens_per_step": fl.get("tokens_per_step", 0.0),
        "padding_waste_pct": fl.get("padding_waste_pct", 0.0),
        "occupancy_p50": fl.get("occupancy_p50", 0.0),
        "occupancy_p95": fl.get("occupancy_p95", 0.0),
        "queue_wait_ms_p50": fl.get("queue_wait_ms_p50", 0.0),
        "queue_wait_ms_max": fl.get("queue_wait_ms_max", 0.0),
        "spec_acceptance": fl.get("spec_acceptance"),
        "modes": fl.get("modes", {}),
        "recorder_overhead_ratio": fl.get("overhead_ratio", 0.0),
    }

    # Per-chip denominator from the mesh the engine actually ran on —
    # the engine's default plan is single-chip even when more chips are
    # visible, so counting all visible chips would deflate the number.
    n_chips = max(1, int(engine.runner.mesh.size))
    value = out_tokens / wall / n_chips

    # MFU estimate (real-hardware runs): ~2*N_params flops per token
    # (forward matmuls), against the chip generation's bf16 dense peak —
    # int8 weight-only still feeds the MXU bf16 operands here, so the
    # bf16 peak is the honest denominator.
    mfu = None
    if on_tpu:
        n_params = sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(
                engine.runner.params
            )
        )
        peak = PEAK_BF16_TFLOPS[device.device_kind] * 1e12
        model_flops = 2.0 * n_params * (out_tokens + in_tokens)
        mfu = round(model_flops / wall / (peak * n_chips), 4)
    # vs_baseline: the absolute 189 tok/s/chip anchor applies only to a
    # real-hardware run of the throughput profile (the anchor is a
    # throughput number) — everywhere else the reference point is the
    # MOST RECENT PRIOR BENCH_r* round with the same profile on the
    # same platform class, so the trajectory is self-describing
    # (vs_baseline > 1 = faster than last round) instead of null.
    vs_baseline_ref = None
    if smoke:
        vs_baseline = None   # a CPU smoke has no rate to compare
    elif profile_name == "throughput":
        vs_baseline = round(value / BASELINE_OUT_TPS_PER_CHIP, 3)
        vs_baseline_ref = {
            "kind": "anchor",
            "value": BASELINE_OUT_TPS_PER_CHIP,
        }
    else:
        prev = prior_round_value(profile_name, smoke)
        if prev is not None:
            vs_baseline = round(value / prev["value"], 3)
            vs_baseline_ref = dict(prev, kind="prev-round")
        else:
            vs_baseline = None   # first round of this profile/platform
    # Overlap-on vs overlap-off on the same box (CPU passes only — a
    # real TPU run must not spend chip time on a reference rerun): the
    # measured run above used the engine's default dispatch-ahead
    # pipeline; replay identical traffic through a pipeline_depth=0
    # serial engine and record both sides, with greedy token parity.
    overlap_cmp = None
    if (
        not on_tpu
        and os.environ.get("BENCH_OVERLAP_COMPARE", "1") == "1"
        and pipeline_depth > 0
        # long-context and cold-fleet measure routing/handoff, not
        # overlap: a serial rerun of their multi-pass flows would
        # double their wall for no signal
        and not prof.get("long_context")
        and not prof.get("cold_fleet")
    ):
        serial_engine = build_engine(
            cfg_name, prof["max_slots"], prof["max_seq_len"],
            prof["prefill_chunk"],
            host_kv_cache_mb=prof.get("host_kv_cache_mb", 0),
            kv_block_tokens=prof.get("kv_block_tokens", 0),
            kv_cache_int8=prof.get("kv_cache_int8", False),
            pipeline_depth=0,
        )
        serial_engine.start()
        if mt_ctx is not None:
            schedule, warm_sched, hit_recs, _ = mt_ctx
            run_multiturn(serial_engine, prof, warm_sched)
            t0 = time.time()
            s_recs = run_multiturn(serial_engine, prof, schedule)
            s_wall = time.time() - t0
            serial_engine.stop()
            overlap_cmp = _cmp_summary(
                sum(len(r["output_ids"]) for r in hit_recs), wall,
                sum(len(r["output_ids"]) for r in s_recs), s_wall,
                all(
                    a["output_ids"] == b["output_ids"]
                    for a, b in zip(hit_recs, s_recs)
                ),
                pipeline_depth,
            )
        else:
            s_wall, s_reqs = _run_profile_pass(
                serial_engine, prof, warm_prompt, prompts, closed_loop
            )
            serial_engine.stop()
            overlap_cmp = _cmp_summary(
                out_tokens, wall,
                sum(len(r.output_ids) for r in s_reqs), s_wall,
                all(
                    a.output_ids == b.output_ids
                    for a, b in zip(reqs, s_reqs)
                ),
                pipeline_depth,
            )

    result = {
        "metric": (
            f"output_tok_per_s_per_chip ({cfg_name} int8, "
            f"{profile_name} profile)"
        ),
        "value": round(value, 2),
        "unit": "tok/s/chip",
        "vs_baseline": vs_baseline,
        "detail": {
            "profile": profile_name,
            "requests": len(reqs),
            "output_tokens": out_tokens,
            "input_tokens": in_tokens,
            "wall_s": round(wall, 2),
            "total_tok_per_s": round((out_tokens + in_tokens) / wall, 2),
            "p50_ttft_ms": round(p50_ttft, 1),
            "phases": phases,
            "flight": flight_detail,
            "mfu_est": mfu,
            "n_chips": n_chips,
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
            # read by prior_round_value: smoke and hardware rounds are
            # separate trajectories
            "tpu_unavailable": not on_tpu,
        },
    }
    if multiturn_detail is not None:
        result["detail"]["multiturn"] = multiturn_detail
    if long_context_detail is not None:
        result["detail"]["long_context"] = long_context_detail
    if cold_fleet_detail is not None:
        result["detail"]["cold_fleet"] = cold_fleet_detail
    if overlap_cmp is not None:
        result["detail"]["overlap_comparison"] = overlap_cmp
    result["detail"]["pipeline_depth"] = pipeline_depth
    if vs_baseline_ref is not None:
        result["detail"]["vs_baseline_ref"] = vs_baseline_ref
    result["detail"]["host_overlap_ratio"] = fl.get(
        "host_overlap_ratio", 0.0
    )
    # overlap buys wall time only when host threads have a core to run
    # on while the device computes — a 1-core container caps the
    # comparison at parity; record the context with the number
    result["detail"]["host_cores"] = os.cpu_count() or 1
    if smoke:
        # counts only: no time, no rate, nothing under a per-chip name
        result = {
            "metric": f"output_tokens (SMOKE tiny on cpu, {profile_name})",
            "value": out_tokens,
            "unit": "tokens",
            "vs_baseline": None,
            "detail": _counts_only(result["detail"]),
        }
    # round file first, THEN the compact final line
    _emit_round_file(result)
    _emit(result)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — the artifact line must print
        import traceback

        traceback.print_exc()
        _emit({
            "metric": "error", "value": 0, "unit": "",
            "vs_baseline": None,
            "detail": {"error": repr(e)[:300]},
        })
        sys.exit(1)
