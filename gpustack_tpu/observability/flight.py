"""Engine flight recorder: one bounded record per scheduler step.

The scheduler loop (engine/engine.py ``Engine.step``) is where every
speed claim is won or lost — slots idle, prefill buckets padded, spec
proposals rejected — yet until now nothing recorded what it actually
did per step. The flight recorder is the measurement layer the
multi-chip speed push spends (ROADMAP item 1): a fixed-capacity ring of
per-step records cheap enough to stay ALWAYS ON (self-measured overhead
is exported; the tier-1 smoke asserts it under 1% of step wall time),
served raw at engine ``GET /debug/flight`` and aggregated into the
Prometheus families the fleet rollup (``GET /v2/debug/fleet``) and the
autoscaler-to-be consume.

Record vocabulary (per step):

- ``mode`` — what the step mostly did: ``prefill`` (one-shot),
  ``prefill_chunk`` (one chunk of a long prompt), ``decode`` (one
  decode_step over all slots), ``spec_verify`` (speculative verify).
- ``dur_ms`` — step wall time.
- ``slots_used``/``slots_total``, ``waiting``, ``oldest_wait_ms`` —
  saturation: occupancy, queue depth, and how long the queue head has
  been waiting.
- ``tokens_real``/``tokens_padded`` — tokens the step genuinely needed
  vs. tokens the padded dispatch actually computed (bucket padding on
  prefill, inactive slots on decode): padding-waste % is the
  utilization gap jit bucketing costs.
- ``tokens_out`` — tokens delivered to requests during the step (the
  engine's fetch pipeline lags by a couple of steps; delivery-side
  counting smooths that honestly).
- ``spec_proposed``/``spec_accepted`` — speculation economics.
- ``kv_blocks``/``kv_reused_total`` — host KV cache pressure.
- ``host_overlap_ms`` — host work (detokenization, SSE stream writes,
  KV staging copies) done on worker threads DURING this step instead of
  on the scheduler: the overlapped engine's win, phase-attributed.
  ``host_overlap_ratio`` (aggregate) is overlapped host ms / step wall
  ms and can exceed 1.0 when several workers overlap one step.
- ``drain_ms``/``admit_ms``/``chunk_ms``/``dispatch_ms``/``wait_ms`` —
  the self time of the step's phases (``StepPhases``): delivering
  fetched tokens, admitting queued requests (their prefill dispatch
  included), one prefill chunk, the decode dispatch, and every place
  the scheduler's thread blocked on the device. ``dur_ms - wait_ms`` is
  the host's own work in the step (``host_ms_p50`` per mode in the
  aggregate).
- ``cpu_ms`` — the CPU time of the scheduler's own thread in the step
  (``time.thread_time()`` at its start and at its end). ``dur_ms -
  wait_ms - cpu_ms`` is the time the thread wanted to run and did not:
  the interpreter held by another thread, or a block inside a runtime
  call that no ``wait`` phase covers. The thread's clock is as fine as
  the host's kernel keeps it: where it moves in ticks of 10 ms (the
  chip's host), a step of 6 ms reads 0.0 or 10.0, a step of two seconds
  is told to a tick, and over many steps the mean is right where a
  single step's number is not (``cpu_ms_mean`` per mode in the
  aggregate). Absent from a record whose writer gave none (the stub
  engine's).
- ``admitted``/``first_tokens`` — per request, in the step that caused
  them: ``[trace_id, wait_ms]`` for each request taken from the queue
  (now - submitted), ``[trace_id, ms]`` for each request whose first
  token was handed on (first token - submitted). Empty in most steps.
  ``trace_id`` is the hop trace's (``GET /v2/debug/traces?trace_id=``),
  empty for a request made in-process.
- ``traced``/``compiled`` — programs lowered and programs compiled (a
  persistent-cache miss) in this process since the last record.
- ``programs`` — only in a step in which a program's record closed
  (``observability/startup.py ProgramLog``): ``[name, lower_ms,
  load_ms, cached]`` for each, Python tracing and lowering, then the
  backend's compile or (``cached``) its load from the persistent cache.
  A steady step's record does not have the key.
- ``moe_dispatch`` — in a step that ran a prefill program of a model
  with experts: prompt tokens by the dispatch the program was traced
  with (``{"grouped": n}`` / ``{"dense": n}``,
  ``models/transformer.py moe_dispatch``). Absent otherwise.
- ``attn`` — for a latent-attention (MLA) model, the form of attention
  the step ran: ``mla_flash`` / ``mla_xla`` in a step that ran a
  prefill program (decompressed, by the bucket's kernel),
  ``mla_absorbed`` in a decode or verify step (over the latent cache).
  Absent for any other model.
- ``kv_live_pct`` — in a step that dispatched a decode step: the cached
  positions its live slots attend (each one's prompt and output so far,
  as the scheduler counts them: no device read, and behind the device
  by the fetch pipeline's lag) over the ``slots x max_len`` the cache
  allocates. It is the share of the cache a decode step has to read:
  all the decode kernel reads (``decode_attention: kernel`` in
  ``/healthz``), where the XLA form reads every position. Absent
  otherwise.
- ``moe_read_pct`` — in a step that fetched a decode step's result, for
  a model with experts: the held experts whose weights those decode
  steps read, summed over the layers with experts (the decode program's
  own count, one int32 beside the sampled tokens, so behind the device
  by the fetch pipeline's lag), over ``held x layers`` a step. Under
  ``decode_moe_dispatch: touched`` (``/healthz``) it is the share of the
  experts' weights the live rows' routing touched; under ``dense`` 100.
  Absent otherwise.
- ``state_slots``, ``ssm_tokens``, ``state_mixer`` — for a model that
  keeps a recurrent state a slot beside its rows: the slots whose state
  the step's decode step moved on, one token each, the prompt tokens
  the step sent through the chunked scan (a prefill), and the kind of
  mixer that keeps the state (``"ssm"`` Mamba-2 layers, ``"delta"``
  gated-delta-rule layers with a decay a head, ``"kda"`` those with a
  decay a key channel). Absent for any other model.

- ``window_rows``, ``full_rows`` — for a stack that keeps its sliding
  layers' rows at window size (``/healthz`` ``cache.window_bytes``):
  the cached rows the step's sliding layers and its full layers
  attended, over slots, positions and layers of the kind (a decode step:
  ``min(length, window)`` and ``length`` a slot a layer; a prefill the
  band's and the triangle's). Absent for any other model.

- ``passes_denoise``, ``passes_commit``, ``tokens_decided``,
  ``blocks_done`` — for a model generated by diffusion over blocks
  (``/healthz`` ``diffusion``), whose step (``mode: denoise``) is one
  program over every slot's block of ``L`` rows: of the passes whose
  results the step fetched, the live slots' that denoised (decided 0 to
  ``L`` positions each, ``tokens_decided`` in all), the ones that
  committed a block's final rows, and the blocks so completed (one a
  commit pass). ``tokens_real`` is then ``L`` rows a live slot a pass
  and ``tokens_out`` the tokens handed on to requests, 0 to ``L`` a
  slot: a decided position waits for every position before it. Absent
  for any other model.

Cumulative (not per-record): ``idle_wait_s_total`` — seconds the
scheduler parked on its wakeup condition instead of busy-polling (the
old 2 ms sleep loop, measured as saved spin); ``rollback_tokens_total``
— speculatively generated tokens the pipeline rolled back because a
lagged fetch revealed their slot finished/diverged (the cost of
dispatch-ahead, which must stay a sliver of tokens_out).

Everything here is dependency-free and import-light (no jax) so the
stub engine and bench can share the exact contract.
"""

from __future__ import annotations

import bisect
import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from gpustack_tpu.observability.startup import (  # noqa: F401
    BACKEND_COMPILE_EVENT,
    CACHE_HIT_EVENT,
    LOWERING_EVENT,
    ProgramLog,
    brief,
)

MODES = ("prefill", "prefill_chunk", "decode", "spec_verify")

# the phases of a scheduler step, in the order the step runs them;
# ``wait`` is innermost (inside ``drain``, ``chunk`` or ``admit``)
PHASES = ("drain", "admit", "chunk", "dispatch", "wait")

# step-time buckets: µs-scale stub steps through multi-second chunked
# prefills on real hardware
STEP_BUCKETS_S = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)

DEFAULT_CAPACITY = 2048


class _Phase:
    """One phase of ``StepPhases``, entered with ``with``. Not
    re-entrant: no phase of a step nests inside itself."""

    __slots__ = ("_owner", "_index", "_span", "_t0", "_inner", "_outer", "_ann")

    def __init__(self, owner: "StepPhases", name: str):
        self._owner = owner
        self._index = PHASES.index(name)
        self._span = "sched." + name
        self._t0 = self._inner = 0.0
        self._outer: Optional["_Phase"] = None
        self._ann = None

    def __enter__(self) -> None:
        owner = self._owner
        self._outer, owner._open = owner._open, self
        self._inner = 0.0
        if owner.annotate is not None:
            self._ann = owner.annotate(self._span)
            self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        took = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        owner = self._owner
        owner.seconds[self._index] += took - self._inner
        outer, self._outer = self._outer, None
        if outer is not None:
            outer._inner += took
        owner._open = outer


class StepPhases:
    """Self time of the phases of one scheduler step, for one thread.

    ``with phases.drain: ...`` adds the seconds spent inside to drain's
    entry of ``seconds`` (one a phase, in the order of ``PHASES``), less
    the seconds of any phase entered inside it (``wait``): a phase's time is its self time, so the phases of a
    step add up to no more than the step. While ``annotate`` is set (the
    engine sets it to ``jax.profiler.TraceAnnotation`` for the steps of
    an open profiler capture, and to None otherwise) every phase is also
    entered as ``annotate("sched.<phase>")``, which puts the same span
    on the profiler's clock beside the device's operations."""

    def __init__(self) -> None:
        self.seconds: List[float] = [0.0] * len(PHASES)
        self.annotate: Optional[Callable[[str], Any]] = None
        self._open: Optional[_Phase] = None
        for name in PHASES:
            setattr(self, name, _Phase(self, name))

    def reset(self) -> None:
        """A new step: a new list, so a record may keep the last one."""
        self.seconds = [0.0] * len(PHASES)


def _pctl(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


def aggregate_records(
    entries: List[Dict[str, Any]],
    slots_total: int,
    overhead_ratio: float = 0.0,
) -> Dict[str, Any]:
    """Utilization aggregates over a list of step records (the ring, a
    window of it, or a profiler capture's slice)."""
    out: Dict[str, Any] = {
        "steps": len(entries),
        "slots_total": slots_total,
        "overhead_ratio": round(overhead_ratio, 6),
    }
    if not entries:
        out["modes"] = {}
        return out
    by_mode: Dict[str, List[float]] = {}
    host_by_mode: Dict[str, List[float]] = {}
    cpu_by_mode: Dict[str, List[float]] = {}
    occ: List[float] = []
    waits: List[float] = []
    real = padded = tokens_out = proposed = accepted = 0
    prompt = 0
    overlap_ms = dur_ms = 0.0
    for e in entries:
        by_mode.setdefault(e["mode"], []).append(e["dur_ms"])
        host_by_mode.setdefault(e["mode"], []).append(
            e["dur_ms"] - e.get("wait_ms", 0.0)
        )
        if "cpu_ms" in e:
            cpu_by_mode.setdefault(e["mode"], []).append(e["cpu_ms"])
        occ.append(e["slots_used"] / max(1, slots_total))
        waits.append(e["oldest_wait_ms"])
        real += e["tokens_real"]
        padded += e["tokens_padded"]
        tokens_out += e["tokens_out"]
        prompt += e.get("prompt_tokens", 0)
        proposed += e["spec_proposed"]
        accepted += e["spec_accepted"]
        overlap_ms += e.get("host_overlap_ms", 0.0)
        dur_ms += e["dur_ms"]
    occ.sort()
    waits.sort()
    span_s = (
        max(1e-9, entries[-1]["ts"] - entries[0]["ts"])
        if len(entries) > 1 else None
    )
    out["modes"] = {
        mode: {
            "steps": len(durs),
            "step_ms_p50": round(_pctl(sorted(durs), 0.5), 3),
            "step_ms_p95": round(_pctl(sorted(durs), 0.95), 3),
            # the step less the time its thread blocked on the device
            "host_ms_p50": round(
                _pctl(sorted(host_by_mode[mode]), 0.5), 3
            ),
            # the thread's own CPU time a step: the mean, which a
            # clock that moves in ticks longer than a step still gets
            # right (records with ``cpu_ms``)
            **({"cpu_ms_mean": round(
                sum(cpu_by_mode[mode]) / len(cpu_by_mode[mode]), 3
            )} if mode in cpu_by_mode else {}),
        }
        for mode, durs in sorted(by_mode.items())
    }
    out.update(
        occupancy_p50=round(_pctl(occ, 0.5), 4),
        occupancy_p95=round(_pctl(occ, 0.95), 4),
        queue_wait_ms_p50=round(_pctl(waits, 0.5), 2),
        queue_wait_ms_max=round(waits[-1], 2),
        tokens_real=real,
        tokens_padded=padded,
        padding_waste_pct=(
            round(100.0 * (1.0 - real / padded), 2) if padded else 0.0
        ),
        tokens_out=tokens_out,
        prompt_tokens=prompt,
        tokens_per_step=round(tokens_out / len(entries), 3),
        spec_proposed=proposed,
        spec_accepted=accepted,
        spec_acceptance=(
            round(accepted / proposed, 4) if proposed else None
        ),
        kv_blocks=entries[-1]["kv_blocks"],
        kv_reused_total=entries[-1]["kv_reused_total"],
        host_overlap_ms=round(overlap_ms, 3),
        # overlapped host work / scheduler step wall time; > 1.0 means
        # several worker threads overlapped the same step
        host_overlap_ratio=(
            round(overlap_ms / dur_ms, 4) if dur_ms else 0.0
        ),
    )
    if span_s:
        out["tokens_out_per_s"] = round(tokens_out / span_s, 2)
    return out


# concurrency contract (checked by `python -m gpustack_tpu.analysis`,
# rule guarded-by): one writer (the engine scheduler's record/note_*
# calls), many readers (HTTP exporters, bench) — every touch of the
# ring, histogram, counters, and self-measurement under `_mu`; how far
# into the program log the last record read is the writer's alone.
GUARDED_BY = {
    "_programs_seen": ("record",),
    "_programs_version": ("record",),
    "_ring": "_mu",
    "_unfolded": "_mu",
    "_hist": "_mu",
    "_tokens_real_total": "_mu",
    "_tokens_padded_total": "_mu",
    "_tokens_out_total": "_mu",
    "_prompt_tokens_total": "_mu",
    "_moe_prompt_tokens_total": "_mu",
    "_decode_kv_live_total": "_mu",
    "_decode_kv_allocated_total": "_mu",
    "_moe_decode_read_total": "_mu",
    "_moe_decode_held_total": "_mu",
    "_ssm_tokens_total": "_mu",
    "_state_mixer": "_mu",
    "_attn_rows_total": "_mu",
    "_diffusion_total": "_mu",
    "_spec_proposed_total": "_mu",
    "_spec_accepted_total": "_mu",
    "_last_slots_used": "_mu",
    "_last_waiting": "_mu",
    "_last_oldest_wait_s": "_mu",
    "_last_kv_blocks": "_mu",
    "_host_overlap_s_total": "_mu",
    "idle_wait_s_total": "_mu",
    "rollback_tokens_total": "_mu",
    "_record_s": "_mu",
    "_step_s": "_mu",
}


class FlightRecorder:
    """Bounded ring of per-step records + cumulative counters.

    ``record`` is called from exactly one thread (the engine scheduler);
    readers (HTTP exporters, bench) take the lock only to copy. The
    recorder measures its own cost: ``overhead_ratio()`` is cumulative
    seconds spent inside ``record`` divided by cumulative step wall
    time — exported so "observability is free" stays a measured claim,
    never an assumption.
    """

    def __init__(
        self,
        slots_total: int,
        capacity: int = DEFAULT_CAPACITY,
        programs: Optional[ProgramLog] = None,
    ):
        """``programs`` is the log of the process's programs
        (``startup.process_programs()`` for an engine: it counts from
        the process's start); without one the recorder has a log of its
        own that nothing feeds."""
        self.slots_total = max(1, int(slots_total))
        self._mu = threading.Lock()
        # tuples, not dicts: the write path is on the scheduler's step
        # budget (the tier-1 smoke asserts <1% of step wall time), and
        # a 14-key dict per step costs ~10x a tuple append. snapshot()
        # re-materializes dicts on the (cold) read side.
        self._ring: deque = deque(maxlen=max(16, int(capacity)))
        # The step path only appends its row: the histogram and the
        # cumulative counters are brought up to date from the ring when
        # somebody reads them (``_fold_locked``), or every ``_fold_at`` rows at
        # the latest, well before the ring could drop a row unread. A
        # record then costs the scheduler about half of what it did (a
        # record of 3 us in a loop is 9 us after a millisecond of other
        # work, most of it these thirty read-modify-writes: PR 46). The
        # counters are read through properties of their old names.
        self._unfolded = 0
        self._fold_at = min(256, self._ring.maxlen // 2)
        # per-mode step-time histogram: plain lists, single writer
        # (same torn-read tolerance as the engine's LatencyHistogram)
        self._hist: Dict[str, List] = {}
        self._tokens_real_total = 0
        self._tokens_padded_total = 0
        self._tokens_out_total = 0
        self._prompt_tokens_total = 0
        # prompt tokens by expert dispatch; empty for a dense model
        self._moe_prompt_tokens_total: Dict[str, int] = {}
        # cached positions over the decode steps: what their live slots
        # attended, and what the cache allocates (slots x max_len a step)
        self._decode_kv_live_total = 0
        self._decode_kv_allocated_total = 0
        # expert weights over the decode steps fetched (a model with
        # experts): the held experts a step read, and held x layers
        self._moe_decode_read_total = 0
        self._moe_decode_held_total = 0
        # tokens through the layers that keep a recurrent state, by the
        # program that took them, and the kind of mixer that keeps it;
        # None until such a step is recorded
        self._ssm_tokens_total: Optional[Dict[str, int]] = None
        self._state_mixer: Optional[str] = None
        # cached rows attended by kind of layer (a stack with a window
        # store); None until such a step is recorded
        self._attn_rows_total: Optional[Dict[str, int]] = None
        # generation by diffusion over blocks: the live slots' passes by
        # kind, the blocks they completed and the positions they
        # decided; None until such a step is recorded
        self._diffusion_total: Optional[Dict[str, int]] = None
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._last_slots_used = 0
        self._last_waiting = 0
        self._last_oldest_wait_s = 0.0
        self._last_kv_blocks = 0
        # overlapped-engine accounting (ISSUE 12): cumulative host work
        # overlapped with device compute, scheduler idle-park seconds
        # (the spin the condition-variable wakeup saves), and tokens the
        # dispatch-ahead pipeline rolled back after a lagged fetch
        self._host_overlap_s_total = 0.0
        self.idle_wait_s_total = 0.0
        self.rollback_tokens_total = 0
        # the programs lowered / compiled in this process, and how far
        # the last step record read: (lowered, compiled, records closed)
        self.programs = programs if programs is not None else ProgramLog()
        self._programs_version = self.programs.version
        self._programs_seen: Tuple[int, int, int] = self.programs.counts()
        # self-measurement
        self._record_s = 0.0
        self._step_s = 0.0

    # ---- write side (scheduler thread) --------------------------------

    def record(
        self,
        *,
        dur_s: float,
        mode: str,
        slots_used: int,
        waiting: int,
        oldest_wait_s: float,
        tokens_real: int,
        tokens_padded: int,
        tokens_out: int,
        prompt_tokens: int = 0,
        spec_proposed: int = 0,
        spec_accepted: int = 0,
        kv_blocks: int = 0,
        kv_reused_total: int = 0,
        host_overlap_s: float = 0.0,
        cpu_s: Optional[float] = None,
        phases_s: Sequence[float] = (0.0,) * len(PHASES),
        admitted: Sequence = (),
        first_tokens: Sequence = (),
        moe_dispatch: Optional[Dict[str, int]] = None,   # empty as None
        attn: Optional[str] = None,
        kv_live: int = 0,
        kv_allocated: int = 0,     # 0: the step dispatched no decode step
        moe_read: int = 0,
        moe_held: int = 0,         # 0: the step fetched no experts' count
        # (state_slots, ssm_tokens, the mixer's kind)
        ssm: Optional[Sequence[Any]] = None,
        attn_rows: Optional[Sequence[int]] = None,  # (window_rows, full_rows)
        # (slot-passes that denoised, that committed, positions decided)
        diffusion: Optional[Sequence[int]] = None,
    ) -> Optional[Sequence[List[Any]]]:
        """Returns the step's ``programs`` (None in a steady step)."""
        t0 = time.perf_counter()
        traced = compiled = 0
        programs: Optional[Sequence[List[Any]]] = None
        if self.programs.version != self._programs_version:
            self._programs_version = self.programs.version
            was = self._programs_seen
            seen = self._programs_seen = self.programs.counts()
            traced, compiled = seen[0] - was[0], seen[1] - was[1]
            if seen[2] != was[2]:
                programs = [brief(r) for r in self.programs.since(was[2])]
        row = (
            time.time(), dur_s, mode, slots_used, waiting,
            oldest_wait_s, tokens_real, tokens_padded, tokens_out,
            prompt_tokens, spec_proposed, spec_accepted, kv_blocks,
            kv_reused_total, host_overlap_s, phases_s, admitted,
            first_tokens, traced, compiled, moe_dispatch, attn,
            kv_live, kv_allocated, moe_read, moe_held, programs, ssm,
            attn_rows, cpu_s, diffusion,
        )
        with self._mu:
            if self._unfolded >= self._fold_at:
                self._fold_locked()
            self._ring.append(row)
            self._unfolded += 1
            self._step_s += dur_s
            self._record_s += time.perf_counter() - t0
        return programs

    def _fold_locked(self) -> None:
        """The rows recorded since the last reader, into the histogram
        and the cumulative counters. Called with ``_mu`` held, by
        whoever is about to read one of them."""
        n = self._unfolded
        if not n:
            return
        self._unfolded = 0
        rows = list(itertools.islice(reversed(self._ring), n))
        for row in reversed(rows):
            (_ts, dur_s, mode, slots_used, waiting, oldest_wait_s,
             tokens_real, tokens_padded, tokens_out, prompt_tokens,
             spec_proposed, spec_accepted, kv_blocks, _kv_reused,
             host_overlap_s, _phases, _admitted, _first, _traced,
             _compiled, moe_dispatch, _attn, kv_live, kv_allocated,
             moe_read, moe_held, _programs, ssm, attn_rows, _cpu,
             diffusion) = row
            h = self._hist.get(mode)
            if h is None:
                h = self._hist[mode] = [
                    [0] * (len(STEP_BUCKETS_S) + 1), 0.0, 0,
                ]
            h[0][bisect.bisect_left(STEP_BUCKETS_S, dur_s)] += 1
            h[1] += dur_s
            h[2] += 1
            self._tokens_real_total += tokens_real
            self._tokens_padded_total += tokens_padded
            self._tokens_out_total += tokens_out
            self._prompt_tokens_total += prompt_tokens
            if moe_dispatch:
                totals = self._moe_prompt_tokens_total
                for name, count in moe_dispatch.items():
                    totals[name] = totals.get(name, 0) + count
            self._decode_kv_live_total += kv_live
            self._decode_kv_allocated_total += kv_allocated
            self._moe_decode_read_total += moe_read
            self._moe_decode_held_total += moe_held
            if ssm is not None:
                totals = self._ssm_tokens_total
                if totals is None:
                    totals = self._ssm_tokens_total = {
                        "decode": 0, "prefill": 0,
                    }
                totals["decode"] += ssm[0]
                totals["prefill"] += ssm[1]
                self._state_mixer = ssm[2]
            if attn_rows is not None:
                totals = self._attn_rows_total
                if totals is None:
                    totals = self._attn_rows_total = {
                        "sliding": 0, "full": 0,
                    }
                totals["sliding"] += attn_rows[0]
                totals["full"] += attn_rows[1]
            if diffusion is not None:
                totals = self._diffusion_total
                if totals is None:
                    totals = self._diffusion_total = {
                        "passes_denoise": 0, "passes_commit": 0,
                        "tokens_decided": 0,
                    }
                totals["passes_denoise"] += diffusion[0]
                totals["passes_commit"] += diffusion[1]
                totals["tokens_decided"] += diffusion[2]
            self._spec_proposed_total += spec_proposed
            self._spec_accepted_total += spec_accepted
            self._host_overlap_s_total += host_overlap_s
        self._last_kv_blocks = kv_blocks
        self._last_waiting = waiting
        self._last_oldest_wait_s = oldest_wait_s
        self._last_slots_used = slots_used

    def note_idle_wait(self, seconds: float) -> None:
        """Scheduler parked on its wakeup condition for ``seconds`` —
        spin time the condition-variable loop saved vs. busy-polling."""
        with self._mu:
            self.idle_wait_s_total += seconds

    def note_rollback(self, tokens: int) -> None:
        """``tokens`` speculatively generated tokens discarded because a
        lagged fetch revealed their slot finished or was re-tenanted."""
        with self._mu:
            self.rollback_tokens_total += tokens

    # lowerings and compiles are the process's, not a recorder's: read
    # from the one log (``compile_seconds_total`` is its two unions)

    @property
    def programs_traced_total(self) -> int:
        return self.programs.counts()[0]

    @property
    def programs_compiled_total(self) -> int:
        return self.programs.counts()[1]

    @property
    def compile_seconds_total(self) -> float:
        totals = self.programs.totals()
        return totals["lower_s"] + totals["load_s"]

    @staticmethod
    def _to_entry(row) -> Dict[str, Any]:
        (ts, dur_s, mode, slots_used, waiting, oldest_wait_s,
         tokens_real, tokens_padded, tokens_out, prompt_tokens,
         spec_proposed, spec_accepted, kv_blocks, kv_reused_total,
         host_overlap_s, phases_s, admitted, first_tokens, traced,
         compiled, moe_dispatch, attn, kv_live, kv_allocated,
         moe_read, moe_held, programs, ssm, attn_rows, cpu_s,
         diffusion) = row
        entry = {
            "ts": ts,
            "dur_ms": round(dur_s * 1e3, 4),
            "mode": mode,
            "slots_used": slots_used,
            "waiting": waiting,
            "oldest_wait_ms": round(oldest_wait_s * 1e3, 2),
            "tokens_real": tokens_real,
            "tokens_padded": tokens_padded,
            "tokens_out": tokens_out,
            "prompt_tokens": prompt_tokens,
            "spec_proposed": spec_proposed,
            "spec_accepted": spec_accepted,
            "kv_blocks": kv_blocks,
            "kv_reused_total": kv_reused_total,
            "host_overlap_ms": round(host_overlap_s * 1e3, 4),
            **{
                f"{name}_ms": round(sec * 1e3, 4)
                for name, sec in zip(PHASES, phases_s)
            },
            "admitted": [
                [tid, round(s * 1e3, 3)] for tid, s in admitted
            ],
            "first_tokens": [
                [tid, round(s * 1e3, 3)] for tid, s in first_tokens
            ],
            "traced": traced,
            "compiled": compiled,
        }
        if cpu_s is not None:
            entry["cpu_ms"] = round(cpu_s * 1e3, 4)
        if moe_dispatch:
            entry["moe_dispatch"] = dict(moe_dispatch)
        if attn:
            entry["attn"] = attn
        if kv_allocated:
            entry["kv_live_pct"] = round(100.0 * kv_live / kv_allocated, 2)
        if moe_held:
            entry["moe_read_pct"] = round(100.0 * moe_read / moe_held, 2)
        if programs:
            entry["programs"] = programs
        if ssm is not None:
            (entry["state_slots"], entry["ssm_tokens"],
             entry["state_mixer"]) = ssm
        if attn_rows is not None:
            entry["window_rows"], entry["full_rows"] = attn_rows
        if diffusion is not None:
            (entry["passes_denoise"], entry["passes_commit"],
             entry["tokens_decided"]) = diffusion
            # a commit pass completes its block
            entry["blocks_done"] = diffusion[1]
        return entry

    # ---- read side -----------------------------------------------------

    def overhead_ratio(self) -> float:
        """Seconds spent recording / seconds of recorded step wall time
        (0.0 until the first step)."""
        with self._mu:
            if self._step_s <= 0.0:
                return 0.0
            return self._record_s / self._step_s

    def host_overlap_ratio(self) -> float:
        """Cumulative overlapped host seconds / cumulative step wall
        time (can exceed 1.0 with several overlapping workers)."""
        with self._mu:
            if self._step_s <= 0.0:
                return 0.0
            self._fold_locked()
            return self._host_overlap_s_total / self._step_s

    def diffusion_totals(self) -> Dict[str, int]:
        """The live slots' block passes so far, by kind, the blocks they
        completed (a commit pass each) and the positions they decided;
        zeros until a pass's result has been fetched."""
        with self._mu:
            self._fold_locked()
            totals = dict(self._diffusion_total or {
                "passes_denoise": 0, "passes_commit": 0, "tokens_decided": 0,
            })
        totals["blocks_done"] = totals["passes_commit"]
        return totals

    def snapshot(self, limit: int = 200) -> List[Dict[str, Any]]:
        """Newest-last copy of the most recent ``limit`` records."""
        with self._mu:
            rows = list(itertools.islice(
                reversed(self._ring), max(1, int(limit))
            ))
        return [self._to_entry(r) for r in reversed(rows)]

    def aggregate(
        self, window_s: Optional[float] = None
    ) -> Dict[str, Any]:
        """Windowed utilization aggregates over the ring (the whole
        ring when ``window_s`` is None): per-mode step counts and
        latency percentiles, occupancy, padding waste, queue stats,
        speculation acceptance, KV pressure. This is the shape bench's
        utilization section and /debug/flight both serve."""
        with self._mu:
            rows = list(self._ring)
        entries = [self._to_entry(r) for r in rows]
        if window_s is not None:
            cutoff = time.time() - window_s
            entries = [e for e in entries if e["ts"] >= cutoff]
        return aggregate_records(
            entries, self.slots_total,
            overhead_ratio=self.overhead_ratio(),
        )

    # ---- prometheus ----------------------------------------------------

    def metrics_lines(self) -> List[str]:
        """Exposition lines for the flight-derived families. TYPE text
        derives from the declared vocabulary (METRIC_FAMILIES) so the
        metrics-drift analyzer sees exactly one declaration site."""
        from gpustack_tpu.observability.metrics import METRIC_FAMILIES

        def decl(family: str) -> str:
            return f"# TYPE {family} {METRIC_FAMILIES[family]}"

        with self._mu:
            self._fold_locked()
            slots_used = self._last_slots_used
            waiting = self._last_waiting
            oldest = self._last_oldest_wait_s
            kv_blocks = self._last_kv_blocks
            real = self._tokens_real_total
            padded = self._tokens_padded_total
            prompt = self._prompt_tokens_total
            moe_prompt = dict(self._moe_prompt_tokens_total)
            kv_live = self._decode_kv_live_total
            kv_allocated = self._decode_kv_allocated_total
            moe_read = self._moe_decode_read_total
            moe_held = self._moe_decode_held_total
            ssm_tokens = dict(self._ssm_tokens_total or {})
            state_mixer = self._state_mixer
            attn_rows = dict(self._attn_rows_total or {})
            diffusion = dict(self._diffusion_total or {})
            proposed = self._spec_proposed_total
            accepted = self._spec_accepted_total
            hist = {
                mode: (list(h[0]), h[1], h[2])
                for mode, h in self._hist.items()
            }
            idle_wait_s = self.idle_wait_s_total
            rollback_tokens = self.rollback_tokens_total
        traced = self.programs_traced_total
        compiled = self.programs_compiled_total
        compile_s = self.compile_seconds_total
        lines = [decl("gpustack_engine_step_seconds")]
        for mode in sorted(hist):
            counts, total, count = hist[mode]
            cum = 0
            for ub, c in zip(STEP_BUCKETS_S, counts):
                cum += c
                lines.append(
                    f"gpustack_engine_step_seconds_bucket"
                    f'{{mode="{mode}",le="{repr(ub)}"}} {cum}'
                )
            inf = cum + counts[-1]
            lines.append(
                f"gpustack_engine_step_seconds_bucket"
                f'{{mode="{mode}",le="+Inf"}} {inf}'
            )
            lines.append(
                f'gpustack_engine_step_seconds_sum{{mode="{mode}"}} '
                f"{total:.6f}"
            )
            lines.append(
                f'gpustack_engine_step_seconds_count{{mode="{mode}"}} '
                f"{min(count, inf)}"
            )
        lines += [
            decl("gpustack_engine_dispatched_tokens_total"),
            f'gpustack_engine_dispatched_tokens_total{{kind="real"}} '
            f"{real}",
            f'gpustack_engine_dispatched_tokens_total{{kind="padded"}} '
            f"{padded}",
            decl("gpustack_engine_prompt_tokens_total"),
            f"gpustack_engine_prompt_tokens_total {prompt}",
            decl("gpustack_engine_decode_kv_positions_total"),
            f'gpustack_engine_decode_kv_positions_total{{kind="live"}} '
            f"{kv_live}",
            f"gpustack_engine_decode_kv_positions_total"
            f'{{kind="allocated"}} {kv_allocated}',
            decl("gpustack_engine_occupancy_ratio"),
            f"gpustack_engine_occupancy_ratio "
            f"{slots_used / max(1, self.slots_total):.4f}",
            decl("gpustack_engine_queue_oldest_wait_seconds"),
            f"gpustack_engine_queue_oldest_wait_seconds "
            f"{oldest:.4f}",
            decl("gpustack_engine_queue_depth"),
            f"gpustack_engine_queue_depth {waiting}",
            decl("gpustack_engine_spec_proposed_total"),
            f"gpustack_engine_spec_proposed_total {proposed}",
            decl("gpustack_engine_spec_accepted_total"),
            f"gpustack_engine_spec_accepted_total {accepted}",
            decl("gpustack_engine_kv_blocks_used"),
            f"gpustack_engine_kv_blocks_used {kv_blocks}",
            decl("gpustack_engine_flight_overhead_ratio"),
            f"gpustack_engine_flight_overhead_ratio "
            f"{self.overhead_ratio():.6f}",
            decl("gpustack_engine_host_overlap_ratio"),
            f"gpustack_engine_host_overlap_ratio "
            f"{self.host_overlap_ratio():.6f}",
            decl("gpustack_engine_idle_wait_seconds_total"),
            f"gpustack_engine_idle_wait_seconds_total "
            f"{idle_wait_s:.6f}",
            decl("gpustack_engine_rollback_tokens_total"),
            f"gpustack_engine_rollback_tokens_total "
            f"{rollback_tokens}",
            decl("gpustack_engine_programs_traced_total"),
            f"gpustack_engine_programs_traced_total {traced}",
            decl("gpustack_engine_programs_compiled_total"),
            f"gpustack_engine_programs_compiled_total {compiled}",
            decl("gpustack_engine_compile_seconds_total"),
            f"gpustack_engine_compile_seconds_total {compile_s:.6f}",
        ]
        if moe_held:     # a model with experts, once it has decoded
            lines += [
                decl("gpustack_engine_moe_decode_experts_total"),
                f'gpustack_engine_moe_decode_experts_total{{kind="read"}} '
                f"{moe_read}",
                f'gpustack_engine_moe_decode_experts_total{{kind="held"}} '
                f"{moe_held}",
            ]
        if ssm_tokens:   # a model that keeps a recurrent state a slot
            lines.append(decl("gpustack_engine_ssm_tokens_total"))
            lines += [
                f'gpustack_engine_ssm_tokens_total{{kind="{kind}",'
                f'mixer="{state_mixer}"}} {n}'
                for kind, n in sorted(ssm_tokens.items())
            ]
        if attn_rows:   # a stack with a window store
            lines.append(decl("gpustack_engine_attn_rows_total"))
            lines += [
                f'gpustack_engine_attn_rows_total{{layer="{kind}"}} {n}'
                for kind, n in sorted(attn_rows.items())
            ]
        if diffusion:   # a model generated by diffusion over blocks
            lines += [
                decl("gpustack_engine_diffusion_passes_total"),
                f'gpustack_engine_diffusion_passes_total{{kind="denoise"}} '
                f"{diffusion['passes_denoise']}",
                f'gpustack_engine_diffusion_passes_total{{kind="commit"}} '
                f"{diffusion['passes_commit']}",
                decl("gpustack_engine_diffusion_blocks_total"),
                f"gpustack_engine_diffusion_blocks_total "
                f"{diffusion['passes_commit']}",
                decl("gpustack_engine_diffusion_tokens_decided_total"),
                f"gpustack_engine_diffusion_tokens_decided_total "
                f"{diffusion['tokens_decided']}",
            ]
        if moe_prompt:   # a model with experts, once it has prefilled
            lines.append(decl("gpustack_engine_moe_prompt_tokens_total"))
            lines += [
                f"gpustack_engine_moe_prompt_tokens_total"
                f'{{dispatch="{name}"}} {n}'
                for name, n in sorted(moe_prompt.items())
            ]
        return lines


def _folded(name: str) -> property:
    """A cumulative counter under its public name: brought up to date
    from the ring (``FlightRecorder._fold_locked``) and read, under the lock."""
    def read(self):
        with self._mu:
            self._fold_locked()
            return getattr(self, "_" + name)

    return property(read)


for _name in (
    "tokens_real_total", "tokens_padded_total", "tokens_out_total",
    "prompt_tokens_total", "moe_prompt_tokens_total",
    "decode_kv_live_total", "decode_kv_allocated_total",
    "moe_decode_read_total", "moe_decode_held_total", "ssm_tokens_total",
    "attn_rows_total", "diffusion_total",
    "spec_proposed_total", "spec_accepted_total", "host_overlap_s_total",
):
    setattr(FlightRecorder, _name, _folded(_name))
