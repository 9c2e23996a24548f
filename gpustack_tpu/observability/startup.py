"""A replica's start in spans, and every program of the process by name.

Two things the engine process keeps about how it came up, both read at
the engine's ``GET /debug/startup`` and, in short, in the ``startup``
object of its ``/healthz`` (docs/OBSERVABILITY.md, "A replica's start"):

- :class:`EngineStart`, the span ``engine_start``: a
  :class:`~gpustack_tpu.observability.tracing.RequestTrace` whose zero
  is the process's creation as the OS has it, whose phases (``import``,
  ``backend``, ``config``, ``weights``, ``engine``, ``listen``) follow
  one another without a gap, and whose two events are ``ready`` (the
  first ``/healthz`` answered 200) and ``first_token``. It is the child
  of the worker's ``instance_start`` span: the worker hands its
  ``traceparent`` over in the environment (``TRACEPARENT``).
- :class:`ProgramLog`, one record for each program JAX lowered or
  loaded in this process, by name, from ``jax.monitoring``'s time
  spans: Python tracing, lowering to MLIR, and the backend's compile or
  its load from the persistent cache, each ``[start, end]`` on
  ``time.time()``, with whether the cache had the program. The flight
  recorder's compile counters and the step records' ``programs`` are
  read from it.

Import-light (no jax until :meth:`ProgramLog.install`), so the stub
engine and the flight recorder's unit tests share the contract.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Mapping, Optional, Tuple

from gpustack_tpu.observability.tracing import (
    TRACEPARENT_ENV,
    RequestTrace,
    TraceContext,
    make_trace_id,
    parse_traceparent,
)

logger = logging.getLogger(__name__)

# what jax.monitoring calls what a program goes through (observed on jax
# 0.9.0, jax/_src/dispatch.py): the Python of a jitted function traced
# to a jaxpr (also fires for every jit nested inside one: the outermost
# ends last), the jaxpr lowered to an MLIR module (one event a program),
# the backend compile *or* the load from the persistent cache, a cache
# hit announced just before the latter's end, and what the read took
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# the phases of an engine process's start, in order; after ``listen``
# a program falls in a scheduler step
PHASES = ("import", "backend", "config", "weights", "engine", "listen")
AFTER_START = "step"

# concurrency contract (checked by `python -m gpustack_tpu.analysis`,
# rule guarded-by): jax.monitoring calls the listeners on whichever
# thread lowers or compiles; readers are the scheduler (step records)
# and the HTTP handlers.
GUARDED_BY = {
    "_ring": "_mu",
    "_threads": "_mu",
    "_lower": "_mu",
    "_load": "_mu",
    "_finished": "_mu",
    "_lowered": "_mu",
    "_compiled": "_mu",
    "_retrieval_s": "_mu",
    "_place": "_mu",
    "_installed": "_mu",
    "_starts": "_mu",
    "_ready_s": "_mu",
    "_first_token_s": "_mu",
    "_PROCESS_LOG": "_PROCESS_LOG_MU",
}

_IMPORTED_AT = time.time()


def process_created_at() -> float:
    """When the OS created this process, on ``time.time()``'s clock:
    the kernel's start time of the process (``/proc/self/stat``, in
    ticks of the boot clock) against that clock now. The interpreter's
    start and the imports are part of a start, and no line of the
    program runs before them. Where the kernel does not say, the time
    this module was imported."""
    now = time.time()
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read()
        # after "(comm)": state is field 3, starttime field 22
        ticks = int(stat[stat.rindex(b")") + 2:].split()[19])
        age = (
            time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK")
        )
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED_AT
    if not -1.0 < age < now - _IMPORTED_AT + 3600.0:
        return _IMPORTED_AT         # a /proc that counts from elsewhere
    return now - max(0.0, age)


class _Union:
    """Seconds covered by the intervals added so far: two threads may
    lower or load at once, and a nested span lies inside its outer one."""

    __slots__ = ("_spans", "_folded")
    _KEEP = 64      # an interval this far back meets no new one

    def __init__(self) -> None:
        self._spans: List[Tuple[float, float]] = []
        self._folded = 0.0

    def add(self, start: float, end: float) -> None:
        if end <= start:
            return
        merged: List[Tuple[float, float]] = []
        for s, e in sorted(self._spans + [(start, end)]):
            if merged and s <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], e))
            else:
                merged.append((s, e))
        while len(merged) > self._KEEP:
            s, e = merged.pop(0)
            self._folded += e - s
        self._spans = merged

    @property
    def seconds(self) -> float:
        return self._folded + sum(e - s for s, e in self._spans)


def _span(start: float, end: float) -> List[float]:
    return [round(start, 6), round(end, 6)]


class ProgramLog:
    """One record a program this process lowered or loaded::

        {"name": "jit_prefill_1024", "phase": "step",
         "trace": [start, end], "lower": [start, end],
         "load": [start, end], "cached": true, "retrieval_s": 0.41}

    ``trace`` is the Python of the outermost jitted function (absent
    where JAX had the jaxpr), ``lower`` the jaxpr to MLIR, ``load`` the
    backend's compile or, where ``cached``, its load from the
    persistent cache (``retrieval_s`` of it reading the entry). All on
    ``time.time()``. ``phase`` is what the process was in when the
    record closed: a phase of its start, or ``step``. A record closes
    with its ``load``; a program lowered and never compiled closes,
    without one, when its thread lowers the next.

    The totals are for the process's whole life (the ring forgets, they
    do not): programs lowered, programs the backend compiled because the
    cache had none, and the seconds the ``trace`` and ``lower`` spans
    cover, and the ``load`` spans (each a union: concurrent or nested
    spans count once)."""

    def __init__(self, capacity: int = 256):
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=max(16, int(capacity)))
        # per thread: the record its lowering opened, the trace spans
        # since (by function), and a cache hit announced for the load
        # in progress
        self._threads: Dict[int, Dict[str, Any]] = {}
        self._lower = _Union()
        self._load = _Union()
        self._finished = 0
        self._lowered = 0
        self._compiled = 0
        self._retrieval_s = 0.0
        self._place = ""
        self._installed = False
        # grows with every lowering and every record closed; written
        # under the lock and read without it (one int): a scheduler
        # step asks only whether it moved
        self.version = 0

    # ---- write side (jax.monitoring's listeners, any thread) ----------

    def install(self) -> None:
        """Register this log with ``jax.monitoring``, once. The
        listeners are process-wide and are never taken off again."""
        import jax.monitoring

        with self._mu:
            if self._installed:
                return
            self._installed = True
        jax.monitoring.register_event_time_span_listener(self.on_time_span)
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration
        )
        jax.monitoring.register_event_listener(self.on_event)

    def set_place(self, place: str) -> None:
        with self._mu:
            self._place = place

    def _thread_locked(self) -> Dict[str, Any]:
        return self._threads.setdefault(
            threading.get_ident(),
            {"record": None, "traces": {}, "hit": False, "retrieval": 0.0},
        )

    def _close_locked(self, record: Dict[str, Any]) -> None:
        record["phase"] = self._place
        self._finished += 1
        self.version += 1
        self._ring.append(record)

    def on_time_span(
        self, event: str, start: float, end: float,
        fun_name: str = "", **_kw: Any,
    ) -> None:
        if event == TRACE_EVENT:
            with self._mu:
                self._lower.add(start, end)
                self._thread_locked()["traces"][str(fun_name)] = (start, end)
        elif event == LOWERING_EVENT:
            with self._mu:
                mine = self._thread_locked()
                if mine["record"] is not None:
                    self._close_locked(mine["record"])
                record: Dict[str, Any] = {"name": str(fun_name)}
                # the program's own Python: the trace of the function
                # the module is named for, "jit(<function>)", which
                # ended before the lowering began (its nested jits'
                # traces ended inside it, its lowering rules' come after)
                traced = mine["traces"].get(
                    record["name"].partition("(")[2][:-1]
                )
                if traced is not None and traced[1] <= start:
                    record["trace"] = _span(*traced)
                record["lower"] = _span(start, end)
                mine.update(record=record, traces={})
                self._lowered += 1
                self.version += 1
                self._lower.add(start, end)
        elif event == BACKEND_COMPILE_EVENT:
            with self._mu:
                mine = self._thread_locked()
                record = mine["record"]
                if record is None or record["name"] != str(fun_name):
                    # compiled on another thread than it was lowered on
                    record = {"name": str(fun_name)}
                else:
                    mine["record"] = None
                record["load"] = _span(start, end)
                record["cached"] = bool(mine["hit"])
                record["retrieval_s"] = round(mine["retrieval"], 6)
                if not mine["hit"]:
                    self._compiled += 1
                mine.update(hit=False, retrieval=0.0)
                self._load.add(start, end)
                self._close_locked(record)

    def on_event(self, event: str, **_kw: Any) -> None:
        if event == CACHE_HIT_EVENT:
            with self._mu:
                self._thread_locked()["hit"] = True

    def on_duration(self, event: str, seconds: float, **_kw: Any) -> None:
        if event == CACHE_RETRIEVAL_EVENT:
            with self._mu:
                self._thread_locked()["retrieval"] = seconds
                self._retrieval_s += seconds

    # ---- read side -----------------------------------------------------

    def counts(self) -> Tuple[int, int, int]:
        """``(lowered, compiled, finished)``: programs lowered, programs
        the backend compiled (a cache miss), records closed."""
        with self._mu:
            return self._lowered, self._compiled, self._finished

    def since(self, finished: int) -> List[Dict[str, Any]]:
        """The records closed after the first ``finished`` ones, oldest
        first (as far back as the ring remembers)."""
        with self._mu:
            n = min(self._finished - finished, len(self._ring))
            return list(self._ring)[len(self._ring) - n:] if n > 0 else []

    def records(self) -> List[Dict[str, Any]]:
        """Every record the ring remembers, oldest first."""
        return self.since(0)

    def totals(self) -> Dict[str, Any]:
        with self._mu:
            return {
                "lowered": self._lowered,
                "lower_s": round(self._lower.seconds, 3),
                "load_s": round(self._load.seconds, 3),
                "cache_misses": self._compiled,
                "retrieval_s": round(self._retrieval_s, 3),
            }


def brief(record: Mapping[str, Any]) -> List[Any]:
    """``[name, lower_ms, load_ms, cached]``: a program in a step record
    (``lower_ms`` with the Python tracing before it)."""
    def ms(*keys: str) -> float:
        return round(sum(
            (record[k][1] - record[k][0]) * 1e3 for k in keys if k in record
        ), 3)

    return [
        record["name"], ms("trace", "lower"), ms("load"),
        bool(record.get("cached", False)),
    ]


_PROCESS_LOG: Optional[ProgramLog] = None
_PROCESS_LOG_MU = threading.Lock()


def process_programs() -> ProgramLog:
    """The log of this process's programs, listening from the first
    call on: an engine server asks at the top of its ``main``, before
    the weights; a process that builds an engine without one, with its
    first engine. ``jax.monitoring``'s listeners are the process's, so
    the log is too, and every engine of a process reads the same."""
    global _PROCESS_LOG
    with _PROCESS_LOG_MU:
        if _PROCESS_LOG is None:
            _PROCESS_LOG = ProgramLog()
        log = _PROCESS_LOG
    log.install()
    return log


class EngineStart:
    """The span ``engine_start`` of an engine process: see the module's
    docstring. ``enter`` closes the open phase and opens the next at the
    same instant, so the phases leave no gap; the main thread enters
    them, the HTTP loop marks ``ready``, the scheduler ``first_token``,
    which seals the span into the engine's ``TraceStore``."""

    def __init__(
        self,
        programs: ProgramLog,
        model: str = "",
        environ: Mapping[str, str] = os.environ,
    ):
        self.programs = programs
        self.t0 = process_created_at()
        parent = parse_traceparent(environ.get(TRACEPARENT_ENV, ""))
        self.trace = RequestTrace(
            parent or TraceContext(make_trace_id()),
            "engine", "engine_start", model=model, started_at=self.t0,
        )
        self._mu = threading.Lock()
        # phase -> seconds since t0 at which it began; the open one last
        self._starts: List[Tuple[str, float]] = []
        self._ready_s: Optional[float] = None
        self._first_token_s: Optional[float] = None
        self.enter("import", at=self.t0)

    def _since_t0(self, at: float = 0.0) -> float:
        return max(0.0, (at or time.time()) - self.t0)

    def enter(self, phase: str, at: float = 0.0) -> None:
        """The process is in ``phase`` from now (or ``at``) on; an empty
        ``phase`` only closes the open one (the start is over)."""
        now = self._since_t0(at)
        with self._mu:
            if self._starts:
                name, began = self._starts[-1]
                self.trace.add_phase(name, now - began, _offset=began)
            if phase:
                self._starts.append((phase, now))
        self.programs.set_place(phase or AFTER_START)

    def listening(self) -> None:
        """The HTTP server accepts: ``listen``, and the start, end."""
        self.enter("")
        logger.info(
            "engine_start trace=%s listening after %.3f s: %s",
            self.trace.ctx.trace_id, self._since_t0(), self._phases_text(),
        )

    def mark_ready(self) -> None:
        """A ``/healthz`` is about to answer 200; the first one counts."""
        with self._mu:
            if self._ready_s is not None:
                return
            self._ready_s = self._since_t0()
            self.trace.event("ready")

    def mark_first_token(self, at: float = 0.0) -> None:
        """The engine handed on the first token of its life."""
        with self._mu:
            if self._first_token_s is not None:
                return
            first_token_s = self._first_token_s = self._since_t0(at)
            self.trace.event("first_token")
            ready_s = self._ready_s
        totals = self.programs.totals()
        self.trace.finish(
            status=200, log=False, observe=False, programs=totals,
        )
        logger.info(
            "engine_start trace=%s first token after %.3f s (ready after "
            "%s s): %s; programs %s",
            self.trace.ctx.trace_id, first_token_s,
            "%.3f" % ready_s if ready_s is not None else "no",
            self._phases_text(), totals,
        )

    def _phases_text(self) -> str:
        return ", ".join(
            f"{p['phase']} {p['duration_ms'] / 1e3:.3f}"
            for p in self.trace.phases
        )

    # ---- read side -----------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        """The ``startup`` object of ``/healthz``: under 400 bytes."""
        with self._mu:
            ready_s, first_token_s = self._ready_s, self._first_token_s
            phases = {
                p["phase"]: round(p["duration_ms"] / 1e3, 3)
                for p in self.trace.phases
            }
        return {
            "t0": round(self.t0, 3),
            "ready_s": None if ready_s is None else round(ready_s, 3),
            "first_token_s": (
                None if first_token_s is None else round(first_token_s, 3)
            ),
            "phases": phases,
            "programs": self.programs.totals(),
        }

    def describe(self) -> Dict[str, Any]:
        """The whole span, for ``GET /debug/startup``."""
        ctx = self.trace.ctx
        with self._mu:
            phases = list(self.trace.phases)
            events = list(self.trace.events)
            sealed = self._first_token_s is not None
        return {
            "trace_id": ctx.trace_id,
            "span_id": ctx.span_id,
            "parent_id": ctx.parent_id,
            "component": "engine",
            "name": "engine_start",
            "model": self.trace.model,
            "sealed": sealed,
            "phases": phases,
            "events": events,
            "programs": self.programs.records(),
            "summary": self.summary(),
        }

    def metrics_lines(self) -> List[str]:
        """``gpustack_engine_start_seconds{phase=}``: each phase's
        seconds, and ``ready`` / ``first_token`` as seconds since the
        process's creation, once they have come."""
        from gpustack_tpu.observability.metrics import METRIC_FAMILIES

        family = "gpustack_engine_start_seconds"
        s = self.summary()
        values = dict(s["phases"])
        for name in ("ready", "first_token"):
            if s[f"{name}_s"] is not None:
                values[name] = s[f"{name}_s"]
        return [f"# TYPE {family} {METRIC_FAMILIES[family]}"] + [
            f'{family}{{phase="{name}"}} {value:.3f}'
            for name, value in values.items()
        ]
