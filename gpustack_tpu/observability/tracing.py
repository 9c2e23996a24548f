"""In-band request tracing: W3C-``traceparent`` ids, per-phase spans.

Dapper-style propagation for the multi-hop serving path (client →
server proxy/failover → worker reverse proxy → engine): the edge mints
a 32-hex trace id (or adopts the caller's ``X-Request-ID``), every
downstream dial carries ``traceparent: 00-<trace>-<span>-01``, and each
hop records its own per-phase spans (auth, schedule, connect,
time-to-first-token, stream, …) into

- a bounded in-memory :class:`TraceStore` ring (served at
  ``GET /v2/debug/traces``),
- the component's request-duration histogram
  (:mod:`gpustack_tpu.observability.metrics`), and
- ONE structured log line per hop (``trace=… phases=[…]``) so a
  chaos-run log greps into a causal timeline.

Everything here is synchronous and allocation-light: tracing rides the
hot proxy path and must never add an await, a lock hold across one, or
an unbounded buffer.
"""

from __future__ import annotations

import hashlib
import logging
import re
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

TRACEPARENT_HEADER = "traceparent"
REQUEST_ID_HEADER = "X-Request-ID"
# how a trace crosses ``exec``: the W3C name for the header's value in a
# child's environment (the worker sets it for the engine it spawns)
TRACEPARENT_ENV = "TRACEPARENT"

# the one log format of every process of the stack (server, worker,
# engine servers): a line without a time cannot be laid beside a span
LOG_FORMAT = "%(asctime)s %(levelname)s %(name)s: %(message)s"

# probe/scrape chatter no hop should trace: a health poll every few
# seconds would flood the hop log and evict real requests from the
# trace ring. Shared by the server's timing middleware, the generic
# hop middleware below, and anything else that adopts tracing.
UNTRACED_PATHS = frozenset(
    {
        "/healthz", "/readyz", "/health", "/metrics", "/metrics/raw",
        "/debug/flight", "/debug/startup",
    }
)

_TRACEPARENT_RE = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)
_HEX32_RE = re.compile(r"^[0-9a-f]{32}$")
# adoptable client request ids: printable token, bounded length
_REQUEST_ID_RE = re.compile(r"^[A-Za-z0-9._\-]{4,128}$")

# component -> (histogram family, registry component); components
# without an entry (engine, stubs) record spans + logs only — the
# engine exports its own native histograms already.
_COMPONENT_HISTOGRAMS = {
    "server": "gpustack_request_duration_seconds",
    "worker": "gpustack_worker_request_duration_seconds",
}


def make_trace_id() -> str:
    return uuid.uuid4().hex


def make_span_id() -> str:
    return uuid.uuid4().hex[:16]


class TraceContext:
    """trace id + this hop's span id (+ the upstream hop's span id)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "request_id")

    def __init__(
        self,
        trace_id: str,
        span_id: str = "",
        parent_id: str = "",
        request_id: str = "",
    ):
        self.trace_id = trace_id
        self.span_id = span_id or make_span_id()
        self.parent_id = parent_id
        self.request_id = request_id or trace_id

    def traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    def child(self) -> "TraceContext":
        """Same trace, fresh span, parented on this hop's span. Note:
        internal hops propagate ``propagation_headers()`` (this hop's
        OWN span id) instead — the receiver mints its span on adoption
        (``from_headers``), so every parent_id in the store points at a
        recorded span."""
        return TraceContext(
            self.trace_id,
            make_span_id(),
            parent_id=self.span_id,
            request_id=self.request_id,
        )

    def propagation_headers(self) -> Dict[str, str]:
        return {
            TRACEPARENT_HEADER: self.traceparent(),
            REQUEST_ID_HEADER: self.request_id,
        }


def parse_traceparent(value: str) -> Optional[TraceContext]:
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    trace_id, parent_span, _flags = m.groups()
    if trace_id == "0" * 32 or parent_span == "0" * 16:
        return None                     # spec: all-zero ids are invalid
    return TraceContext(trace_id, parent_id=parent_span)


def from_headers(headers) -> TraceContext:
    """Adopt the incoming hop's context, else mint a fresh one.

    Order: a valid ``traceparent`` wins (internal hops always send it);
    else a client-supplied ``X-Request-ID`` is adopted — used verbatim
    when it is already a 32-hex trace id, otherwise hashed into one
    (the original survives as ``request_id`` for log correlation)."""
    tp = headers.get(TRACEPARENT_HEADER, "")
    if tp:
        ctx = parse_traceparent(tp)
        if ctx is not None:
            rid = headers.get(REQUEST_ID_HEADER, "")
            if rid and _REQUEST_ID_RE.match(rid):
                ctx.request_id = rid
            return ctx
    rid = headers.get(REQUEST_ID_HEADER, "")
    if rid and _REQUEST_ID_RE.match(rid):
        low = rid.lower()
        if _HEX32_RE.match(low):
            return TraceContext(low, request_id=rid)
        digest = hashlib.sha256(rid.encode()).hexdigest()[:32]
        return TraceContext(digest, request_id=rid)
    return TraceContext(make_trace_id())


# concurrency contract (checked by `python -m gpustack_tpu.analysis`,
# rule guarded-by): the trace ring and the store registry are touched
# from proxy threads, the asyncio loop, and debug handlers — always
# under their lock.
GUARDED_BY = {
    "_ring": "_mu",
    "_STORES": "_STORES_MU",
}


class TraceStore:
    """Bounded ring of finished hop traces, newest last. Reads and
    writes are tiny and lock-guarded (never held across an await —
    nothing here awaits)."""

    def __init__(self, maxlen: int = 512):
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, maxlen))

    def configure(self, maxlen: int) -> None:
        with self._mu:
            self._ring = deque(self._ring, maxlen=max(1, maxlen))

    def add(self, entry: Dict[str, Any]) -> None:
        with self._mu:
            self._ring.append(entry)

    def query(
        self,
        trace_id: str = "",
        model: str = "",
        min_duration_ms: float = 0.0,
        phase: str = "",
        outcome: str = "",
        limit: int = 50,
    ) -> List[Dict[str, Any]]:
        """Filter the ring: ``phase`` keeps entries that recorded a span
        with that name (e.g. ``kv_upload``, ``connect``); ``outcome``
        matches the sealed outcome (``ok``/``error``/``shed``/…)."""
        with self._mu:
            entries = list(self._ring)
        out = []
        for entry in reversed(entries):       # newest first
            if trace_id and entry.get("trace_id") != trace_id:
                continue
            if model and entry.get("model") != model:
                continue
            if entry.get("duration_ms", 0.0) < min_duration_ms:
                continue
            if outcome and entry.get("outcome") != outcome:
                continue
            if phase and not any(
                p.get("phase") == phase
                for p in entry.get("spans", ())
            ):
                continue
            out.append(entry)
            if len(out) >= max(1, limit):
                break
        return out


_STORES: Dict[str, TraceStore] = {}
_STORES_MU = threading.Lock()


def get_store(component: str) -> TraceStore:
    with _STORES_MU:
        store = _STORES.get(component)
        if store is None:
            store = TraceStore()
            _STORES[component] = store
        return store


def store_components() -> List[str]:
    with _STORES_MU:
        return sorted(_STORES)


class RequestTrace:
    """Per-phase span collection for one hop of one request.

    Phases are named wall-clock intervals (``begin``/``end`` or the
    ``phase`` context manager); ``event`` records point-in-time
    annotations (e.g. a failover attempt). ``finish`` seals the trace:
    spans land in the component's :class:`TraceStore`, every phase plus
    the total observes into the component's request-duration histogram,
    and one structured log line is emitted.
    """

    def __init__(
        self,
        ctx: TraceContext,
        component: str,
        name: str,
        model: str = "",
        started_at: float = 0.0,
    ):
        """``started_at`` (``time.time()``) dates the span's zero in the
        past: a process's start is traced from its creation, which is
        before any of its code ran."""
        self.ctx = ctx
        self.component = component
        self.name = name
        self.model = model
        now = time.time()
        self.started_at = started_at or now
        self._t0 = time.monotonic() - (now - self.started_at)
        self._open: Dict[str, float] = {}
        self.phases: List[Dict[str, Any]] = []
        self.events: List[Dict[str, Any]] = []
        self._finished = False

    # ---- span recording -------------------------------------------------

    def begin(self, phase: str) -> None:
        self._open.setdefault(phase, time.monotonic())

    def end(self, phase: str, **attrs: Any) -> None:
        start = self._open.pop(phase, None)
        if start is None:
            return
        now = time.monotonic()
        self.add_phase(
            phase, now - start, _offset=start - self._t0, **attrs
        )

    @contextmanager
    def phase(self, name: str, **attrs: Any):
        self.begin(name)
        try:
            yield self
        finally:
            self.end(name, **attrs)

    def add_phase(
        self, phase: str, seconds: float, _offset: float = -1.0,
        **attrs: Any,
    ) -> None:
        """Record an externally measured phase duration."""
        entry: Dict[str, Any] = {
            "phase": phase,
            "offset_ms": round(
                (_offset if _offset >= 0.0
                 else time.monotonic() - self._t0 - seconds) * 1e3,
                3,
            ),
            "duration_ms": round(seconds * 1e3, 3),
        }
        if attrs:
            entry["attrs"] = attrs
        self.phases.append(entry)

    def event(self, name: str, **attrs: Any) -> None:
        entry: Dict[str, Any] = {
            "event": name,
            "offset_ms": round(
                (time.monotonic() - self._t0) * 1e3, 3
            ),
        }
        if attrs:
            entry["attrs"] = attrs
        self.events.append(entry)

    def phase_names(self) -> List[str]:
        return [p["phase"] for p in self.phases]

    # ---- sealing --------------------------------------------------------

    def finish(
        self,
        status: int = 0,
        outcome: str = "",
        log: bool = True,
        observe: bool = True,
        **attrs: Any,
    ) -> float:
        """Seal the trace; returns total duration in ms. Idempotent —
        the first call wins (middleware and handler may both try).
        ``observe=False`` keeps a span that is no request (a replica's
        start) out of the component's request-duration histogram."""
        if self._finished:
            return 0.0
        self._finished = True
        # close any dangling phase (an exception mid-stream must not
        # lose the span entirely)
        for phase in list(self._open):
            self.end(phase, truncated=True)
        duration_s = time.monotonic() - self._t0
        if not outcome:
            outcome = "ok" if 0 < status < 500 else "error"
        entry: Dict[str, Any] = {
            "trace_id": self.ctx.trace_id,
            "span_id": self.ctx.span_id,
            "parent_id": self.ctx.parent_id,
            "request_id": self.ctx.request_id,
            "component": self.component,
            "name": self.name,
            "model": self.model,
            "status": status,
            "outcome": outcome,
            "started_at": self.started_at,
            "duration_ms": round(duration_s * 1e3, 3),
            "spans": self.phases,
        }
        if self.events:
            entry["events"] = self.events
        if attrs:
            entry["attrs"] = {
                k: v for k, v in attrs.items() if v is not None
            }
        get_store(self.component).add(entry)
        if observe:
            self._observe(duration_s, outcome)
        if log:
            logger.info("%s", self.log_line(entry))
        return entry["duration_ms"]

    def _observe(self, total_s: float, outcome: str) -> None:
        family = _COMPONENT_HISTOGRAMS.get(self.component)
        if family is None:
            return
        from gpustack_tpu.observability.metrics import get_registry

        hist = get_registry(self.component).histogram(
            family, label_names=("phase", "model", "outcome")
        )
        hist.observe(
            total_s, phase="total", model=self.model, outcome=outcome
        )
        for p in self.phases:
            hist.observe(
                p["duration_ms"] / 1e3,
                phase=p["phase"], model=self.model, outcome=outcome,
            )

    @staticmethod
    def log_line(entry: Dict[str, Any]) -> str:
        """One greppable line: ``trace=<id> … phases=[a:1.2 b:3.4]``."""
        phases = " ".join(
            f"{p['phase']}:{p['duration_ms']:.1f}"
            for p in entry.get("spans", [])
        )
        parts = [
            f"trace={entry['trace_id']}",
            f"span={entry['span_id']}",
            f"component={entry['component']}",
            f"name={entry['name']!r}",
            f"status={entry['status']}",
            f"outcome={entry['outcome']}",
            f"ms={entry['duration_ms']:.1f}",
        ]
        if entry.get("model"):
            parts.append(f"model={entry['model']}")
        if entry.get("request_id") != entry["trace_id"]:
            parts.append(f"req={entry['request_id']}")
        parts.append(f"phases=[{phases}]")
        return " ".join(parts)


def trace_middleware(component: str):
    """Generic aiohttp tracing middleware for single-phase hops (the
    engine API server and its test stand-ins): adopts/mints the
    context, stamps ``X-Request-ID``/``traceparent`` on the response,
    and emits the hop's ``trace=…`` log line on completion.

    The server app and the worker reverse proxy do NOT use this — they
    record richer multi-phase traces inline (api/middlewares.py,
    worker/server.py)."""
    from aiohttp import web

    @web.middleware
    async def middleware(request, handler):
        if request.path in UNTRACED_PATHS:
            return await handler(request)
        ctx = from_headers(request.headers)
        trace = RequestTrace(
            ctx, component, f"{request.method} {request.path}"
        )
        request["trace"] = trace
        status = 500
        try:
            resp = await handler(request)
            status = resp.status
            if not resp.prepared:
                resp.headers.setdefault(
                    REQUEST_ID_HEADER, ctx.request_id
                )
                resp.headers.setdefault(
                    TRACEPARENT_HEADER, ctx.traceparent()
                )
            return resp
        finally:
            trace.finish(status=status)

    return middleware
