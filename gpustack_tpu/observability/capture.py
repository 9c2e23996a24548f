"""What the host was doing while the chip stood idle, read from a
profiler capture by the program that made it.

A capture (``LLMEngine.capture_profile``, ``POST
/v2/model-instances/{id}/profile``) writes one ``*.xplane.pb``. On its
``/device:TPU:<n>`` planes the line ``XLA Ops`` holds one event for each
executed operation and ``XLA Modules`` one for each executed program; on
``/host:CPU`` the scheduler's thread holds the spans the engine entered
while the capture was open: ``sched.step`` and, inside it,
``sched.<phase>`` for the phases of ``flight.PHASES``. Both lie on one
clock, that of the profiler's session, and this file is the only place
where they do: so the join is made here.

    python -m gpustack_tpu.observability.capture <trace dir or .xplane.pb>

prints ``summarize``'s answer as one JSON line. The engine runs that in a
child process (``JAX_PLATFORMS=cpu``) after each traced capture: the parse
then takes neither the chip nor the engine's interpreter.

**Window and idle**, a chip: the window runs from the first ``XLA Ops``
event's start to the last one's end, and the chip is idle in the window
less the union of those events (a program's own event also covers the
gaps inside it).

**A piece of idle time gets its name** from the scheduler's spans. Every
idle interval is cut at every span boundary; a piece belongs to the
innermost phase that covers it (``wait`` lies inside ``drain``, ``admit``
or ``chunk``); else to ``step_other`` if a ``sched.step`` covers it (the
record's sealing and the bookkeeping between phases); else to
``between_steps`` if it lies between two ``sched.step`` spans of the trace
(the loop round ``step()``); else to ``unannotated`` (before the first
step's span or after the last one's). The parts add up to the idle time.

Everything but ``read_xplane`` is plain Python over ``(name, start_ns,
duration_ns)`` tuples.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import json
import os
import re
import subprocess
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from gpustack_tpu.observability.flight import PHASES

Event = Tuple[str, float, float]        # name, start_ns, duration_ns
Interval = Tuple[float, float]          # [start_ns, end_ns)
# what a piece of the timeline is put down to, and the step's number
Label = Tuple[str, Optional[int]]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_SPAN = "sched.step"
PHASE_SPANS = {"sched." + name: name for name in PHASES}
PARTS = PHASES + ("step_other", "between_steps", "unannotated")
GAPS_KEPT = 10
CHILD_TIMEOUT_S = 60.0


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def read_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: {"ops": [Event], "modules": [Event]}},
    "spans": [Event], "step_nums": {start_ns: int}}``: the chips' two
    lines, and the scheduler's thread (the host line that holds
    ``sched.step`` events) with the ``step_num`` of each ``sched.step``
    by the span's start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    step_nums: Dict[float, int] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(
                plane.name, {"ops": [], "modules": []}
            )
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    lines[key].extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                found = [
                    e for e in line.events
                    if e.name == STEP_SPAN or e.name in PHASE_SPANS
                ]
                if not any(e.name == STEP_SPAN for e in found):
                    continue
                for e in found:
                    spans.append(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                    )
                    if e.name == STEP_SPAN:
                        num = dict(e.stats).get("step_num")
                        if num is not None:
                            step_nums[float(e.start_ns)] = int(num)
    return {"devices": devices, "spans": spans, "step_nums": step_nums}


def merge_intervals(events: Sequence[Event]) -> List[Interval]:
    """Sorted, disjoint intervals covering the events."""
    merged: List[Interval] = []
    for s, e in sorted((s, s + d) for _, s, d in events if d > 0):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def idle_intervals(ops: Sequence[Event]) -> Tuple[Interval, List[Interval]]:
    """The window of one chip's ``XLA Ops`` events and the intervals of
    it in which none of them ran."""
    busy = merge_intervals(ops)
    if not busy:
        return (0.0, 0.0), []
    return (busy[0][0], busy[-1][1]), [
        (a_end, b_start)
        for (_, a_end), (b_start, _) in zip(busy, busy[1:])
    ]


class Timeline:
    """The scheduler's spans as a step function of time: what an
    instant is put down to is constant between two neighbouring
    ``bounds``, and ``pieces`` cuts an interval there."""

    def __init__(
        self,
        spans: Sequence[Event],
        step_nums: Optional[Dict[float, int]] = None,
    ) -> None:
        # (time, 1 opens / 0 closes, order, name, step number): of the
        # spans that open at one instant the longest is the outermost
        marks = []
        steps: List[Interval] = []
        for name, s, d in spans:
            if d <= 0 or (name != STEP_SPAN and name not in PHASE_SPANS):
                continue
            num = None
            if name == STEP_SPAN:
                steps.append((s, s + d))
                num = (step_nums or {}).get(s)
            marks.append((s, 1, -d, name, num))
            marks.append((s + d, 0, d, name, num))
        self.steps = len(steps)
        self._first = min((s for s, _ in steps), default=0.0)
        self._last = max((e for _, e in steps), default=0.0)
        self.bounds: List[float] = []
        self._labels: List[Optional[Label]] = []
        open_: List[Tuple[str, Optional[int]]] = []     # outermost first
        for t, opens, _, name, num in sorted(marks, key=lambda m: m[:3]):
            if opens:
                open_.append((name, num))
            else:
                open_.remove((name, num))
            label = self._innermost(open_)
            if self.bounds and self.bounds[-1] == t:
                self._labels[-1] = label
            else:
                self.bounds.append(t)
                self._labels.append(label)

    @staticmethod
    def _innermost(open_: Sequence[Tuple[str, Optional[int]]]) -> Optional[Label]:
        step = next((num for name, num in open_ if name == STEP_SPAN), None)
        for name, _ in reversed(open_):
            if name in PHASE_SPANS:
                return PHASE_SPANS[name], step
        if any(name == STEP_SPAN for name, _ in open_):
            return "step_other", step
        return None

    def pieces(self, start: float, end: float) -> List[Tuple[float, Label]]:
        """``[start, end)`` cut at every span boundary: ``(nanoseconds,
        label)`` a piece, in order."""
        out: List[Tuple[float, Label]] = []
        i = bisect.bisect_right(self.bounds, start) - 1
        a = start
        while a < end:
            b = min(end, self.bounds[i + 1]) if i + 1 < len(self.bounds) else end
            label = self._labels[i] if i >= 0 else None
            if label is None:
                # outside every span: the loop round step(), or the
                # trace's ends
                label = (
                    "between_steps"
                    if self.steps and self._first <= a and b <= self._last
                    else "unannotated", None,
                )
            out.append((b - a, label))
            a, i = b, i + 1
        return out


def strip_hash(name: str) -> str:
    """``jit__decode_impl(1234567)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def neighbours(
    modules: Sequence[Event], start: float, end: float
) -> Tuple[str, str]:
    """The programs on either side of the idle interval ``[start, end)``
    (``modules`` sorted by start): the last one that ended by its start
    and the first one that began at its end; a program whose own event
    covers the interval is both."""
    after, before = "start", "end"
    for name, s, d in modules:
        if s <= start and s + d >= end:
            return strip_hash(name), strip_hash(name)
        if s + d <= start + 1:
            after = strip_hash(name)
        elif s >= end - 1:
            before = strip_hash(name)
            break
    return after, before


def summarize_device(
    ops: Sequence[Event], modules: Sequence[Event], timeline: Timeline
) -> Optional[Dict[str, Any]]:
    """One chip: window, idle time, the idle time by part, and the
    longest idle intervals, all in nanoseconds. None for a plane without
    operations."""
    (w0, w1), idle = idle_intervals(ops)
    if w1 <= w0:
        return None
    by_part = dict.fromkeys(PARTS, 0.0)
    for s, e in idle:
        for ns, (part, _) in timeline.pieces(s, e):
            by_part[part] += ns
    modules = sorted(modules, key=lambda m: m[1])
    gaps = []
    for s, e in heapq.nlargest(GAPS_KEPT, idle, key=lambda g: g[1] - g[0]):
        # a gap that a boundary cuts is named by its longest piece
        _, (part, step_num) = max(timeline.pieces(s, e), key=lambda p: p[0])
        after, before = neighbours(modules, s, e)
        gaps.append({
            "at_ms": round((s - w0) / 1e6, 6), "ms": round((e - s) / 1e6, 6),
            "span": part, "step_num": step_num,
            "after": after, "before": before,
        })
    return {
        "window_ns": w1 - w0, "idle_ns": sum(e - s for s, e in idle),
        "by_part_ns": by_part, "gaps": gaps,
    }


def summarize(
    devices: Dict[str, Dict[str, Sequence[Event]]],
    spans: Sequence[Event],
    step_nums: Optional[Dict[float, int]] = None,
) -> Dict[str, Any]:
    """A capture's idle time by host span. Several chips: the numbers
    are means over the chips (as the benchmark's ``device.idle_pct``
    is), and ``gaps`` are the lowest-numbered chip's. A trace without a
    chip's plane gives ``{"devices": 0, "steps": n}`` and no idle
    number."""
    timeline = Timeline(spans, step_nums)
    chips = [
        got for got in (
            summarize_device(
                devices[plane].get("ops") or (),
                devices[plane].get("modules") or (), timeline,
            )
            for plane in sorted(
                (p for p in devices if DEVICE_PLANE.match(p)),
                key=lambda p: int(DEVICE_PLANE.match(p).group(1)),
            )
        ) if got is not None
    ]
    out: Dict[str, Any] = {"devices": len(chips), "steps": timeline.steps}
    if not chips:
        return out

    def mean_ms(values: Sequence[float]) -> float:
        return round(sum(values) / len(values) / 1e6, 6)

    out.update(
        window_ms=mean_ms([c["window_ns"] for c in chips]),
        idle_ms=mean_ms([c["idle_ns"] for c in chips]),
        idle_pct=round(
            sum(100.0 * c["idle_ns"] / c["window_ns"] for c in chips)
            / len(chips), 4,
        ),
        idle_ms_by_span={
            part: mean_ms([c["by_part_ns"][part] for c in chips])
            for part in PARTS
        },
        gaps=chips[0]["gaps"],
    )
    return out


def digest(summary: Dict[str, Any]) -> Dict[str, Any]:
    """What ``/healthz`` keeps of a summary (``last_capture``): the
    totals and the eight parts, no gap."""
    if "error" in summary:
        return {"error": str(summary["error"])[:200]}
    out = {k: summary[k] for k in ("steps", "devices") if k in summary}
    if "idle_ms_by_span" in summary:
        out.update(
            window_ms=round(summary["window_ms"], 3),
            idle_pct=summary["idle_pct"],
            idle_ms={
                part: round(ms, 3)
                for part, ms in summary["idle_ms_by_span"].items()
            },
        )
    return out


def summarize_in_child(
    path: str, timeout_s: float = CHILD_TIMEOUT_S
) -> Dict[str, Any]:
    """``summarize`` of the trace under ``path``, made by a child
    process on the CPU with a time limit of its own, so that reading the
    file takes neither the chip nor the caller's interpreter. A child
    that fails or is cut gives ``{"error": ...}``; nothing is raised."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_SKIP_MDS_QUERY="1")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (root, env.get("PYTHONPATH")) if p
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "gpustack_tpu.observability.capture",
             path],
            env=env, capture_output=True, text=True, timeout=timeout_s,
        )
        if proc.returncode != 0:
            said = proc.stderr.strip().splitlines()[-1:] or [""]
            return {"error": f"exit {proc.returncode}: {said[0]}"[:400]}
        return json.loads(proc.stdout.splitlines()[-1])
    except (subprocess.TimeoutExpired, OSError, ValueError, IndexError) as e:
        return {"error": f"{type(e).__name__}: {e}"[:400]}


def main(argv: List[str]) -> int:
    if len(argv) != 2:
        sys.stderr.write(__doc__)
        return 2
    print(json.dumps(summarize(**read_xplane(argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
