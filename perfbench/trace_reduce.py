"""From a profiler trace (``*.xplane.pb``) to numbers: per device the
busy union, the idle share, the operations that took most time, the
programs (XLA modules) by name, and the longest idle gaps.

    python perfbench/trace_reduce.py <trace dir or .xplane.pb> <out.json>

(``tests/perfbench/cut_fixture.py`` prints a trace for the eye and cuts
the tests' fixture from one.)

Run as a child of the benchmark with ``JAX_PLATFORMS=cpu`` (``--trace 2``
reduces a capture while the engine still holds the chip): reading a trace
needs ``jax.profiler.ProfileData`` and the benchmark process itself never
imports JAX. Everything but ``read_xplane`` is plain Python over ``(name,
start_ns, duration_ns)`` tuples, and is what the tests check by hand-worked
intervals.

What a v5e trace looks like (looked at by hand, PR 25; PERF.md section
3): one plane per chip named ``/device:TPU:<n>``; on it the line ``XLA
Ops`` holds one event per executed HLO operation or fusion, ``XLA
Modules`` one event per executed program (``jit_<function>(<hash>)``),
``Steps`` the profiler's own step markers. Busy time is the union of the
``XLA Ops`` intervals: a program's own event also covers the gaps inside
it, in which the chip waits.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The ``XLA Ops`` line nests: a ``while`` (the scan over the layers), a
# ``conditional`` or a ``call`` is an event of its own that covers the
# operations inside it. They count for the busy union like any other,
# and are left out of the lists by name, which would count their time
# twice.
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")
KERNEL = "custom-call("


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


def read_xplane(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events
            )
    return out


def merge_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Sorted, disjoint [start, end) intervals covering the events."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[Tuple[float, float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_and_gaps(
    events: Sequence[Event], k: int = 10
) -> Tuple[float, float, List[Tuple[float, float]]]:
    """(window_ns, busy_ns, the k longest idle gaps as (start_ns,
    duration_ns)). The window runs from the first event's start to the
    last event's end."""
    merged = merge_intervals(events)
    if not merged:
        return 0.0, 0.0, []
    window = merged[-1][1] - merged[0][0]
    busy = sum(e - s for s, e in merged)
    gaps = [
        (a_end, b_start - a_end)
        for (_, a_end), (b_start, _) in zip(merged, merged[1:])
    ]
    gaps.sort(key=lambda g: g[1], reverse=True)
    return window, busy, gaps[:k]


def by_name(events: Iterable[Event]) -> Dict[str, Dict[str, float]]:
    """{name: {count, total_ns, median_ns}}."""
    durs: Dict[str, List[float]] = {}
    for name, _, d in events:
        durs.setdefault(name, []).append(d)
    return {
        name: {
            "count": len(ds), "total_ns": sum(ds),
            "median_ns": statistics.median(ds),
        }
        for name, ds in durs.items()
    }


def top(named: Dict[str, Dict[str, float]], k: int) -> List[List[Any]]:
    """[[name, seconds], ...], most time first."""
    rows = sorted(named.items(), key=lambda kv: kv[1]["total_ns"], reverse=True)
    return [[name, v["total_ns"] / 1e9] for name, v in rows[:k]]


def short_name(name: str, width: int = 96) -> str:
    """An operation's HLO text without layouts and operands:
    ``%copy.107 copy bf16[36,12,2048,8,128]``."""
    flat = re.sub(r"\{[^}]*\}", "", name)
    m = re.match(r"^(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", flat)
    if not m:
        return flat[:width]
    return f"{m.group(1)} {m.group(3)} {m.group(2)}"[:width]


def strip_hash(name: str) -> str:
    """``jit__decode_impl(1234567)`` -> ``jit__decode_impl``: a program's
    name without the fingerprint that changes from build to build."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(
    planes: Dict[str, Dict[str, List[Event]]], k_ops: int = 40
) -> Dict[str, Any]:
    """The reduction the per-layer readers work from."""
    devices = []
    for pname in sorted(planes):
        if not DEVICE_PLANE.match(pname):
            continue
        lines = planes[pname]
        ops = lines.get(OPS_LINE) or []
        if not ops:
            continue
        window, busy, gaps = busy_and_gaps(ops, k=20)
        ops_named = by_name(e for e in ops if not CONTAINER.match(e[0]))
        modules = by_name(
            (strip_hash(n), s, d) for n, s, d in lines.get(MODULES_LINE, [])
        )
        first = min(s for _, s, _ in ops)
        devices.append({
            "plane": pname,
            "first_ns": first,
            "window_s": window / 1e9,
            "busy_s": busy / 1e9,
            "idle_pct": 100.0 * (1.0 - busy / window) if window else None,
            "op_events": len(ops),
            "top_ops": top(ops_named, k_ops),
            "ops": {
                n: v for i, (n, v) in enumerate(sorted(
                    ops_named.items(), key=lambda kv: kv[1]["total_ns"],
                    reverse=True,
                )) if i < k_ops or KERNEL in n
            },
            "modules": modules,
            "module_events": [
                [strip_hash(n), s - first, d]
                for n, s, d in sorted(
                    lines.get(MODULES_LINE, []), key=lambda e: e[1]
                )
            ],
            "gaps": [[s - first, d] for s, d in gaps],
        })
    return {
        "structure": {
            p: {ln: len(evs) for ln, evs in lines.items()}
            for p, lines in planes.items()
        },
        "devices": devices,
    }


def name_gaps(
    device: Dict[str, Any], k: int = 10
) -> List[List[Any]]:
    """The longest idle gaps of one device, each named by the programs on
    either side of it: ``after <program> before <program>``. The host's
    own spans are not on the device's clock yet (the next tracing issue),
    so this is as far as a name can go: the program the chip had just
    finished and the one it was made to wait for. Gaps of one name are
    added up."""
    mods = device.get("module_events") or []
    totals: Dict[str, float] = {}
    for start, dur in device.get("gaps") or []:
        before = [m for m in mods if m[1] + m[2] <= start + 1]
        after = [m for m in mods if m[1] >= start + dur - 1]
        inside = [
            m for m in mods if m[1] <= start and m[1] + m[2] >= start + dur
        ]
        if inside:
            label = f"inside {inside[-1][0]}"
        else:
            a = before[-1][0] if before else "start"
            b = after[0][0] if after else "end"
            label = f"after {a} before {b}"
        totals[label] = totals.get(label, 0.0) + dur / 1e9
    rows = sorted(totals.items(), key=lambda kv: kv[1], reverse=True)
    return [[n, s] for n, s in rows[:k]]


def breakdown(reduced: Dict[str, Any], k: int = 10) -> Dict[str, Any]:
    """The contract's ``breakdown``: device operations by time and idle
    gaps by name, summed over the devices of the trace."""
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for dev in reduced["devices"]:
        for name, s in dev["top_ops"]:
            ops[short_name(name)] = ops.get(short_name(name), 0.0) + s
        for name, s in name_gaps(dev, k=50):
            gaps[name] = gaps.get(name, 0.0) + s
    def rows(d: Dict[str, float]) -> List[List[Any]]:
        return [
            [n, s] for n, s in
            sorted(d.items(), key=lambda kv: kv[1], reverse=True)[:k]
        ]
    return {"device_ops": rows(ops), "idle_gaps": rows(gaps)}


def main(argv: List[str]) -> int:
    if len(argv) != 3:
        sys.stderr.write(__doc__)
        return 2
    reduced = reduce_planes(read_xplane(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
