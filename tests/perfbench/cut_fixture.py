"""Look at a profiler trace by hand, and cut the tests' fixture from one.

    JAX_PLATFORMS=cpu python tests/perfbench/cut_fixture.py describe <trace>
    JAX_PLATFORMS=cpu python tests/perfbench/cut_fixture.py cut <trace> <out.pb> <first ms>

``fixtures/v5e_chat_open_250ms.xplane.pb`` is the first 250 ms of a
capture of qwen3-8b-int8.chat-open on one v5e chip (my chip run, PR 25),
made with ``cut``. Not part of the yardstick: the reduction the benchmark
runs is ``perfbench/trace_reduce.py``.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, Event, find_xplane, name_gaps, read_xplane, reduce_planes,
)


def to_text_proto(
    planes: Dict[str, Dict[str, List[Event]]],
    keep_ns: Optional[float] = None,
) -> str:
    """An XSpace text proto of the device planes' events, cut to the
    first ``keep_ns`` nanoseconds: how the tests' fixture was cut from a
    chip's trace (``ProfileData.text_proto_to_serialized_xspace``)."""
    out: List[str] = []
    pid = 0
    for pname, lines in planes.items():
        if not DEVICE_PLANE.match(pname):
            continue
        pid += 1
        starts = [s for evs in lines.values() for _, s, _ in evs]
        if not starts:
            continue
        t0 = min(starts)
        meta: Dict[str, int] = {}
        body: List[str] = []
        for lid, (lname, evs) in enumerate(sorted(lines.items()), 1):
            evs = [
                e for e in sorted(evs, key=lambda e: e[1])
                if keep_ns is None or e[1] - t0 <= keep_ns
            ]
            if not evs:
                continue
            rows = []
            for name, s, d in evs:
                mid = meta.setdefault(name, len(meta) + 1)
                rows.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int((s - t0) * 1000)} "
                    f"duration_ps: {int(d * 1000)} }}"
                )
            body.append(
                f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                + " ".join(rows) + " }"
            )
        metas = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'{json.dumps(n)} }} }}'
            for n, i in meta.items()
        )
        out.append(
            f'planes {{ id: {pid} name: "{pname}" '
            + " ".join(body) + " " + metas + " }"
        )
    return "\n".join(out)


def describe(path: str, k: int = 60) -> None:
    """A trace, for the eye: planes, lines, a few events of each with
    their stats, and per device the operations and programs by time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:3]:
                stats = [(str(a), str(b)[:80]) for a, b in list(e.stats)[:12]]
                print(f"    {e.name[:100]!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={stats}")
    reduced = reduce_planes(read_xplane(path), k_ops=k)
    for dev in reduced["devices"]:
        print(f"DEVICE {dev['plane']}: window {dev['window_s']:.4f}s busy "
              f"{dev['busy_s']:.4f}s idle {dev['idle_pct']:.2f}% "
              f"{dev['op_events']} op events")
        for name, s in dev["top_ops"]:
            v = dev["ops"][name]
            print(f"  op {s:9.5f}s n={v['count']:6d} med="
                  f"{v['median_ns'] / 1e3:9.1f}us {name[:110]}")
        for name, v in sorted(
            dev["modules"].items(), key=lambda kv: -kv[1]["total_ns"]
        ):
            print(f"  module {v['total_ns'] / 1e9:9.5f}s n={v['count']:5d} "
                  f"med={v['median_ns'] / 1e6:9.3f}ms {name}")
        for name, s in name_gaps(dev):
            print(f"  gap {s:9.5f}s {name}")


def main(argv: List[str]) -> int:
    if len(argv) == 3 and argv[1] == "describe":
        describe(argv[2])
        return 0
    if len(argv) == 5 and argv[1] == "cut":
        from jax.profiler import ProfileData

        text = to_text_proto(read_xplane(argv[2]), float(argv[4]) * 1e6)
        with open(argv[3], "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))
        return 0
    sys.stderr.write(__doc__)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
