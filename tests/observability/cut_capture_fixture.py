"""Lay a capture's scheduler spans beside its programs, for the eye, and
cut the tests' fixture from one.

    JAX_PLATFORMS=cpu python tests/observability/cut_capture_fixture.py layout <trace> [steps]
    JAX_PLATFORMS=cpu python tests/observability/cut_capture_fixture.py cut <trace> <out.pb> <first step> <steps>

``fixtures/v5e_moe_rag_3steps.xplane.pb`` is three scheduler steps of a
capture of qwen3-30b-a3b-int8-l12.rag-closed on one v5e chip (my chip
run, PR 58), made with ``cut``: the chip's ``XLA Ops`` and ``XLA
Modules`` lines and the scheduler's thread of ``/host:CPU``, all on the
one clock the capture had.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from gpustack_tpu.observability import capture  # noqa: E402


def steps_of(got) -> List[capture.Event]:
    return sorted(
        (e for e in got["spans"] if e[0] == capture.STEP_SPAN),
        key=lambda e: e[1],
    )


def layout(path: str, steps: int = 3) -> None:
    """Each step's ``sched.dispatch`` span beside the programs that began
    at or after it, and the longest idle gaps with the span that holds
    their end (a gap ends because the host dispatched: if the two clocks
    agree, it ends inside ``sched.dispatch``, a launch's latency after
    the span's start)."""
    got = capture.read_xplane(path)
    plane = sorted(got["devices"])[0]
    modules = sorted(got["devices"][plane]["modules"], key=lambda m: m[1])
    (w0, _), idle = capture.idle_intervals(got["devices"][plane]["ops"])
    spans = sorted(got["spans"], key=lambda e: e[1])
    print(f"{plane}: window starts at {w0:.0f} ns; {len(modules)} programs, "
          f"{len(steps_of(got))} sched.step spans")
    for name, s, d in steps_of(got)[:steps]:
        print(f"step {got['step_nums'].get(s)} "
              f"[{(s - w0) / 1e6:9.3f}, {(s + d - w0) / 1e6:9.3f}] ms")
        for pname, ps, pd in spans:
            if s <= ps < s + d and pname != capture.STEP_SPAN:
                print(f"  {pname:15s} [{(ps - w0) / 1e6:9.3f}, "
                      f"{(ps + pd - w0) / 1e6:9.3f}]")
                if pname == "sched.dispatch":
                    after = [m for m in modules if m[1] >= ps][:2]
                    before = [m for m in modules if m[1] < ps][-1:]
                    for m in before + after:
                        print(f"    program {capture.strip_hash(m[0]):28s} "
                              f"starts {(m[1] - w0) / 1e6:9.3f} "
                              f"({(m[1] - ps) / 1e3:+9.1f} us from the "
                              f"span's start), runs {m[2] / 1e6:.3f} ms")
    print("longest gaps, and the span that holds each one's end:")
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:8]:
        holds = [p for p in spans if p[1] <= e < p[1] + p[2]]
        inner = max(holds, key=lambda p: p[1]) if holds else None
        print(f"  gap [{(s - w0) / 1e6:9.3f}, {(e - w0) / 1e6:9.3f}] "
              f"{(e - s) / 1e6:7.3f} ms ends in "
              + (f"{inner[0]} {(e - inner[1]) / 1e3:+.1f} us after its start"
                 if inner else "no span"))


def to_text_proto(got, t_lo: float, t_hi: float) -> str:
    """An XSpace text proto of the events that start in ``[t_lo, t_hi]``,
    every line on one clock that starts at ``t_lo``."""
    def events(evs, meta: Dict[str, int], stats=None) -> str:
        rows = []
        for name, s, d in sorted(evs, key=lambda e: e[1]):
            if not t_lo <= s <= t_hi:
                continue
            mid = meta.setdefault(name, len(meta) + 1)
            stat = ""
            if stats and s in stats and name == capture.STEP_SPAN:
                stat = f" stats {{ metadata_id: 1 int64_value: {stats[s]} }}"
            rows.append(
                f"events {{ metadata_id: {mid} "
                f"offset_ps: {int(round((s - t_lo) * 1000))} "
                f"duration_ps: {int(round(d * 1000))}{stat} }}"
            )
        return " ".join(rows)

    def metas(meta: Dict[str, int]) -> str:
        return " ".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}" for n, i in meta.items()
        )

    out = []
    for pid, (plane, lines) in enumerate(sorted(got["devices"].items()), 1):
        meta: Dict[str, int] = {}
        body = " ".join(
            f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
            f"{events(lines[key], meta)} }}"
            for lid, (lname, key) in enumerate(
                ((capture.MODULES_LINE, "modules"), (capture.OPS_LINE, "ops")), 1
            )
        )
        out.append(f'planes {{ id: {pid} name: "{plane}" {body} {metas(meta)} }}')
    meta = {}
    body = (
        'lines { id: 1 name: "python3" timestamp_ns: 0 '
        f"{events(got['spans'], meta, got['step_nums'])} }}"
    )
    out.append(
        f'planes {{ id: {len(out) + 1} name: "/host:CPU" {body} {metas(meta)} '
        'stat_metadata { key: 1 value { id: 1 name: "step_num" } } }'
    )
    return "\n".join(out)


def main(argv: List[str]) -> int:
    if len(argv) in (3, 4) and argv[1] == "layout":
        layout(argv[2], *(int(a) for a in argv[3:]))
        return 0
    if len(argv) == 6 and argv[1] == "cut":
        from jax.profiler import ProfileData

        got = capture.read_xplane(argv[2])
        steps = steps_of(got)[int(argv[4]):int(argv[4]) + int(argv[5])]
        # from a little before the first step's span to the last one's end
        t_lo = steps[0][1] - 0.2e6
        t_hi = steps[-1][1] + steps[-1][2]
        text = to_text_proto(got, t_lo, t_hi)
        with open(argv[3], "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))
        print(json.dumps(capture.summarize(**capture.read_xplane(argv[3]))))
        return 0
    sys.stderr.write(__doc__)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
