"""What a captured stretch holds. A capture is ``trace_steps`` busy
scheduler steps wrapped in ``jax.profiler`` (``POST
/v2/model-instances/{id}/profile``). The chip is never idle under load,
so its trace begins and ends inside a program, and in a closed loop a
prefill comes only when a stream ends: a stretch of 16 steps now and then
holds no prefill, or only one that an end of the trace cuts, and a reader
of the prefill then has nothing to read. ``run.py`` reduces each capture
while the traffic goes on, asks the cell's ``device_trace`` readers, and
captures again until each reads a number (``capture_served``).

The step records cannot decide that beforehand (PERF.md section 3, "The
retake rule"): the step sealed first may have dispatched its prefill
before the trace began, and the programs of the last two steps run after
the profiler has stopped. They are for the log: what a capture held, a
letter a step.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

Record = Dict[str, Any]

MODE_LETTER = {
    "prefill": "p", "prefill_chunk": "c", "decode": "d", "spec_verify": "s",
}


def modes(records: Sequence[Record]) -> str:
    """One letter a step (``p`` prefill, ``c`` prefill chunk, ``d``
    decode, ``s`` speculative verify): what a capture held, for the log."""
    return "".join(MODE_LETTER.get(r.get("mode"), "?") for r in records)


def whole_programs(device: Dict[str, Any]) -> List[List[Any]]:
    """The program events of a reduced trace (``[name, start_ns,
    duration_ns]``, starts counted from the first operation) that neither
    end of the trace cuts: the event of a program that was running when
    the trace began or ended is only as long as the part that was
    traced. The reduction counts the window from the first traced
    operation's start to the last one's end, and a cut program's event
    begins with the one and ends with the other (each of 67 captures, PR
    28): nothing but rounding is allowed for."""
    end = device["window_s"] * 1e9
    return [
        m for m in device["module_events"]
        if m[1] > 0 and m[1] + m[2] < end - 1.0
    ]
