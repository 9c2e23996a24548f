"""The sliding layers' decode attention as a share of the decode
program: the device time of the calls ``%gqa_window_decode_attention.N``
(``ops/decode_attention.py`` over a ring of window rows, one a sliding
layer a decode step) over the device time of the programs
``jit__decode_impl`` in the traced stretch (``XLA Modules`` line), in
percent. Both are added up over the same stretch, a program that an end
of the trace cuts included with the part that was traced.

0.0 where the configuration has no sliding layer whose rows are kept at
window size (no ``sliding_attention`` in a ``cohere2_moe`` file's
``layer_types``): that is the truth of it. **Nothing** where it has such
layers and the stretch holds no such call, so that the capture is
retaken and the run fails by name: a renamed kernel, or a decode step
that took the XLA form, must not read 0."""

import re

from perfbench import roofline_window

KERNEL = re.compile(r"^%gqa_window_decode_attention[\w.\-]* = .* custom-call\(")
PROGRAM = "jit__decode_impl"


def read(ctx):
    cfg = ctx["model_config"]
    if cfg.get("model_type") != "cohere2_moe" or not (
        roofline_window.window_of(cfg)[1]
    ):
        return 0.0
    devices = [d for t in (ctx.get("traces") or []) for d in t["devices"]]
    kernel = sum(
        v["total_ns"] for d in devices for name, v in d["ops"].items()
        if KERNEL.match(name)
    )
    program = sum(
        m[2] for d in devices for m in d["module_events"] if m[0] == PROGRAM
    )
    if not kernel or not program:
        return None
    return 100.0 * kernel / program
