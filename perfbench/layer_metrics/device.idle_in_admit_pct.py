"""Share of the traced stretch in which the chip stood idle while the
scheduler's thread was in a step's ``admit`` phase (the span
``sched.admit``, less any ``sched.wait`` inside it): the engine's own
reading of its capture, ``last_capture.idle_ms.admit`` over
``window_ms`` of its ``/healthz``."""

from perfbench.capture_read import share


def read(ctx):
    return share(ctx, "admit")
