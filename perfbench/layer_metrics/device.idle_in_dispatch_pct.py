"""Share of the traced stretch in which the chip stood idle while the
scheduler's thread was in a step's ``dispatch`` phase (the span
``sched.dispatch``, less any ``sched.wait`` inside it): the engine's own
reading of its capture, ``last_capture.idle_ms.dispatch`` over
``window_ms`` of its ``/healthz``."""

from perfbench.capture_read import share


def read(ctx):
    return share(ctx, "dispatch")
