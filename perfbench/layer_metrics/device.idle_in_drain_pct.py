"""Share of the traced stretch in which the chip stood idle while the
scheduler's thread was in a step's ``drain`` phase (the span
``sched.drain``, less any ``sched.wait`` inside it): the engine's own
reading of its capture, ``last_capture.idle_ms.drain`` over
``window_ms`` of its ``/healthz``."""

from perfbench.capture_read import share


def read(ctx):
    return share(ctx, "drain")
