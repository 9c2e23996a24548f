"""Share of the traced stretch in which the chip stood idle while the
scheduler's thread waited for it (the span ``sched.wait``: the chip has
finished and the host has not yet been told, the result's way back):
``last_capture.idle_ms.wait`` over ``window_ms`` of the engine's
``/healthz``."""

from perfbench.capture_read import share


def read(ctx):
    return share(ctx, "wait")
