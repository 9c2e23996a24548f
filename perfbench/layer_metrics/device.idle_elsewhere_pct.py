"""Share of the traced stretch in which the chip stood idle and the
scheduler's thread was in none of ``drain``, ``admit``, ``dispatch`` and
``wait``: in a prefill chunk, inside a step and outside every phase (the
record's sealing), between two steps (the loop round ``step()``), or
where the trace holds no ``sched.step`` span. With the four
``device.idle_in_*`` shares it adds up to the capture's idle share.
``last_capture.idle_ms`` of the engine's ``/healthz``."""

from perfbench.capture_read import share


def read(ctx):
    return share(ctx, "chunk", "step_other", "between_steps", "unannotated")
