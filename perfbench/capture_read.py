"""What the ``device.idle_in_*`` readers share: a part of the idle time
of the engine's newest traced capture, as a share of its window. The
engine reads its own capture (``gpustack_tpu/observability/capture.py``:
every idle interval of the chip put down to the ``sched.*`` span of the
scheduler's thread it falls in) and keeps the digest in its ``/healthz``
as ``last_capture`` (``ctx["healths"]``, read after the tail and the
checks, which capture nothing: the capture that served the run)."""


def share(ctx, *names):
    """``100 * sum(idle_ms[name]) / window_ms`` of ``last_capture``, the
    mean over the replicas whose object has idle numbers. None where no
    engine has them: a program from before the object, a capture whose
    summary failed (``error``), a CPU run (``devices: 0``)."""
    values = []
    for health in ctx.get("healths") or []:
        capture = health.get("last_capture")
        if not isinstance(capture, dict):
            continue
        idle, window = capture.get("idle_ms"), capture.get("window_ms")
        if isinstance(idle, dict) and window:
            values.append(100.0 * sum(idle.get(n, 0.0) for n in names) / window)
    return sum(values) / len(values) if values else None
