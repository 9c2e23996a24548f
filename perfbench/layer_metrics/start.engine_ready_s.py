"""Seconds from the engine process's creation (as the OS has it) to the
first ``/healthz`` it answered 200: what the worker's ``RUNNING`` waits
for, interpreter, imports, the chip's runtime, the weights, the cache and
the listener included. ``startup.ready_s`` of the engine's ``/healthz``
(gpustack_tpu/observability/startup.py), the largest over the replicas.
An engine from before the object existed gives nothing to read."""

from perfbench.start_read import largest


def read(ctx):
    return largest(ctx, 'ready_s')
