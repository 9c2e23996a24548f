"""Programs the backend compiled because the persistent cache had none, in
the engine process's whole life. 0 on a warm machine: a run where it is
not is a cold run whatever its ``setup_s``.
``startup.programs.cache_misses`` of the engine's ``/healthz``, the
largest over the replicas.
An engine from before the object existed gives nothing to read."""

from perfbench.start_read import largest


def read(ctx):
    return largest(ctx, 'programs', 'cache_misses')
