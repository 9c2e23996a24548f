"""Seconds the engine process's threads stood in the backend's compile or,
where the persistent cache had the program, in its load from there, every
program of the process's life, concurrent spans counted once.
``startup.programs.load_s`` of the engine's ``/healthz``, the largest over
the replicas.
An engine from before the object existed gives nothing to read."""

from perfbench.start_read import largest


def read(ctx):
    return largest(ctx, 'programs', 'load_s')
