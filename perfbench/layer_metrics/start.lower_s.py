"""Seconds the engine process's threads stood in Python tracing and in
lowering to MLIR, every program of the process's life (the weights', the
warm-up's, the window's if any), concurrent and nested spans counted
once: what a new kernel or model family adds to every start, whatever the
compile cache holds. ``startup.programs.lower_s`` of the engine's
``/healthz``, the largest over the replicas.
An engine from before the object existed gives nothing to read."""

from perfbench.start_read import largest


def read(ctx):
    return largest(ctx, 'programs', 'lower_s')
