"""Seconds of the engine's ``weights`` phase: ``load_or_init_params`` (and a
LoRA merge and ``quantize_params`` where they run) until the tree is on
the device. ``startup.phases.weights`` of the engine's ``/healthz``, the
largest over the replicas.
An engine from before the object existed gives nothing to read."""

from perfbench.start_read import largest


def read(ctx):
    return largest(ctx, 'phases', 'weights')
