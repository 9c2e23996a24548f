"""Seconds from the engine process's creation to the first token any of
its requests was handed: less ``start.engine_ready_s`` it is what the
worker's ``RUNNING`` leaves out (the first serving programs' lowering and
their compile or load). ``startup.first_token_s`` of the engine's
``/healthz``, the largest over the replicas.
An engine from before the object existed gives nothing to read."""

from perfbench.start_read import largest


def read(ctx):
    return largest(ctx, 'first_token_s')
