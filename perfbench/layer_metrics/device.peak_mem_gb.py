"""Peak bytes in use on the fullest chip, as the engine's ``/healthz``
reports JAX's ``memory_stats()`` after the window, in GB (1e9)."""


def read(ctx):
    peak = ctx.get("peak_memory_bytes")
    return peak / 1e9 if peak else None
