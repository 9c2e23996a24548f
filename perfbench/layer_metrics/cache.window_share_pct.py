"""Of the rows the slots keep on the device, the share that lies in the
window store (the sliding layers' rows, kept at window size) and not in
the full layers' ``S_max`` rows a slot: ``cache.window_bytes`` over
``window_bytes + kv_bytes`` as the engines' ``/healthz`` gives them after
the window (``engine/engine.py health``, both allocated whole at the
start), in percent. 60 for 16 slots of 8,192 positions under Command
A+'s 6 sliding layers of 4,096 rows and 2 full layers (75 if the sliding
layers kept 8,192); 0 for a model without such a store. An engine from
before ``/healthz`` had ``cache.window_bytes`` gives nothing to read."""


def read(ctx):
    caches = [
        h["cache"] for h in (ctx.get("healths") or [])
        if "window_bytes" in (h.get("cache") or {})
    ]
    window = sum(c["window_bytes"] for c in caches)
    total = window + sum(c["kv_bytes"] for c in caches)
    return 100.0 * window / total if total else None
