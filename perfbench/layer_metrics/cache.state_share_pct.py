"""Of what the slots keep on the device, the share that is recurrent
state and not rows a position: ``cache.state_bytes`` over ``state_bytes
+ kv_bytes`` as the engines' ``/healthz`` gives them after the window
(``engine/engine.py health``, both allocated whole at the start), in
percent. 66 for 32 slots of 4,096 positions under Nemotron-3-Nano's 23
state-space and 6 attention layers; 0 for a model without a state. An
engine from before ``/healthz`` had ``cache`` gives nothing to read."""


def read(ctx):
    caches = [
        h["cache"] for h in (ctx.get("healths") or []) if h.get("cache")
    ]
    state = sum(c["state_bytes"] for c in caches)
    total = state + sum(c["kv_bytes"] for c in caches)
    return 100.0 * state / total if total else None
