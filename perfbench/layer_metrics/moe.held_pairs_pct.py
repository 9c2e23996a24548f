"""Share of the router's (token, expert) pairs that fell on experts this
replica holds, in a configuration that holds one chip's share of its
experts: the engine's counters ``gpustack_engine_moe_pairs_total
{held="yes"|"no"}`` as its ``/healthz`` gives them after the window
(``moe_pairs``), held over all, in percent. 6.25 where 12 of 192 are
held and the routing is even. The counters are the engine's whole life's
(warm-up, window, tail and checks: the same traffic throughout) and
count the prefill programs' pairs, bucket padding included; decode
steps are not counted. An engine that has no such counter (every expert
held, or a program from before the counter) gives nothing to read."""


def read(ctx):
    pairs = [
        h["moe_pairs"] for h in (ctx.get("healths") or [])
        if h.get("moe_pairs")
    ]
    held = sum(p["held"] for p in pairs)
    total = held + sum(p["absent"] for p in pairs)
    return 100.0 * held / total if total else None
