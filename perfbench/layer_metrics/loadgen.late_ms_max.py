"""How late the generator sent a request against its schedule, at worst
(ms): a starved generator must not be read as a fast server."""


def read(ctx):
    return (ctx.get("loadgen") or {}).get("late_ms_max")
