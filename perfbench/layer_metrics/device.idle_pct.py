"""Share of the traced stretch in which no operation ran on the chip: 1 -
the union of the ``XLA Ops`` intervals over the stretch, averaged over
the chips traced."""


def read(ctx):
    idle = [
        d["idle_pct"] for t in (ctx.get("traces") or []) for d in t["devices"]
        if d["idle_pct"] is not None
    ]
    return sum(idle) / len(idle) if idle else None
