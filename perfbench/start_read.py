"""What the ``start.*`` readers share: one number of the ``startup``
object that an engine's ``/healthz`` carries (``ctx["healths"]``, read
after the window and the checks, so for the process's whole life)."""


def largest(ctx, *path):
    """``startup[path...]`` of every replica that has the object, the
    largest (the replica that came up last is the one a deployment
    waits for). None where no engine has the object: a program from
    before it; a count of 0 is a number."""
    values = []
    for health in ctx.get("healths") or []:
        value = health.get("startup")
        for key in path:
            value = value.get(key) if isinstance(value, dict) else None
        if value is not None:
            values.append(float(value))
    return max(values) if values else None
