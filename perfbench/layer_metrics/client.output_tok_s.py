"""Content chunks (tokens) received per second of the window, at the
client. In an open-loop cell under its knee this is the offered load, cut
by where the window ends, not a property of the system: it stands here,
and is end to end only in the closed-loop cells."""


def read(ctx):
    red = ctx.get("loadgen") or {}
    return red["tokens"] / red["seconds"] if red.get("seconds") else None
