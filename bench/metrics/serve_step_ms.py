"""Milliseconds per engine step: the engine's own episode time (host clock
around each ``ServingEngine.run`` program) over the steps it ran."""


def read(ctx):
    f = ctx.facts
    if not f.get("steps") or "engine_wall_s" not in f:
        return None
    return 1e3 * f["engine_wall_s"] / f["steps"]
