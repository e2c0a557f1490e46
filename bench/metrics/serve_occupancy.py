"""Share of the slots occupied over the window's engine steps, from the
engine's own per-step count (``ServeRecord.n_active``): the load the
offered traffic holds, drain included."""


def read(ctx):
    f = ctx.facts
    if "occupancy" not in f:
        return None
    return 100.0 * f["occupancy"]
