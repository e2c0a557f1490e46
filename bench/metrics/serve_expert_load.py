"""Token-slots routed to each held expert per engine step, averaged over
the window's steps, the expert layers and the held experts: the engine's
own per-step count of routes to held experts
(``ServeRecord.stats["expert_slots"]``, all layers) over steps x expert
layers x held experts.  With every slot occupied and routing even it
reads slots x top-k / routed experts (64 x 6 / 64 = 6 in the
DeepSeek-V2-Lite cell); a deployment of 8 chips serving 64 slots each
gives an expert 48."""


def read(ctx):
    f = ctx.facts
    if not f.get("steps") or "expert_slots" not in f:
        return None
    return f["expert_slots"] / (f["steps"] * f["held_expert_layers"])
