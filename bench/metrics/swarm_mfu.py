"""The swarm round's share of the chips' bf16 peak: the forward and backward
FLOPs its gradients need (from shapes, bench/flops.py) times the rounds of
the window, over the window and the chips."""


def read(ctx):
    f = ctx.facts
    if not f.get("steps") or "flops_per_step" not in f:
        return None
    rate = f["flops_per_step"] * f["steps"] / f["elapsed"]
    return 100.0 * rate / (ctx.chips * ctx.peak["bf16_flops_per_s"])
