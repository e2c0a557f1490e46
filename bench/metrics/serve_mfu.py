"""The serving engine's share of the chips' bf16 peak: 2 FLOPs per matmul
weight for every token an occupied slot decoded or prefilled in the
window (bench/flops.py), over the window and the chips."""


def read(ctx):
    f = ctx.facts
    if not f.get("flops"):
        return None
    return 100.0 * f["flops"] / f["elapsed"] / (
        ctx.chips * ctx.peak["bf16_flops_per_s"])
