"""The fused robust-aggregation kernels' share of their roofline: the least
time the chip needs for the bytes and operations of every round's kernels
in the window (from the stack's shape, bench/flops.py), over the device
time of their Mosaic custom calls in the traced window, per chip.  The
bytes bound them: 42.9 GB of HBM traffic against 9.6 GFLOP per round."""


def read(ctx):
    f = ctx.facts
    if ctx.trace is None or "kernel_bytes_per_step" not in f:
        return None
    seconds = sum(s.seconds for s in ctx.trace.ops.values()
                  if "tpu_custom_call" in s.detail) / ctx.trace.n_devices
    if seconds <= 0:
        return None
    least = max(f["kernel_bytes_per_step"] / ctx.peak["hbm_bytes_per_s"],
                f["kernel_flops_per_step"] / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least * f["steps"] / seconds
