"""``policy_mfu``: the policy's matrix-product FLOPs the algorithm needs
for the samples trained in the traced window (``benchlib/flops.py``), over
the window's length times the chips times their bf16 peak, in %."""


def read(ctx):
    t = ctx.traced
    if not t or t["samples"] <= 0:
        return None
    return 100.0 * t["samples"] * ctx.flops_per_sample / (
        t["seconds"] * ctx.chips * ctx.peaks["bf16_flops"])
