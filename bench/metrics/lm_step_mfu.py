"""``lm_step_mfu``: the FLOPs the GRPO step needs for the response tokens
trained in the traced window (``benchlib/lm_flops.py``: real tokens only,
causal attention, the old log-probabilities' forward counted, remat not,
the routed term from the step's assignment counter), over the window's
length times the chips times their bf16 peak, in %."""


def read(ctx):
    t = ctx.traced
    if not t or t["samples"] <= 0 or not ctx.flops_per_sample:
        return None
    return 100.0 * t["samples"] * ctx.flops_per_sample / (
        t["seconds"] * ctx.chips * ctx.peaks["bf16_flops"])
