"""``gae_us_per_call``: device time of one call of the fused GAE kernel
(``kernels/gae_scan.py``, run through ``ops.gae_norm``) in the traced
window, in microseconds.

A time and not a roofline share: inside the compiled PPO step XLA keeps
the kernel's operands in on-chip memory (``S(1)`` in the compiled HLO),
so the least HBM bytes of GAE over HBM bandwidth is no bound on it (it
read 208% on a TPU v5e)."""


def is_kernel(op):
    return op.pallas and op.name.startswith("gae_norm")


def read(ctx):
    seconds, calls = ctx.reduction.kernel_seconds(is_kernel)
    if not calls:
        return None
    return 1e6 * seconds / calls
