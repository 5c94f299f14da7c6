"""``env_mega_ms_per_round``: summed device time of the Pallas env
megakernel (``kernels/env_megakernel.py``; the one compiled Pallas kernel
of the ``_collect_ring`` program) in the traced window, over the rounds
completed there, in ms."""


def is_kernel(op):
    return op.pallas and op.module.startswith("jit__collect_ring")


def read(ctx):
    seconds, calls = ctx.reduction.kernel_seconds(is_kernel)
    if not calls or not ctx.traced or ctx.traced["units"] <= 0:
        return None
    return 1e3 * seconds / ctx.traced["units"]
