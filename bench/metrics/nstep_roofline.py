"""``nstep_roofline``: the fused n-step return kernel
(``kernels/gae_scan.py::nstep_scan``, run through ``ops.nstep_returns``)
against its HBM roofline: the least bytes the scan moves
(``benchlib/flops.nstep_bytes``) over the HBM peak, per call, over the
kernel's measured device time per call, in %."""
from benchlib.flops import nstep_bytes


def is_kernel(op):
    return op.pallas and op.name.startswith("nstep_returns")


def read(ctx):
    seconds, calls = ctx.reduction.kernel_seconds(is_kernel)
    if not calls or "nstep" not in ctx.kernel_shapes:
        return None
    least = calls * nstep_bytes(*ctx.kernel_shapes["nstep"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
