"""``expert_gmm_roofline``: the grouped expert kernel (Pallas megablox
``gmm`` forward and input gradient, ``tgmm`` weight gradient, run through
``kernels/ops.py::gmm``) against its roofline, in %: for each call found
in the traced window, the least time max(FLOPs / bf16 peak, least bytes /
HBM peak) of an average call of its kind (``benchlib/lm_flops.py``: rows
from the step's assignment counter, widths from the configuration),
summed, over the calls' measured device time.  The calls are the
``tpu_custom_call`` instructions named ``gmm.N`` and ``tgmm.N`` after the
kernels' jitted wrappers."""
from benchlib.lm_flops import gmm_call_cost, gmm_calls


def is_kernel(kind):
    return lambda op: op.pallas and op.name.split(".")[0] == kind


def read(ctx):
    shape = ctx.kernel_shapes.get("expert_gmm")
    if not shape:
        return None
    w, rows, peaks = shape["widths"], shape["rows_per_call"], ctx.peaks
    mix = gmm_calls(w)
    least, seconds = 0.0, 0.0
    for kind in ("gmm", "tgmm"):
        s, calls = ctx.reduction.kernel_seconds(is_kernel(kind))
        costs = [gmm_call_cost(k, rows, K, N, w["Eh"])
                 for k, K, N in mix if k == kind]
        per_call = sum(max(f / peaks["bf16_flops"],
                           b / peaks["hbm_bytes_per_s"])
                       for f, b in costs) / len(costs)
        least += calls * per_call
        seconds += s
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
