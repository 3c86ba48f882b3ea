"""agg_roofline (%): the least time of the aggregation work one round of
the paper's updates requires (``harness.work.aggregations``: each Ã·X at
sparse nnz, bounded by the larger of FLOPs / peak FLOP/s and bytes / peak
bytes/s) over the device time per round in the aggregation kernels."""
from harness import work
from metrics import agg_kernel_ms


def read(ctx):
    s = agg_kernel_ms.kernel_s_per_round(ctx)
    if not s:
        return None
    least = work.aggregation_least_s(ctx["n"], ctx["nnz"], ctx["dims"],
                                     ctx["peak"])
    return 100.0 * least / s
