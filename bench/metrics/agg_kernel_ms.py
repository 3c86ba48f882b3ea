"""agg_kernel_ms (ms): device time per round in the Pallas aggregation
kernels (``kernels/community_spmm.py``), in every program; the mean over
the cell's devices."""
from harness import names, trace

MATCH = names.matcher(names.AGG_KERNEL)


def kernel_s_per_round(ctx):
    tr = ctx["trace"]
    found = any(MATCH(e[0]) for d in tr["devices"].values() for e in d["ops"])
    if ctx["rounds"] <= 0 or not found:
        return None
    s = trace.mean_over_devices(tr, lambda d: trace.time_in(tr, d, "ops",
                                                            MATCH))
    return s / ctx["rounds"]


def read(ctx):
    s = kernel_s_per_round(ctx)
    return None if s is None else 1e3 * s
