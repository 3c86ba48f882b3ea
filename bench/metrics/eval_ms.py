"""eval_ms (ms): device time per round in the programs ``train()`` runs
after every step, the metrics and the Lagrangian, found by their XLA
module names; the mean over the cell's devices."""
from harness import names, trace

MATCH = names.matcher(names.EVAL_MODULE)


def read(ctx):
    tr = ctx["trace"]
    found = any(MATCH(e[0]) for d in tr["devices"].values()
                for e in d["modules"])
    if ctx["rounds"] <= 0 or not found:
        return None
    s = trace.mean_over_devices(
        tr, lambda d: trace.time_in(tr, d, "modules", MATCH))
    return 1e3 * s / ctx["rounds"]
