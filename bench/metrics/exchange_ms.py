"""exchange_ms (ms): device time per round in the collective-permute
operations of the ppermute exchange (``core/messages.py``); the mean over
the cell's devices."""
from harness import names, trace

MATCH = names.matcher(names.EXCHANGE_OP)


def read(ctx):
    tr = ctx["trace"]
    found = any(MATCH(e[0]) for d in tr["devices"].values() for e in d["ops"])
    if ctx["rounds"] <= 0 or not found:
        return None
    s = trace.mean_over_devices(tr, lambda d: trace.time_in(tr, d, "ops",
                                                            MATCH))
    return 1e3 * s / ctx["rounds"]
