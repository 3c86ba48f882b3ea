"""idle_share (%): the share of the traced window in which no operation
runs on the device, 1 − (union of device-op intervals) / window, the mean
over the cell's devices."""
from harness import trace


def read(ctx):
    tr = ctx["trace"]
    w = trace.window_s(tr)
    if not tr["devices"] or w <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(tr) / w)
