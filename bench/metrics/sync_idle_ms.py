"""sync_idle_ms (ms): device idle time per round that lies under the
program's ``train.sync`` and ``train.wait`` host spans (``train()``
reading its metrics back, and waiting for the step), the mean over the
cell's devices."""
from harness import program


def read(ctx):
    return program.idle_under_spans_ms_per_round(ctx)
