"""z_update_ms (ms): device time per round of the hidden-layer Z update
with its per-community backtracking (eq. 5/6; ops under the program's
``admm_z`` scope), the mean over the cell's devices."""
from harness import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "admm_z")
