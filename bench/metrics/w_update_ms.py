"""w_update_ms (ms): device time per round of the W update with its psum
line search (Line 3; ops under the program's ``admm_w`` scope), the mean
over the cell's devices."""
from harness import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "admm_w")
