"""dual_ms (ms): device time per round of the dual update (eq. 3; ops
under the program's ``admm_dual`` scope), the mean over the cell's
devices."""
from harness import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "admm_dual")
