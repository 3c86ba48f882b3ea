"""fista_ms (ms): device time per round of the last layer's FISTA prox
(eq. 7; ops under the program's ``admm_fista`` scope), the mean over the
cell's devices."""
from harness import program


def read(ctx):
    return program.scope_ms_per_round(ctx, "admm_fista")
