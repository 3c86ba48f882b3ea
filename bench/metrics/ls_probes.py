"""ls_probes (count): line-search objective evaluations per round (the W,
hidden-Z and FISTA searches), from the program's ``state.probes``
counter, the mean over shards."""
from harness import program


def read(ctx):
    return program.counter_per_round(ctx, 0)
