"""ls_capped (count): line searches per round that ran all
``max_backtracks`` iterations, from the program's ``state.probes``
counter, the mean over shards."""
from harness import program


def read(ctx):
    return program.counter_per_round(ctx, 1)
