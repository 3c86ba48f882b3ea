"""init_state_s (s): the trainer constructor's ``construct.init_state``
span: initial weights, the normalised adjacency, the dense initial
forward, packing and placing the state."""
from harness import program


def read(ctx):
    return program.span_s(ctx, "construct.init_state")
