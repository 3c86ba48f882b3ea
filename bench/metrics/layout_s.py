"""layout_s (s): the trainer constructor's ``construct.layout`` span: the
community layout, the packed device layout and the device data placed on
the mesh."""
from harness import program


def read(ctx):
    return program.span_s(ctx, "construct.layout")
