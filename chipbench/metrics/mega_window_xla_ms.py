"""Device time per call of the XLA ops under ``aif.window``: each window's
glue around the megakernel (key block, Gumbel draws, schedule slices,
operand layout) and the landing of its outputs in the slot tape."""
from chipbench import program


def read(ctx):
    return program.scope_ms(ctx, "aif.window")
