"""Device time per call of the ops that are neither a Pallas kernel nor a
collective: the mega engine's slow boundary, slot landing and window glue,
and the whole window where the XLA oracle runs it."""


def read(ctx):
    v = ctx.per_call_ms(kinds=("xla",))
    return v if v else None
