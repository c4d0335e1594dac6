"""Device time per call of the XLA ops under ``aif.slow_step``: the slow
boundary (``repro.core.mega.mega_slow_step``: replay gathers, the A update,
the cache advance)."""
from chipbench import program


def read(ctx):
    return program.scope_ms(ctx, "aif.slow_step")
