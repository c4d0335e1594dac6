"""Bytes per call that ``repro.run.summarize.fetch`` copies from the device
(its ``bytes`` argument, counted from shapes), in MB."""
from chipbench import program


def read(ctx):
    v = program.span_arg(ctx, "run.summarize.fetch", "bytes")
    return None if v is None else v / 1e6
