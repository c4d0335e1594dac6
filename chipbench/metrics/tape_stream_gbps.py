"""Slot-tape bytes the megakernel's launches read from HBM per call (the
``tape_bytes`` argument of the call's ``repro.run.dispatch`` spans, counted
from shapes by the program) over the kernel's device time per call, in
GB/s.  Nothing to read where no dispatch span carries the count."""
import numpy as np

from chipbench import program


def read(ctx):
    prog = program.of(ctx)
    if prog is None or not ctx.has_kind("pallas"):
        return None
    per_call = []
    for lo, hi in ctx.calls:
        spans = [a for _, _, _, a, _ in prog.named("run.dispatch", lo, hi)
                 if "tape_bytes" in a]
        if not spans:
            return None
        per_call.append(sum(float(a["tape_bytes"]) for a in spans))
    ms = ctx.per_call_ms(kinds=("pallas",))
    if not ms:
        return None
    return float(np.mean(per_call)) / (ms / 1e3) / 1e9
