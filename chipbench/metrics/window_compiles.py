"""Functions traced per call inside the window: the ``traces`` argument of
each call's ``repro.run.counts`` marker (a jit cache miss), averaged over
calls.  A call's outermost run ends last, and its marker counts the runs
nested in it too.  Warm calls should trace nothing."""
import numpy as np

from chipbench import program


def read(ctx):
    prog = program.of(ctx)
    if prog is None:
        return None
    per_call = []
    for lo, hi in ctx.calls:
        marks = prog.named("run.counts", lo, hi)
        per_call.append(float(max(marks, key=lambda sp: sp[2])[3]["traces"])
                        if marks else 0.0)
    return float(np.mean(per_call))
