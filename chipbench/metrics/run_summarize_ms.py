"""Host time per call of ``repro.run.summarize``: the device-to-host copies
of the rollout's outputs and the per-cell reduction
(``repro.envsim.batched.summarize``) into the ``RunResult``.  Also notes
the longest idle gaps of the device, each named by the program span it
fell in."""
from chipbench import program


def read(ctx):
    v = program.span_ms(ctx, "run.summarize")
    if v is not None:
        gaps = program.idle_gaps(ctx, top=10)
        ctx.notes.append("idle_gaps " + " ".join(
            f"{name}={sec:.6f}" for name, sec in gaps))
    return v
