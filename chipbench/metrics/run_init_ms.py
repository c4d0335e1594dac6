"""Host time per call of ``repro.run.init``: the call's state set-up
(``repro.core.mega.init_mega_state``, the telemetry carry, the env state
and the key), dispatched eagerly before the launch."""
from chipbench import program


def read(ctx):
    return program.span_ms(ctx, "run.init")
