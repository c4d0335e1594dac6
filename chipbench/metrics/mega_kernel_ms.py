"""Device time per call of the Pallas megakernel's launches (every Pallas
custom call in the call's span); nothing to read where no kernel ran."""


def read(ctx):
    if not ctx.has_kind("pallas"):
        return None
    return ctx.per_call_ms(kinds=("pallas",))
