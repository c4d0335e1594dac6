"""Host time of one ``repro.api.run`` call: the harness's span around the
call minus the device-busy time inside it (world lookup, state
initialisation, dispatch and the host summary), averaged over calls."""


def read(ctx):
    busy = ctx.per_call_ms()
    if busy is None:
        return None
    span = sum(e - s for s, e in ctx.calls) / len(ctx.calls) / 1e6
    return float(span - busy)
