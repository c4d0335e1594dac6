"""Share of the traced window in which no op ran on the device, averaged
over the cell's devices (layer: the TPU itself)."""
import numpy as np


def read(ctx):
    lo, hi = ctx.window
    if hi <= lo or not ctx.trace.n_devices:
        return None
    busy = [ctx.trace.busy_ns(d, lo, hi) for d in range(ctx.trace.n_devices)]
    return float(100.0 * (1.0 - np.mean(busy) / (hi - lo)))
