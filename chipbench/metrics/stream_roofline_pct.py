"""Least time for the rollout's windows (``chipbench/work.py``: filled
slots, cache and carry read once, outputs written once, over the peaks
table) as a share of the megakernel's device time per call, in the cell
whose kernel streams its slot tape from HBM."""
from chipbench import work


def read(ctx):
    if not ctx.has_kind("pallas"):
        return None
    ms = ctx.per_call_ms(kinds=("pallas",))
    if not ms:
        return None
    w = work.window_work(ctx.cell["config"], ctx.n_cells, ctx.n_windows)
    pct, bound = work.roofline(w, ctx.peak, ms / 1e3)
    ctx.notes.append(f"stream_roofline_pct bound={bound}")
    return float(pct)
