"""What the per-layer metric readers read: the traced window reduced to
device-op intervals, the harness's spans around each ``api.run`` call, the
cell and the chip's peaks.  Also the ``breakdown`` of a traced result
line."""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from chipbench import trace as trace_mod


@dataclass
class Context:
    trace: trace_mod.Trace
    cell: dict
    n_cells: int
    n_windows: int
    calls: list          # (start_ns, end_ns) of every api.run span
    window: tuple        # (start_ns, end_ns) of the window span
    peak: dict
    notes: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds of the window in which an op ran, averaged over devices."""
        lo, hi = self.window
        return float(np.mean([self.trace.busy_ns(d, lo, hi)
                              for d in range(self.trace.n_devices)])) / 1e9

    def per_call_ms(self, kinds=None, reduce=np.mean) -> float | None:
        """Device time of ops of ``kinds`` inside each call's span (union per
        device, ``reduce`` over devices), averaged over calls, in ms."""
        if not self.calls or not self.trace.n_devices:
            return None
        vals = [reduce([self.trace.busy_ns(d, s, e, kinds)
                        for d in range(self.trace.n_devices)])
                for s, e in self.calls]
        return float(np.mean(vals)) / 1e6

    def has_kind(self, kind: str) -> bool:
        lo, hi = self.window
        return any(np.any((o["kind"] == kind) & (o["end"] > lo)
                          & (o["start"] < hi)) for o in self.trace.ops)


def context(trace_dir: str, cell: dict, e, n_calls: int, peak: dict) -> Context:
    tr = trace_mod.load(trace_mod.newest_xplane(trace_dir))
    windows = tr.spans_named("window")
    calls = tr.spans_named("api_run")
    if not windows or len(calls) != n_calls:
        raise RuntimeError(f"trace holds {len(windows)} window spans and "
                           f"{len(calls)} api_run spans for {n_calls} calls")
    if not tr.n_devices:
        raise RuntimeError("trace holds no TPU device plane")
    return Context(trace=tr, cell=cell, n_cells=e.n_cells,
                   n_windows=e.n_windows, calls=calls, window=windows[0],
                   peak=peak)


def breakdown(ctx: Context, top: int = 10) -> dict:
    """The device ops that took the most time in the window (seconds, mean
    over devices) and the longest idle gaps of device 0, each named by the
    harness span it fell in."""
    lo, hi = ctx.window
    totals: dict[str, float] = {}
    for o in ctx.trace.ops:
        sel = (o["end"] > lo) & (o["start"] < hi)
        for name, s, e in zip(o["name"][sel], o["start"][sel], o["end"][sel]):
            totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
    n = max(ctx.trace.n_devices, 1)
    ops = sorted(((k, v / n) for k, v in totals.items()), key=lambda x: -x[1])

    def where(a, b):
        mid = 0.5 * (a + b)
        for s, e in ctx.calls:
            if s <= mid <= e:
                return "api.run"
        return "between calls"

    gaps = sorted(((where(a, b), (b - a) / 1e9)
                   for a, b in ctx.trace.idle_gaps(0, lo, hi)),
                  key=lambda x: -x[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}
