"""Read a profiler trace (``.xplane.pb``) into device-op intervals and the
benchmark's own host spans, on one clock.

Device ops come from the ``XLA Ops`` line of every ``/device:TPU:<n>``
plane, each named by its HLO instruction text.  Each op gets a kind from its
opcode, never from a Python function name of the program: ``pallas`` for a
Pallas kernel (a custom call to ``tpu_custom_call``), ``collective`` for the
collective opcodes, ``xla`` for the rest.  Control-flow ops (``while``,
``conditional``, ``call``) span the ops they run, which the line lists
too, so they are left out.  Host spans are the ``chipbench.*``
``TraceAnnotation`` events the harness records around its own calls.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

import numpy as np

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv")
#: ops whose event spans the ops they run, which the trace lists themselves
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "chipbench."


def hlo_opcode(name: str) -> str:
    """The opcode of an op event named by its HLO instruction text
    (``%fusion.3 = f32[8]{0} fusion(...)``, or a tuple shape before the
    opcode); the name itself where it is not such text."""
    if " = " not in name:
        return name.split(".", 1)[0].lstrip("%")
    rhs = name.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rhs[i + 1:].lstrip()
    else:
        rest = rhs.split(" ", 1)[1] if " " in rhs else rhs
    return rest.split("(", 1)[0].strip()


def op_kind(name: str) -> str:
    """``pallas`` (a Mosaic kernel: a custom call to ``tpu_custom_call``),
    ``collective``, ``container`` (while/conditional/call, whose children
    are listed apart) or ``xla`` for one device op."""
    op = hlo_opcode(name)
    if op == "custom-call" and 'custom_call_target="tpu_custom_call"' in name:
        return "pallas"
    if any(op.startswith(c) for c in COLLECTIVES):
        return "collective"
    if op in CONTAINERS:
        return "container"
    return "xla"


@dataclass
class Trace:
    """Device ops per device: ``ops[d]`` is a dict of arrays sorted by start,
    ``start``/``end`` (ns), ``kind`` and ``name``; ``spans`` is a list of
    (name, start, end) host spans on the same clock."""

    ops: list[dict] = field(default_factory=list)
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def spans_named(self, name: str) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == SPAN_PREFIX + name]

    def _clipped(self, d: int, lo: float, hi: float, kinds=None):
        o = self.ops[d]
        sel = (o["end"] > lo) & (o["start"] < hi)
        if kinds is not None:
            sel &= np.isin(o["kind"], list(kinds))
        return np.maximum(o["start"][sel], lo), np.minimum(o["end"][sel], hi)

    def busy_ns(self, d: int, lo: float, hi: float, kinds=None) -> float:
        """Length of the union of device ``d``'s op intervals (of ``kinds``,
        or all) inside [lo, hi]."""
        start, end = self._clipped(d, lo, hi, kinds)
        return sum(b - a for a, b in _merged(start, end))

    def idle_gaps(self, d: int, lo: float, hi: float):
        """(start, end) of every gap in device ``d``'s busy union in [lo, hi]."""
        gaps, cursor = [], lo
        for a, b in _merged(*self._clipped(d, lo, hi)):
            if a > cursor:
                gaps.append((cursor, a))
            cursor = b
        if cursor < hi:
            gaps.append((cursor, hi))
        return gaps


def _merged(start: np.ndarray, end: np.ndarray):
    """The union of the intervals as disjoint (start, end) pairs, in order."""
    out = []
    for a, b in sorted(zip(start.tolist(), end.tolist())):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def build(events, spans) -> Trace:
    """A :class:`Trace` from raw ``(device, start_ns, end_ns, name)`` op
    events and ``(name, start_ns, end_ns)`` host spans; containers and
    spans of other prefixes are dropped."""
    per_dev: dict[int, list] = {}
    for d, s, e, name in events:
        kind = op_kind(name)
        if kind != "container":
            per_dev.setdefault(int(d), []).append((float(s), float(e), kind,
                                                   name))
    tr = Trace(spans=sorted(((n, float(s), float(e)) for n, s, e in spans
                             if n.startswith(SPAN_PREFIX)),
                            key=lambda x: x[1]))
    for d in sorted(per_dev):
        rows = sorted(per_dev[d])
        tr.ops.append({
            "start": np.asarray([r[0] for r in rows], np.float64),
            "end": np.asarray([r[1] for r in rows], np.float64),
            "kind": np.asarray([r[2] for r in rows], object),
            "name": np.asarray([r[3] for r in rows], object),
        })
    return tr


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Parse one ``.xplane.pb`` (``jax.profiler.ProfileData``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    events, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                d = int(plane.name.rsplit(":", 1)[1])
            except ValueError:        # a plane of another unit of the chip
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    events.extend((d, ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return build(events, spans)
