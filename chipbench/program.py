"""The program's own record in a traced window: its ``repro.*`` host spans
with their arguments, and the scope path of every device op.

The program names its host steps with ``jax.profiler.TraceAnnotation``
(``repro.run`` and its children, see :mod:`repro.obs`) and its device work
with ``jax.named_scope`` (``aif.window``, ``aif.slow_step``, ...).  The
spans sit on the host planes of the trace with their arguments as event
stats.  A device op's scope path is the ``tf_op`` stat of its event
metadata (``jit(_mega_impl)/while/body/closed_call/aif.slow_step/...``);
``jax.profiler.ProfileData`` does not expose metadata stats, so
:func:`op_metadata` walks the ``.xplane.pb`` wire format for them.

An op the compiler added after the program was traced (a layout copy, the
custom fusion a scatter compiles to) has no ``tf_op``; it is counted with
the op that ran before it in the same program, since the device runs a
program's ops one after the other.  An op of another program (the eager
ops that set up a call's state) keeps its own path.

Where the program records nothing, as a build without these spans and
scopes does, every reader built on this module returns None.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import tempfile
from dataclasses import dataclass, field

import numpy as np

from chipbench import trace as trace_mod

SPAN_PREFIX = "repro."
SCOPE_PREFIX = "aif."


@dataclass
class Program:
    """``spans``: (name, start_ns, end_ns, args, line) of every ``repro.*``
    host span, sorted by start; ``scope[d]``: the scope path of each op of
    device ``d``, aligned with ``Trace.ops[d]``."""

    spans: list = field(default_factory=list)
    scope: list = field(default_factory=list)

    def named(self, name: str, lo: float = -np.inf, hi: float = np.inf):
        """Spans called ``repro.<name>`` that lie inside [lo, hi]."""
        full = SPAN_PREFIX + name
        return [sp for sp in self.spans
                if sp[0] == full and sp[1] >= lo and sp[2] <= hi]


# ------------------------------------------------------------ reading a file
def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of each field in buf[lo:hi]: an int for a
    varint or fixed field, a (start, end) pair for a length-delimited one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_values(buf: bytes, entry):
    """The value message of one ``map<int64, Message>`` entry."""
    for f, v in _fields(buf, *entry):
        if f == 2:
            return v
    return (entry[1], entry[1])


def op_metadata(path: str) -> dict[int, dict[str, tuple[str, int]]]:
    """Per TPU device, each op name's (``tf_op`` scope path, ``program_id``)
    from the event metadata of its plane (XSpace.planes = 1; XPlane: name
    2, event_metadata 4, stat_metadata 5; XEventMetadata: name 2, stats 5;
    XStat: metadata_id 1, uint64 3, int64 4, str 5, ref 7)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for fnum, plane in _fields(buf, 0, len(buf)):
        if fnum != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = buf[v[0]:v[1]].decode()
            elif f == 4:
                events.append(_map_values(buf, v))
            elif f == 5:
                sid, sname = 0, ""
                for g, w in _fields(buf, *_map_values(buf, v)):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = buf[w[0]:w[1]].decode()
                stat_names[sid] = sname
        if not name.startswith("/device:TPU:"):
            continue
        try:
            dev = int(name.rsplit(":", 1)[1])
        except ValueError:
            continue
        ops = out.setdefault(dev, {})
        for ev in events:
            ev_name, scope, program = "", "", 0
            for g, w in _fields(buf, *ev):
                if g == 2:
                    ev_name = buf[w[0]:w[1]].decode()
                elif g == 5:
                    stat, val = "", None
                    for h, x in _fields(buf, *w):
                        if h == 1:
                            stat = stat_names.get(x, "")
                        elif h == 5:
                            val = buf[x[0]:x[1]].decode()
                        elif h == 7:
                            val = stat_names.get(x, "")
                        elif h in (3, 4):
                            val = x
                    if stat == "tf_op" and isinstance(val, str):
                        scope = val
                    elif stat == "program_id" and isinstance(val, int):
                        program = val
            ops[ev_name] = (scope, program)
    return out


def host_spans(path: str) -> tuple[list, list]:
    """(``repro.*`` spans as (name, start, end, args, line), sorted by
    start; ``chipbench.*`` spans as (name, start, end)) from the host
    planes, on the device planes' clock."""
    from jax.profiler import ProfileData
    program, harness = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    program.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats), line.name))
                elif ev.name.startswith(trace_mod.SPAN_PREFIX):
                    harness.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    program.sort(key=lambda sp: sp[1])
    return program, harness


def build(tr: trace_mod.Trace, metadata: dict, spans: list) -> Program:
    """A :class:`Program` over ``tr``'s ops from :func:`op_metadata`'s
    table and :func:`host_spans`' program spans."""
    scopes = []
    for d, o in enumerate(tr.ops):
        table = metadata.get(d, {})
        last: dict[int, str] = {}            # program -> last scope path
        path = np.empty(len(o["name"]), object)
        for i, name in enumerate(o["name"]):
            scope, prog = table.get(name, ("", 0))
            if scope:
                last[prog] = scope
            else:
                scope = last.get(prog, "")
            path[i] = scope
        scopes.append(path)
    return Program(spans=list(spans), scope=scopes)


# ------------------------------------------------------ the run's own trace
def of(ctx) -> Program | None:
    """The program's record of ``ctx``'s traced window, read once from the
    window's ``.xplane.pb`` (the newest ``chipbench-trace-*`` directory,
    whose window span must be ``ctx.window``) and kept on ``ctx``; None
    where it cannot be found or holds no ``repro.run`` span."""
    if not hasattr(ctx, "program"):
        ctx.program = _find(ctx)
    prog = ctx.program
    return prog if prog is not None and prog.named("run") else None


def _find(ctx) -> Program | None:
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "chipbench-trace-*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        try:
            path = trace_mod.newest_xplane(d)
        except FileNotFoundError:
            continue
        spans, harness = host_spans(path)
        windows = [(s, e) for n, s, e in harness
                   if n == trace_mod.SPAN_PREFIX + "window"]
        if windows[:1] != [tuple(ctx.window)]:
            return None
        return build(ctx.trace, op_metadata(path), spans)
    return None


# ------------------------------------------------------------------ readers
def span_ms(ctx, name: str) -> float | None:
    """Total length of the ``repro.<name>`` spans inside each call,
    averaged over calls, in ms."""
    prog = of(ctx)
    if prog is None:
        return None
    per_call = [sum(e - s for _, s, e, _, _ in prog.named(name, lo, hi))
                for lo, hi in ctx.calls]
    return float(np.mean(per_call)) / 1e6


def span_arg(ctx, name: str, arg: str) -> float | None:
    """Sum of argument ``arg`` over the ``repro.<name>`` spans inside each
    call, averaged over calls."""
    prog = of(ctx)
    if prog is None:
        return None
    per_call = [sum(float(a.get(arg, 0)) for _, _, _, a, _
                    in prog.named(name, lo, hi)) for lo, hi in ctx.calls]
    return float(np.mean(per_call))


def scope_ms(ctx, scope: str, kinds=("xla",)) -> float | None:
    """Device time of the ops of ``kinds`` under ``scope`` inside each
    call (union per device, mean over devices), averaged over calls, in
    ms; None where no op of the window carries an ``aif.*`` scope."""
    prog = of(ctx)
    if prog is None or not any(
            SCOPE_PREFIX in p for paths in prog.scope for p in paths):
        return None
    masks = [np.fromiter((scope in p.split("/") for p in paths), bool,
                         len(paths)) for paths in prog.scope]
    under = trace_mod.Trace(ops=[{k: v[m] for k, v in o.items()}
                                 for o, m in zip(ctx.trace.ops, masks)])
    return dataclasses.replace(ctx, trace=under).per_call_ms(kinds=kinds)


def idle_gaps(ctx, top: int = 10) -> list | None:
    """The longest idle gaps of device 0 in the window, each named by the
    innermost ``repro.run*`` span that holds its midpoint (the harness's
    ``api.run`` or ``between calls`` where none does)."""
    prog = of(ctx)
    if prog is None or not ctx.trace.n_devices:
        return None
    runs = [sp for sp in prog.spans if sp[0].startswith(SPAN_PREFIX + "run")]

    def where(a, b):
        mid = 0.5 * (a + b)
        inner = [sp for sp in runs if sp[1] <= mid <= sp[2]]
        if inner:
            return min(inner, key=lambda sp: sp[2] - sp[1])[0]
        return ("api.run" if any(s <= mid <= e for s, e in ctx.calls)
                else "between calls")

    lo, hi = ctx.window
    gaps = sorted(ctx.trace.idle_gaps(0, lo, hi), key=lambda g: g[0] - g[1])
    return [[where(a, b), (b - a) / 1e9] for a, b in gaps[:top]]
