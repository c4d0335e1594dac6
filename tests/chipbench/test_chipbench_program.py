"""The program's own record in a trace (``chipbench/program.py``) and the
readers built on it, against a call traced on a TPU v5e.

``data/paper-3tier.burst.r2048.spans.npz`` holds the raw ``XLA Ops`` events
of device 0 (containers included) of one ``api.run`` call of a ``--trace
1`` run of the paper-3tier cell at 2,048 cells, recorded on the chip with
``run.py --seconds 0 --keep-trace``; times are ns from the window's start.
Beside them: each op name's ``tf_op`` scope path and ``program_id`` from
its event metadata, the harness spans, and the program's ``repro.*`` spans
with their arguments.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import layers, program, registry
from chipbench import trace as trace_mod

DATA = Path(__file__).parent / "data" / "paper-3tier.burst.r2048.spans.npz"
CELL = "paper-3tier.burst.r2048"
READERS = ("run_init_ms", "run_summarize_ms", "host_fetch_mb",
           "mega_slow_ms", "mega_window_xla_ms", "window_compiles")


def _context(tr, prog=None):
    cell = registry.cell(CELL)
    ctx = layers.Context(trace=tr, cell=cell, n_cells=2048,
                         n_windows=cell["traffic"]["n_windows"],
                         calls=tr.spans_named("api_run"),
                         window=tr.spans_named("window")[0],
                         peak=registry.peaks()["devices"]["TPU v5 lite"])
    if prog is not None:
        ctx.program = prog
    return ctx


@pytest.fixture(scope="module")
def fixture():
    d = np.load(DATA)
    names = [str(n) for n in d["names"]]
    events = [(0, s, s + dur, names[i]) for s, dur, i
              in zip(d["start_ns"], d["duration_ns"], d["name_ix"])]
    meta = {0: {n: (str(sc), int(pid)) for n, sc, pid
                in zip(names, d["scope"], d["program"])}}
    spans = [(n, s, e, a, ln) for n, s, e, a, ln
             in json.loads(str(d["program_spans"]))]
    tr = trace_mod.build(events, json.loads(str(d["spans"])))
    return tr, meta, spans


@pytest.fixture
def ctx(fixture):
    tr, meta, spans = fixture
    return _context(tr, program.build(tr, meta, spans))


def _plain_union(intervals):
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _scope_of_each_op(tr, meta):
    """Each op's scope path by a plain walk: its own ``tf_op``, else the
    path of the op before it in the same program."""
    last, out = {}, []
    for name in tr.ops[0]["name"]:
        scope, pid = meta[0].get(name, ("", 0))
        if scope:
            last[pid] = scope
        out.append(scope or last.get(pid, ""))
    return out


def _xla_ms_where(ctx, keep):
    """Device time in the call of the XLA ops for which ``keep(scope path,
    op name)`` holds, by a plain sweep."""
    (lo, hi), = ctx.calls
    o = ctx.trace.ops[0]
    return _plain_union(
        (max(s, lo), min(e, hi)) for s, e, k, p, n
        in zip(o["start"], o["end"], o["kind"], ctx.program.scope[0],
               o["name"])
        if k == "xla" and e > lo and s < hi and keep(p, n)) / 1e6


def test_build_inherits_scopes_within_a_program(fixture, ctx):
    tr, meta, _ = fixture
    assert list(ctx.program.scope[0]) == _scope_of_each_op(tr, meta)
    o = tr.ops[0]
    kernel = [p for p, k in zip(ctx.program.scope[0], o["kind"])
              if k == "pallas"]
    assert kernel and all("/aif.window/aif_mega_window/" in p
                          for p in kernel)


@pytest.mark.parametrize("scope,reader", [("aif.slow_step", "mega_slow_ms"),
                                          ("aif.window",
                                           "mega_window_xla_ms")])
def test_scope_readers_match_a_plain_sweep(ctx, scope, reader):
    got = registry.metric_reader(reader)(ctx)
    want = _xla_ms_where(ctx, lambda p, _: scope in p.split("/"))
    assert got == pytest.approx(want, rel=1e-9) and got > 0


def test_scopes_cover_the_xla_layer(fixture, ctx):
    """The slow boundary, the window glue and the call's state set-up (ops
    under ``aif.init``, or of the eager programs beside the launched one)
    hold at least 95% of the XLA time the harness measures per call."""
    _, meta, _ = fixture
    slow = registry.metric_reader("mega_slow_ms")(ctx)
    window = registry.metric_reader("mega_window_xla_ms")(ctx)
    launched = {pid for scope, pid in meta[0].values()
                if "aif_mega_window" in scope.split("/")}
    init = _xla_ms_where(ctx, lambda p, n: "aif.init" in p.split("/")
                         or meta[0][n][1] not in launched)
    xla = registry.metric_reader("mega_xla_ms")(ctx)
    assert len(launched) == 1 and 0 < init < 0.05 * xla
    assert (slow + window + init) / xla >= 0.95


def test_span_readers_read_the_call_spans(ctx):
    (lo, hi), = ctx.calls
    spans = ctx.program.spans

    def total(name):
        return sum(e - s for n, s, e, _, _ in spans
                   if n == "repro." + name and lo <= s and e <= hi) / 1e6
    assert registry.metric_reader("run_init_ms")(ctx) == pytest.approx(
        total("run.init"), rel=1e-12)
    assert registry.metric_reader("run_summarize_ms")(ctx) == pytest.approx(
        total("run.summarize"), rel=1e-12)
    fetch, = [a["bytes"] for n, *_, a, _ in spans
              if n == "repro.run.summarize.fetch"]
    assert registry.metric_reader("host_fetch_mb")(ctx) == fetch / 1e6
    assert registry.metric_reader("window_compiles")(ctx) == 0.0


def test_run_children_cover_the_call(ctx):
    run, = ctx.program.named("run")
    kids = [sp for sp in ctx.program.spans if sp is not run
            and sp[0].count(".") == 2 and run[1] <= sp[1] <= sp[2] <= run[2]]
    assert {sp[0] for sp in kids} >= {
        "repro.run.world", "repro.run.init", "repro.run.dispatch",
        "repro.run.wait", "repro.run.summarize", "repro.run.counts"}
    covered = _plain_union((sp[1], sp[2]) for sp in kids)
    assert covered / (run[2] - run[1]) >= 0.95
    assert all(sp[3]["run"] == run[3]["run"] for sp in kids)
    assert run[3]["path"] == "mega.pallas"


def test_idle_gaps_are_named_by_program_spans(ctx):
    gaps = program.idle_gaps(ctx)
    assert 0 < len(gaps) <= 10
    assert all(name.startswith("repro.run") for name, _ in gaps)
    assert [t for _, t in gaps] == sorted((t for _, t in gaps),
                                          reverse=True)
    # the summary's host reduction is the longest gap of a call
    assert gaps[0][0] == "repro.run.summarize.reduce"
    registry.metric_reader("run_summarize_ms")(ctx)
    note, = [n for n in ctx.notes if n.startswith("idle_gaps ")]
    assert note.split()[1].startswith("repro.run.summarize.reduce=")


@pytest.mark.parametrize("reader", READERS)
def test_readers_return_nothing_without_program_spans(fixture, reader):
    """A build that records no spans or scopes (as before they existed)
    gives the readers nothing to read."""
    tr, meta, _ = fixture
    assert registry.metric_reader(reader)(
        _context(tr, program.build(tr, {}, []))) is None


def test_scope_readers_need_scopes(fixture):
    tr, _, spans = fixture
    ctx = _context(tr, program.build(tr, {}, spans))
    assert registry.metric_reader("mega_slow_ms")(ctx) is None
    assert registry.metric_reader("run_init_ms")(ctx) > 0


# ----------------------------------------------------------- reading a file
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, msg):
    return _field(1, key) + _field(2, msg)


def test_op_metadata_walks_the_wire_format(tmp_path):
    """A hand-encoded XSpace: the ``tf_op`` stat as a string, as a
    reference to an interned string, and a plane of another unit."""
    stat_md = [(1, "tf_op"), (2, "program_id"), (3, "jit(f)/aif.init/x:")]
    ops = [(10, "%a = f32[] add()", [_field(1, 1) + _field(5, "jit(f)/y:"),
                                     _field(1, 2) + _field(3, 7)]),
           (11, "%b = f32[] copy()", [_field(1, 2) + _field(3, 7)]),
           (12, "%c = f32[] neg()", [_field(1, 1) + _field(7, 3),
                                     _field(1, 2) + _field(4, 9)])]

    def plane(name):
        body = _field(2, name)
        body += b"".join(_field(5, _entry(i, _field(1, i) + _field(2, n)))
                         for i, n in stat_md)
        body += b"".join(
            _field(4, _entry(i, _field(1, i) + _field(2, n)
                             + b"".join(_field(5, st) for st in stats)))
            for i, n, stats in ops)
        return _field(1, body)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(plane("/device:TPU:3") + plane("/device:TPU:0 SC")
                     + plane("/host:CPU"))
    assert program.op_metadata(str(path)) == {3: {
        "%a = f32[] add()": ("jit(f)/y:", 7),
        "%b = f32[] copy()": ("", 7),
        "%c = f32[] neg()": ("jit(f)/aif.init/x:", 9)}}


def test_of_finds_the_window_trace_and_its_spans(tmp_path, monkeypatch):
    """On the CPU: the window's trace is found in the temporary directory
    the harness traces into, its ``repro.*`` spans keep their arguments,
    and a trace of another window is not taken for it."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    trace_dir = tmp_path / "chipbench-trace-a"
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.api_run"):
            with obs.run_span(n_cells=32) as sp:
                sp.set_metadata(path="tick")
                with obs.span("run.summarize.fetch", bytes=4096):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_mod.load(trace_mod.newest_xplane(str(trace_dir)))
    ctx = _context(tr)
    prog = program.of(ctx)
    assert prog is ctx.program
    run, = prog.named("run")
    assert run[3]["path"] == "tick" and run[3]["n_cells"] == 32
    fetch, = prog.named("run.summarize.fetch", *ctx.calls[0])
    assert fetch[3] == {"run": run[3]["run"], "bytes": 4096}
    assert registry.metric_reader("host_fetch_mb")(ctx) == 4096 / 1e6
    other = _context(tr)
    other.window = (ctx.window[0] - 1.0, ctx.window[1])
    assert program.of(other) is None
