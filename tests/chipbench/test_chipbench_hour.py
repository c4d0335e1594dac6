"""The hour-long deployment (``paper-3tier-1h``) is the paper's router and
traffic, changed only in fleet size and horizon; its work count and its two
per-layer readers against counts made by hand."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import layers, program, registry, work
from chipbench import trace as trace_mod

CELL = "paper-3tier-1h.burst.t3600"
# one api.run call of a --trace 1 run of paper-3tier.burst.r2048, recorded
# on a TPU v5e (see test_chipbench_program.py)
RECORDED = "paper-3tier.burst.r2048"
DATA = Path(__file__).parent / "data" / f"{RECORDED}.spans.npz"


def test_hour_cell_is_the_paper_deployment_run_for_an_hour():
    cell = registry.cell(CELL)
    cfg, base = cell["config"], registry.config("paper-3tier")
    own = {"name", "source", "n_cells", "deployment", "assumed"}
    assert {k: v for k, v in cfg.items() if k not in own} == {
        k: v for k, v in base.items() if k not in own}
    assert cfg["reduced"] == ["n_cells"] and cfg["n_cells"] == 256
    assert cfg["assumed"]["horizon_s"] == 3600
    mix, paper = cell["traffic"], registry.traffic("paper-burst")
    assert mix["n_windows"] == 3600
    own = {"name", "source", "n_windows"}
    assert {k: v for k, v in mix.items() if k not in own} == {
        k: v for k, v in paper.items() if k not in own}
    assert mix["n_windows"] * mix["window_s"] == cfg["assumed"]["horizon_s"]
    assert cell["engine"] == {"mega": True, "use_pallas": True,
                              "mega_slot_dtype": "float32", "shard": None}
    assert cell["sample_cells"] == 16


def test_hour_horizon_work_matches_hand_count():
    """The paper's widths over T=3600: S=243, A=20, K=3, M=4, max_bins=3,
    so P=16; W=10 ticks a window, dwell 5, so 2 selecting ticks.

    slot 2·243 + 20 + 16 + 2 = 524 floats = 2,096 B; cache 20·243 + 16·243
    + 16 + 4·3·243 = 11,680 floats = 46,720 B; carries (243+5) + (27+12+4)
    + (8+9) = 308 floats, twice = 2,464 B; per tick in 1+3+6+20 = 30 floats
    = 120 B, out (486+8+2) + (12+24+5) = 537 floats = 2,148 B.  A window
    at t0 moves 2,096·t0 + 46,720 + 2,464 + 10·2,268 = 2,096·t0 + 71,864 B
    and does 10·4·243·t0 + 2·(2·20·260·t0 + 2·20·16·243) = 30,520·t0 +
    311,040 FLOP.  Over t0 = 0, 10, ..., 3590, Σt0 = 10·359·360/2 =
    646,200."""
    cfg = registry.config("paper-3tier-1h")
    w = work.window_work(cfg, n_cells=256, n_windows=3600)
    assert w["slot_bytes"] == 2_096
    assert w["bytes"] == 256 * (2_096 * 646_200 + 360 * 71_864)
    assert w["flops"] == 256 * (30_520 * 646_200 + 360 * 311_040)


@pytest.fixture(scope="module")
def recorded():
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


def _context(tr, meta, spans):
    cell = registry.cell(RECORDED)
    ctx = layers.Context(trace=tr, cell=cell, n_cells=2048,
                         n_windows=cell["traffic"]["n_windows"],
                         calls=tr.spans_named("api_run"),
                         window=tr.spans_named("window")[0],
                         peak=registry.peaks()["devices"]["TPU v5 lite"])
    ctx.program = program.build(tr, meta, spans)
    return ctx


def test_tape_stream_reader_is_the_counter_over_kernel_time(recorded):
    """``tape_stream_gbps`` divides the ``tape_bytes`` the call's dispatch
    spans carry by the kernel's device time; the recorded build predates
    the counter, so on it the reader has nothing to read."""
    tr, meta, spans = recorded
    assert registry.metric_reader("tape_stream_gbps")(
        _context(tr, meta, spans)) is None
    counted = [(n, s, e, dict(a, tape_bytes=123_000_000_000)
                if n == "repro.run.dispatch" else a, ln)
               for n, s, e, a, ln in spans]
    ctx = _context(tr, meta, counted)
    (lo, hi), = ctx.calls
    n = len(ctx.program.named("run.dispatch", lo, hi))
    ms = registry.metric_reader("mega_kernel_ms")(ctx)
    got = registry.metric_reader("tape_stream_gbps")(ctx)
    assert n >= 1
    assert got == pytest.approx(123.0 * n / (ms / 1e3), rel=1e-12)


def test_stream_roofline_reads_the_kernel_roofline(recorded):
    """``stream_roofline_pct`` is the same work count over the same kernel
    time as ``mega_window_roofline_pct``."""
    ctx = _context(*recorded)
    got = registry.metric_reader("stream_roofline_pct")(ctx)
    assert got == registry.metric_reader("mega_window_roofline_pct")(ctx)
    assert 0.0 < got <= 100.0
    assert "stream_roofline_pct bound=hbm" in ctx.notes
