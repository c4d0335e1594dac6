"""Trace reduction: op intervals, kinds, spans, and every per-layer reader
against a window traced on a TPU v5e.

``data/paper-3tier.burst.r2048.call.npz`` holds the raw ``XLA Ops`` events
of device 0 (containers included) and the harness spans of the first
``api.run`` call of a ``--trace 1`` run of the paper-3tier cell at 2,048
cells, recorded on the chip with ``run.py --keep-trace``; times are ns from
the window's start.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import layers, registry, work
from chipbench import trace as trace_mod

DATA = Path(__file__).parent / "data" / "paper-3tier.burst.r2048.call.npz"
CELL = "paper-3tier.burst.r2048"
TRACED_CELLS = 2048          # the fleet the recorded call ran


def _trace(rows, spans=()):
    return trace_mod.build([(0, s, e, name) for s, e, name in rows], spans)


def test_busy_is_the_union_of_overlapping_ops():
    tr = _trace([(0, 10, "%a = f32[] fusion()"),
                 (5, 20, '%b = f32[] custom-call(), '
                         'custom_call_target="tpu_custom_call"'),
                 (30, 40, "%c = f32[] fusion()")])
    assert tr.busy_ns(0, 0, 100) == 30
    assert tr.busy_ns(0, 8, 35) == 17              # clipped to the window
    assert tr.busy_ns(0, 0, 100, kinds=("xla",)) == 20
    assert tr.idle_gaps(0, 0, 50) == [(20, 30), (40, 50)]


@pytest.mark.parametrize("name,kind", [
    ("%fusion.12 = f32[2048,243]{1,0} fusion(f32[2048,243]{1,0} %p), "
     "kind=kLoop", "xla"),
    ('%closed_call.37 = (f32[10,2048,243]{2,1,0}, s32[10,2048,1]{2,1,0}) '
     'custom-call(s32[1,1]{1,0} %bi), custom_call_target="tpu_custom_call"',
     "pallas"),
    ('%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %x), '
     'custom_call_target="Sharding"', "xla"),
    ("%all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %x), to_apply=%add",
     "collective"),
    ("%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
     "condition=%c, body=%b", "container"),
    ("%copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %x)",
     "xla"),
])
def test_op_kind(name, kind):
    assert trace_mod.op_kind(name) == kind


def test_load_reads_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("chipbench.api_run"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_mod.load(trace_mod.newest_xplane(str(tmp_path)))
    assert len(tr.spans_named("window")) == 1
    calls = tr.spans_named("api_run")
    assert len(calls) == 2 and all(e > s for s, e in calls)
    w0, w1 = tr.spans_named("window")[0]
    assert w0 <= calls[0][0] and calls[1][1] <= w1
    assert tr.n_devices == 0                       # no TPU plane on the CPU


# ------------------------------------------------ readers on a chip trace
@pytest.fixture(scope="module")
def chip_ctx():
    d = np.load(DATA)
    names = d["names"]
    events = [(0, s, s + dur, str(names[i])) for s, dur, i
              in zip(d["start_ns"], d["duration_ns"], d["name_ix"])]
    tr = trace_mod.build(events, json.loads(str(d["spans"])))
    cell = registry.cell(CELL)
    peak = registry.peaks()["devices"]["TPU v5 lite"]
    return layers.Context(trace=tr, cell=cell, n_cells=TRACED_CELLS,
                          n_windows=cell["traffic"]["n_windows"],
                          calls=tr.spans_named("api_run"),
                          window=tr.spans_named("window")[0], peak=peak)


def _plain_union(intervals):
    """Busy time by a sweep over sorted interval ends."""
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def test_chip_trace_drops_containers_and_finds_the_kernel(chip_ctx):
    o = chip_ctx.trace.ops[0]
    kinds = set(o["kind"])
    assert kinds == {"xla", "pallas"}
    assert not any(trace_mod.hlo_opcode(n) in trace_mod.CONTAINERS
                   for n in o["name"])


def test_idle_reader_matches_a_plain_sweep(chip_ctx):
    lo, hi = chip_ctx.window
    o = chip_ctx.trace.ops[0]
    busy = _plain_union([(max(s, lo), min(e, hi))
                         for s, e in zip(o["start"], o["end"])
                         if e > lo and s < hi])
    got = registry.metric_reader("device_idle_pct")(chip_ctx)
    assert got == pytest.approx(100.0 * (1 - busy / (hi - lo)), rel=1e-9)
    assert 0.0 < got < 100.0
    assert chip_ctx.busy_s == pytest.approx(busy / 1e9, rel=1e-9)


def test_kernel_and_xla_readers_split_the_call(chip_ctx):
    (s, e), = chip_ctx.calls
    o = chip_ctx.trace.ops[0]
    inside = (o["end"] > s) & (o["start"] < e)
    pallas = inside & (o["kind"] == "pallas")
    xla = inside & (o["kind"] == "xla")

    def clipped(sel):
        return zip(np.maximum(o["start"][sel], s), np.minimum(o["end"][sel], e))
    k_ms = registry.metric_reader("mega_kernel_ms")(chip_ctx)
    x_ms = registry.metric_reader("mega_xla_ms")(chip_ctx)
    assert k_ms == pytest.approx(_plain_union(clipped(pallas)) / 1e6, rel=1e-9)
    assert x_ms == pytest.approx(_plain_union(clipped(xla)) / 1e6, rel=1e-9)
    host = registry.metric_reader("api_host_ms")(chip_ctx)
    busy = _plain_union(clipped(inside)) / 1e6
    assert host == pytest.approx((e - s) / 1e6 - busy, rel=1e-9)
    assert k_ms > 0 and x_ms > 0 and host > 0


def test_roofline_reader_is_the_work_count_over_kernel_time(chip_ctx):
    pct = registry.metric_reader("mega_window_roofline_pct")(chip_ctx)
    ms = registry.metric_reader("mega_kernel_ms")(chip_ctx)
    w = work.window_work(chip_ctx.cell["config"], chip_ctx.n_cells,
                         chip_ctx.n_windows)
    least = max(w["bytes"] / chip_ctx.peak["hbm_bytes_per_s"],
                w["flops"] / chip_ctx.peak["bf16_flops_per_s"])
    assert pct == pytest.approx(100.0 * least / (ms / 1e3), rel=1e-9)
    assert 0.0 < pct <= 100.0
    assert "mega_window_roofline_pct bound=hbm" in chip_ctx.notes


def test_breakdown_lists_ops_and_gaps(chip_ctx):
    b = layers.breakdown(chip_ctx)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
    gaps = [t for _, t in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert {w for w, _ in b["idle_gaps"]} <= {"api.run", "between calls"}


@pytest.mark.parametrize("reader", ["mega_kernel_ms",
                                    "mega_window_roofline_pct"])
def test_kernel_readers_return_nothing_without_a_kernel(chip_ctx, reader):
    o = chip_ctx.trace.ops[0]
    keep = o["kind"] != "pallas"
    no_kernel = trace_mod.Trace(ops=[{k: v[keep] for k, v in o.items()}],
                                spans=chip_ctx.trace.spans)
    ctx = layers.Context(trace=no_kernel, cell=chip_ctx.cell,
                         n_cells=chip_ctx.n_cells,
                         n_windows=chip_ctx.n_windows, calls=chip_ctx.calls,
                         window=chip_ctx.window, peak=chip_ctx.peak)
    assert registry.metric_reader(reader)(ctx) is None
