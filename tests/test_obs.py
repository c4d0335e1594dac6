"""The run's own spans and counters (:mod:`repro.obs`) under the profiler.

A ``repro.api.run`` call is one ``repro.run`` host span whose children name
its host steps; every span carries the call's ``run`` id.  Counters advance
per call without waiting for the device, and a warm call traces nothing.
"""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro import api, obs

STEPS = ("run.world", "run.init", "run.dispatch", "run.wait",
         "run.summarize", "run.counts")


def _traced(tmp_path, experiments):
    """Run each experiment under one profiler trace; return the ``repro.*``
    spans as (name, start, end, args) sorted by start, and the counter
    deltas of each call."""
    deltas = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for e in experiments:
            before = obs.counters()
            api.run(e)
            after = obs.counters()
            deltas.append({k: after.get(k, 0) - before.get(k, 0)
                           for k in after})
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.name[len(obs.PREFIX):], ev.start_ns,
              ev.start_ns + ev.duration_ns, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith(obs.PREFIX)]
    return sorted(spans, key=lambda sp: sp[1]), deltas


def _parent(spans, child):
    """The shortest other span that encloses ``child``."""
    outer = [sp for sp in spans if sp is not child
             and sp[1] <= child[1] and child[2] <= sp[2]]
    return min(outer, key=lambda sp: sp[2] - sp[1]) if outer else None


def test_mega_run_span_tree_and_counters(tmp_path):
    # start from empty jit caches: a long-lived test process may hold its
    # bounded eager-op caches full of earlier tests' entries
    jax.clear_caches()
    r, t = 8, 40
    e = api.Experiment(router="aif", mega=True, n_cells=r, n_windows=t,
                       seed=5)
    spans, deltas = _traced(tmp_path, [e, e])
    runs = [sp for sp in spans if sp[0] == "run"]
    assert len(runs) == 2
    for run in runs:
        args = run[3]
        assert args["n_cells"] == r and args["n_windows"] == t
        assert args["path"] == "mega.xla"
        kids = [sp for sp in spans if sp is not run
                and run[1] <= sp[1] and sp[2] <= run[2]]
        assert {sp[0] for sp in kids} == set(STEPS) | {
            "run.summarize.fetch", "run.summarize.reduce"}
        assert all(sp[3]["run"] == args["run"] for sp in kids)
        for sp in kids:
            want = "run.summarize" if sp[0].startswith(
                "run.summarize.") else "run"
            assert _parent(spans, sp)[0] == want
        # set-up, launch, wait and summary follow one another
        order = [sp[0] for sp in kids if _parent(spans, sp) is run]
        assert order.index("run.world") < order.index("run.init")
        assert (order.index("run.dispatch") < order.index("run.wait")
                < order.index("run.summarize") < order.index("run.counts"))
        dispatch, = [sp for sp in kids if sp[0] == "run.dispatch"]
        assert dispatch[3]["launches"] == 1
        fetch, = [sp for sp in kids if sp[0] == "run.summarize.fetch"]
        # (T, R, K) latencies, p95s and completions are among the copies
        assert fetch[3]["bytes"] >= 3 * t * r * 3 * 4
        counts, = [sp for sp in kids if sp[0] == "run.counts"]
        assert counts[2] - counts[1] < 1e6            # a marker, < 1 ms
    assert runs[0][3]["run"] + 1 == runs[1][3]["run"]
    for d in deltas:
        assert d["runs"] == 1 and d["cell_windows"] == r * t
        assert d["launches"] == 1 and d["watchdog_events"] == 0
    assert deltas[1]["traces"] == 0 and deltas[1]["compiles"] == 0
    marks = [sp[3] for sp in spans if sp[0] == "run.counts"]
    assert [m["traces"] for m in marks] == [d["traces"] for d in deltas]
    assert [m["cell_windows"] for m in marks] == [r * t] * 2


@pytest.mark.parametrize("kw,path", [
    (dict(router="least_loaded"), "tick"),
    (dict(router="least_loaded", shard="auto"), "sharded"),
    (dict(router="least_loaded", checkpoint_every=10), "tick"),
], ids=["tick", "sharded", "chunked"])
def test_every_path_names_its_steps(tmp_path, kw, path):
    if "checkpoint_every" in kw:
        kw = dict(kw, checkpoint_dir=str(tmp_path / "ckpt"))
    e = api.Experiment(n_cells=4, n_windows=20, seed=1, **kw)
    spans, deltas = _traced(tmp_path / "trace", [e])
    run, = [sp for sp in spans if sp[0] == "run"]
    assert run[3]["path"] == path
    assert set(STEPS) <= {sp[0] for sp in spans}
    assert deltas[0]["launches"] == sum(
        sp[3]["launches"] for sp in spans if sp[0] == "run.dispatch")
    assert deltas[0]["launches"] >= (2 if "checkpoint_every" in kw else 1)


def test_chaos_control_run_nests_as_a_child_run(tmp_path):
    e = api.Experiment(router="least_loaded", scenario="zone-outage",
                       n_cells=3, n_windows=30, seed=2)
    spans, deltas = _traced(tmp_path, [e])
    outer, inner = sorted((sp for sp in spans if sp[0] == "run"),
                          key=lambda sp: sp[1])
    assert _parent(spans, inner) is outer
    assert inner[3]["run"] == outer[3]["run"] + 1
    assert deltas[0]["runs"] == 2
    assert deltas[0]["cell_windows"] == 2 * 3 * 30


def test_spans_outside_a_run_carry_no_run_id():
    with obs.span("probe", k=1) as sp:
        assert isinstance(sp, jax.profiler.TraceAnnotation)
    with obs.run_span(n_cells=1) as run_sp:
        assert obs._run_id.get() == obs.counters()["runs"]
        run_sp.set_metadata(path="tick")
    assert obs._run_id.get() is None
