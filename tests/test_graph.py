"""Networked-continuum coverage: FleetGraph spec semantics, spillover
conservation, the empty-edge bit-identity contract, graph x chaos shedding,
1-device sharded parity, mega-engine parity, the neighbor-pressure modality
and the nearest-neighbor offloader baseline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.core import graph as graph_mod
from repro.core.graph import FleetGraph
from repro.core.topology import default_topology
from repro.envsim import SimConfig, batched, scenarios


# ------------------------------------------------------------- graph spec
def test_ring_preset_shape():
    g = graph_mod.ring(8)
    assert g.n_cells == 8 and g.n_edges == 16          # bidirectional ring
    srcs = {e[0] for e in g.edges}
    assert srcs == set(range(8))                        # every cell exports
    gd = g.device_data()
    assert gd.src.shape == (16,) and gd.has_out.shape == (8,)
    assert np.all(np.asarray(gd.has_out) == 1.0)
    # 1/out_degree split: ring cells have out-degree 2
    np.testing.assert_allclose(np.asarray(gd.share), 0.5)


def test_grid_and_hier_presets():
    g = graph_mod.grid(9)                               # 3x3 grid
    assert g.n_cells == 9
    # interior cell 4 has 4 neighbors, corners have 2
    deg = np.zeros(9, int)
    for s, _ in g.edges:
        deg[s] += 1
    assert deg[4] == 4 and deg[0] == 2
    h = graph_mod.hier(8, cluster=4)
    assert h.n_cells == 8
    # leaf<->head star edges plus the head ring
    assert any(e == (1, 0) for e in h.edges)            # leaf -> head uplink


def test_graph_validation_and_hashability():
    with pytest.raises(ValueError, match="edge"):
        FleetGraph(n_cells=4, edges=((0, 9),), hop_s=(0.1,))
    with pytest.raises(ValueError, match="self"):
        FleetGraph(n_cells=4, edges=((1, 1),), hop_s=(0.1,))
    with pytest.raises(ValueError, match="hop"):
        FleetGraph(n_cells=4, edges=((0, 1),), hop_s=())
    g = graph_mod.ring(6)
    assert hash(g) == hash(graph_mod.ring(6))           # static jit arg


def test_validate_true_rows_names_pad_policy():
    g = graph_mod.ring(8)
    with pytest.raises(ValueError, match="pad"):
        g.validate_true_rows(6)
    g.validate_true_rows(8)                             # exact fit is fine
    # padded worlds: edges stay within the true rows, r_pad only grows
    assert g.device_data(r_pad=12).has_out.shape == (12,)
    with pytest.raises(ValueError, match="r_pad"):
        g.device_data(r_pad=4)


def test_resolve_graph_semantics():
    r = 6
    assert graph_mod.resolve_graph(None, r) is None
    assert graph_mod.resolve_graph("none", r) is None
    # empty-edge graphs resolve to None: the exact pre-graph program
    assert graph_mod.resolve_graph(FleetGraph(n_cells=r), r) is None
    g = graph_mod.resolve_graph("ring", r)
    assert isinstance(g, FleetGraph) and g.n_cells == r
    # graph scenarios auto-attach their preset; "none" still wins
    auto = graph_mod.resolve_graph(None, r, scenario="ring-spillover")
    assert auto is not None and auto.name == "ring"
    assert graph_mod.resolve_graph("none", r,
                                   scenario="ring-spillover") is None
    with pytest.raises(KeyError, match="graph preset"):
        graph_mod.resolve_graph("bogus", r)
    with pytest.raises(ValueError, match="true fleet size"):
        graph_mod.resolve_graph(graph_mod.ring(4), r)


def test_with_neighbor_modality_idempotent():
    topo = default_topology()
    t5 = graph_mod.with_neighbor_modality(topo)
    assert t5.modalities[-1] == "neighbor"
    assert t5.n_bins[-1] == graph_mod.NEIGHBOR_BINS
    assert graph_mod.with_neighbor_modality(t5) == t5


# ----------------------------------------------- engine: spillover physics
def _world(r, t, scenario, graph=None, seed=0):
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, t, seed=seed)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc, graph=graph)
    return params, env_step


def test_spillover_conserves_fleet_mass():
    """Fleet-global accounting closes under spillover: every offered unit
    ends as success, a failure bucket, or in-flight backlog."""
    r, t = 6, 40
    g = graph_mod.ring(r)
    params, env_step = _world(r, t, "ring-spillover", graph=g)
    router = api.LeastLoadedRouter(tiers=3, extra_modalities=1)
    _, est, trace = engine.rollout(
        router, router.init_carry(r),
        batched.init_fluid_state(params, n_modalities=5),
        env_step, t, jax.random.key(0))
    tot = lambda x: float(np.asarray(x, np.float64).sum())
    offered = tot(est.n_requests)
    accounted = (tot(est.n_success) + tot(est.err_timeout)
                 + tot(est.err_overflow) + tot(est.err_refused)
                 + tot(est.err_restart) + tot(est.backlog))
    np.testing.assert_allclose(accounted, offered, rtol=1e-5)
    # spillover actually moved mass in this scenario
    assert tot(trace.env.spill_admitted) > 0.0
    assert tot(trace.env.spill_out) >= tot(trace.env.spill_admitted)


def test_empty_edge_graph_is_pre_graph_program():
    """graph=FleetGraph(edges=()) resolves to None and the env adapter
    compiles the exact ungraphed program (same pytree, no spill fields)."""
    r, t = 4, 20
    _, step_none = _world(r, t, "flash-crowd", graph=None)
    g_empty = graph_mod.resolve_graph(FleetGraph(n_cells=r), r)
    _, step_empty = _world(r, t, "flash-crowd", graph=g_empty)
    assert not step_none.has_graph and not step_empty.has_graph
    assert step_none.n_obs_modalities == batched.N_OBS_MODALITIES
    router = api.LeastLoadedRouter(tiers=3)
    outs = []
    for step in (step_none, step_empty):
        _, est, trace = engine.rollout(
            router, router.init_carry(r),
            batched.init_fluid_state(_world(r, t, "flash-crowd")[0]),
            step, t, jax.random.key(0))
        assert trace.env.spill_admitted is None
        outs.append(est)
    for name, a, b in zip(outs[0]._fields, outs[0], outs[1]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_neighbor_modality_emitted():
    r, t = 6, 10
    params, env_step = _world(r, t, "ring-spillover",
                              graph=graph_mod.ring(r))
    assert env_step.has_graph and env_step.n_obs_modalities == 5
    est = batched.init_fluid_state(params, n_modalities=5)
    est, info = env_step(est, jnp.full((r, 3), 1 / 3), 0, jax.random.key(0))
    assert info.raw_obs.shape == (r, 5)
    assert info.obs_mask.shape == (r, 5)
    nbr = np.asarray(info.raw_obs[:, 4])
    assert np.all(nbr >= 0.0) and np.all(nbr <= 1e3)
    assert info.nbr_pressure is not None


# ------------------------------------------------- experiment-level checks
def _fleet_success(res):
    return (float(res.fluid.n_success.sum())
            / max(float(res.fluid.n_requests.sum()), 1.0))


def test_ring_spillover_beats_ungraphed():
    """Acceptance: a ring fleet under a localized flash crowd absorbs
    strictly more of the burst than the same run with no graph."""
    base = dict(router="least_loaded", scenario="ring-spillover",
                n_cells=8, n_windows=40)
    graphed = api.run(api.Experiment(**base))
    control = api.run(api.Experiment(**base, graph="none"))
    assert _fleet_success(graphed) > _fleet_success(control)
    assert graphed.offload_frac > 0.0
    assert control.offload_frac == 0.0
    assert graphed.success_pct <= 100.0


def test_graph_chaos_zone_outage_sheds_to_neighbors():
    """A zone outage on a ring sheds its refused load to live neighbors:
    the graphed run strictly beats the ungraphed one under the same
    fault schedule."""
    base = dict(router="least_loaded", scenario="zone-outage",
                n_cells=8, n_windows=40)
    graphed = api.run(api.Experiment(**base, graph="ring"))
    control = api.run(api.Experiment(**base))
    assert graphed.offload_frac > 0.0
    assert _fleet_success(graphed) > _fleet_success(control)


def test_sharded_single_device_graph_bit_identity():
    """The graphed engine composes with shard_map: on a 1-device mesh the
    all_gather exchange is the identity and the final env state matches
    the dense rollout bit-for-bit."""
    r, t = 6, 30
    g = graph_mod.ring(r)
    params, env_step = _world(r, t, "ring-spillover", graph=g)
    router = api.LeastLoadedRouter(tiers=3, extra_modalities=1)
    _, est_ref, _ = engine.rollout(
        router, router.init_carry(r),
        batched.init_fluid_state(params, n_modalities=5),
        env_step, t, jax.random.key(0))
    _, est_sh, stats = engine.sharded_rollout(
        router, batched.init_fluid_state(params, n_modalities=5),
        env_step, t, jax.random.key(0), shard=api.ShardSpec(devices=1),
        n_cells=r, reducer=api.FleetMetricsReducer(n_cells=r))
    for name, a, b in zip(est_ref._fields, est_ref, est_sh):
        if name == "util_scrape":
            # derived telemetry output: its final division fuses with the
            # trace-stacking consumer in the dense program and with the
            # reducer in the sharded one — 1 ulp of output rounding; every
            # dynamics/accounting field below is bitwise equal, so the
            # trajectories themselves never diverged
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    assert float(stats[3]) > 0.0                        # spill_sum psummed


def test_mega_engine_matches_per_tick_with_graph():
    """The mega path (XLA-oracle fallback under a graph) reproduces the
    per-tick engine's actions and final accounting on a graphed world."""
    base = dict(router="aif", scenario="ring-spillover",
                n_cells=6, n_windows=25)
    r1 = api.run(api.Experiment(**base))
    r2 = api.run(api.Experiment(**base, mega=True))
    np.testing.assert_array_equal(np.asarray(r1.trace.actions),
                                  np.asarray(r2.trace.actions))
    np.testing.assert_allclose(
        np.asarray(r1.fluid.n_success, np.float64),
        np.asarray(r2.fluid.n_success, np.float64), atol=1e-3)
    assert abs(r1.offload_frac - r2.offload_frac) < 1e-5


def test_aif_graph_run_learns_on_five_modalities():
    res = api.run(api.Experiment(router="aif", scenario="ring-spillover",
                                 n_cells=4, n_windows=20))
    assert res.trace.raw_obs.shape[-1] == 5
    assert np.all(np.isfinite(np.asarray(res.fluid.n_success)))


def test_graph_router_instance_mismatch_raises():
    with pytest.raises(ValueError, match="neighbor"):
        api.run(api.Experiment(
            router=api.AifRouter(), scenario="ring-spillover",
            n_cells=4, n_windows=10))


# --------------------------------------------------- nn_offload + Table 1
def test_min_response_router_greedy_and_failover():
    r = api.MinResponseRouter(service_s=(0.1, 0.2), cap_rps=(10.0, 20.0))
    obs = api.RouterObs(
        raw_obs=jnp.zeros((2, 4)),
        tier_utilization=jnp.zeros((2, 2)),
        tier_up=jnp.ones((2, 2)),
        tier_queue=jnp.asarray([[0.0, 0.0], [100.0, 0.0]]),
        t_idx=jnp.asarray(0, jnp.int32))
    _, w, info = r.step(r.init_carry(2), obs, jnp.ones((2, 4)),
                        jax.random.split(jax.random.key(0), 2))
    # idle fleet -> fastest service; deep queue on tier 0 -> tier 1
    assert np.asarray(info.action).tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0)
    # all-down cell falls back to uniform
    obs_dn = obs._replace(tier_up=jnp.zeros((2, 2)))
    _, w_dn, _ = r.step(r.init_carry(2), obs_dn, jnp.ones((2, 4)),
                        jax.random.split(jax.random.key(0), 2))
    np.testing.assert_allclose(np.asarray(w_dn), 0.5)
    with pytest.raises(ValueError, match="cap_rps"):
        api.MinResponseRouter(service_s=(0.1,), cap_rps=(1.0, 2.0))


def test_nn_offload_in_table1_grid():
    assert "nn_offload" in api.TABLE1_ROUTERS
    comp = api.compare([
        api.Experiment(router=r, scenario="ring-spillover",
                       n_cells=4, n_windows=20)
        for r in ("nn_offload", "least_loaded")])
    md = comp.markdown()
    assert "nn_offload" in md and "offload %" in md
    js = comp.to_json()
    row = js["ring-spillover"]["nn_offload"]
    assert row["offload_frac"] > 0.0


def test_offload_frac_reported_sharded():
    res = api.run(api.Experiment(router="least_loaded",
                                 scenario="ring-spillover", n_cells=6,
                                 n_windows=30,
                                 shard=api.ShardSpec(devices=1)))
    assert res.offload_frac > 0.0
    assert res.summary()["offload_frac"] == round(res.offload_frac, 4)
