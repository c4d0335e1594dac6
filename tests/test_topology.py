"""Topology-parameterized core: generated policy sets, golden rollout pins of
the default (paper) topology, K=5 end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, core
from repro.core import fleet, generative, policies
from repro.core.topology import (PolicySpec, Topology, default_topology,
                                 five_tier_topology, get_topology)
from repro.envsim import (SimConfig, batched, discretization_for, scenarios,
                          sim_config_for)

# The paper's hand-written 20-policy table (§4.1) — the pinned regression
# target for the K=3 generator.
PAPER_TABLE = np.asarray([
    (0.33, 0.33, 0.34),
    # 5 heavy-biased
    (0.15, 0.25, 0.60), (0.10, 0.20, 0.70), (0.05, 0.15, 0.80),
    (0.00, 0.10, 0.90), (0.00, 0.00, 1.00),
    # 4 medium-biased
    (0.20, 0.60, 0.20), (0.15, 0.70, 0.15), (0.10, 0.80, 0.10),
    (0.00, 1.00, 0.00),
    # 4 light-biased
    (0.60, 0.25, 0.15), (0.70, 0.20, 0.10), (0.80, 0.10, 0.10),
    (1.00, 0.00, 0.00),
    # 6 adaptive / exploratory
    (0.45, 0.45, 0.10), (0.45, 0.10, 0.45), (0.10, 0.45, 0.45),
    (0.50, 0.25, 0.25), (0.25, 0.50, 0.25), (0.25, 0.25, 0.50),
], dtype=np.float32)


def _topo_k2() -> Topology:
    return Topology(tier_names=("edge", "cloud"),
                    tier_classes=("edge-light", "server"))


# ------------------------------------------------------------ policy generator
def test_generated_k3_table_is_paper_table_bitwise():
    """The default topology's generated policy set == the paper's 20 rows."""
    gen = policies.generate_policy_table(default_topology())
    assert gen.dtype == np.float32 and gen.shape == (20, 3)
    np.testing.assert_array_equal(gen, PAPER_TABLE)


@pytest.mark.parametrize("topo,expect_a", [
    (_topo_k2(), 10),
    (default_topology(), 20),
    (five_tier_topology(), 37),
])
def test_generated_tables_are_valid_simplex_points(topo, expect_a):
    t = policies.generate_policy_table(topo)
    assert t.shape == (expect_a, topo.n_tiers)
    np.testing.assert_allclose(t.sum(-1), 1.0, atol=1e-5)
    assert (t >= 0).all()
    # balanced row first; no duplicate rows
    np.testing.assert_allclose(
        t[policies.BALANCED_ACTION], policies.balanced_weights(topo.n_tiers),
        atol=1e-6)
    for i in range(len(t)):
        for j in range(i + 1, len(t)):
            assert not np.allclose(t[i], t[j], atol=1e-6), (i, j)


def test_lattice_family_adds_simplex_points():
    topo = Topology(policy_spec=PolicySpec(lattice_resolution=2))
    t = policies.generate_policy_table(topo)
    # resolution-2 lattice on K=3 adds e.g. (0.5, 0.5, 0.0)
    assert any(np.allclose(row, [0.5, 0.5, 0.0]) for row in t)


def test_topology_registry_and_validation():
    assert get_topology("paper-3tier") is default_topology()
    with pytest.raises(KeyError):
        get_topology("nope")
    with pytest.raises(ValueError):
        Topology(util_edges=(0.5,))          # needs n_levels-1 edges
    with pytest.raises(ValueError):
        Topology(tier_classes=("server",))   # length mismatch


# -------------------------------------------------- golden bit-compatibility
def _dwell_blocks(blocks):
    """Expand one action triple per 5-tick dwell block to the (T, R) trace."""
    return [list(b) for b in blocks for _ in range(5)]


# Pins per PRNG implementation: (actions, final n_success).  The legacy pin
# is the pre-refactor ``fleet_rollout`` output from commit 0af21fc.  jax 0.9
# made ``jax_threefry_partitionable=True`` the default, which changes every
# random draw (so the sampled actions) while the engine itself is unchanged;
# that pin was taken from the per-tick engine under the new default.
GOLDEN = {
    "threefry-legacy": (
        _dwell_blocks([(19, 1, 4), (16, 1, 4), (2, 19, 2), (3, 11, 7),
                       (17, 12, 14), (4, 14, 17)]),
        [1510.6968994140625, 1292.2806396484375, 1291.2789306640625]),
    "threefry-partitionable": (
        _dwell_blocks([(12, 16, 19), (7, 7, 0), (9, 10, 16), (13, 7, 16),
                       (9, 11, 5), (17, 10, 17)]),
        [854.62255859375, 1112.5733642578125, 1444.7894287109375]),
}


@pytest.fixture(params=list(GOLDEN))
def threefry(request):
    """Run the test under one PRNG implementation, restoring the default."""
    saved = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable",
                      request.param == "threefry-partitionable")
    yield request.param
    jax.config.update("jax_threefry_partitionable", saved)


def test_golden_fleet_rollout_paper_burst(threefry):
    """The default topology reproduces pinned per-tick rollout outputs
    exactly (same seed, R=3, T=30, paper-burst scenario).

    ``threefry-legacy`` runs under ``jax_threefry_partitionable=False`` and
    keeps the pin from commit 0af21fc, before the topology refactor: it
    shows the per-tick engine has not moved since.  ``threefry-partitionable``
    is the jax default; the key tree is the same but each draw differs, so
    it has a pin of its own.
    """
    golden_actions, golden_success = GOLDEN[threefry]
    cfg = core.AifConfig()
    scfg = SimConfig()
    r, t = 3, 30
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_env_step(params, jnp.asarray(sc.arrival_rate),
                                     jnp.asarray(sc.hazard_scale))
    ast, est, trace = api.rollout(
        api.AifRouter(cfg=cfg), fleet.init_fleet_state(cfg, r),
        batched.init_fluid_state(params), env_step, t, jax.random.key(42))
    assert np.asarray(trace.actions).tolist() == golden_actions
    np.testing.assert_allclose(np.asarray(est.n_success), golden_success,
                               rtol=1e-6)


# ----------------------------------------------------------- K=5 end-to-end
def test_five_tier_fleet_rollout_end_to_end():
    """K=5 topology through fleet_rollout + batched env, odd fleet size."""
    topo = five_tier_topology()
    cfg = core.AifConfig(topology=topo)
    scfg = sim_config_for(topo)
    assert len(scfg.tiers) == 5
    r, t = 3, 22   # crosses the slow-learning boundary at t=10,20
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    assert sc.hazard_scale.shape == (t, r, 5)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_env_step(params, jnp.asarray(sc.arrival_rate),
                                     jnp.asarray(sc.hazard_scale))
    disc = discretization_for(scfg)   # rps edges rescaled to the K=5 load
    assert disc.rps_edges[0] < scfg.rps < disc.rps_edges[1]
    ast, est, trace = fleet.fleet_rollout(
        fleet.init_fleet_state(cfg, r), batched.init_fluid_state(params),
        env_step, t, jax.random.key(7), cfg, disc=disc)
    assert trace.routing_weights.shape == (t, r, 5)
    acts = np.asarray(trace.actions)
    assert acts.min() >= 0 and acts.max() < policies.n_actions(topo)
    res = batched.summarize(est, trace.env)
    assert np.all(res.n_requests > 0)


def test_hetero_fleet_rollout_static_sharding():
    """Different topologies run as separate shards of one heterogeneous
    fleet; each shard gets its own shapes and scan."""
    t = 8
    groups = []
    for name, topo, r in (("k3", default_topology(), 2),
                          ("k5", five_tier_topology(), 3)):
        cfg = core.AifConfig(topology=topo)
        scfg = sim_config_for(topo) if topo.n_tiers != 3 else SimConfig()
        sc = scenarios.build_scenario("steady", scfg, r, t)
        params = batched.params_from_config(scfg, r, sc.capacity_scale)
        env_step = batched.make_env_step(params,
                                         jnp.asarray(sc.arrival_rate),
                                         jnp.asarray(sc.hazard_scale))
        groups.append(fleet.FleetGroup(
            name=name, cfg=cfg,
            agent_state=fleet.init_fleet_state(cfg, r),
            env_state=batched.init_fluid_state(params), env_step=env_step))
    out = fleet.hetero_fleet_rollout(groups, t, jax.random.key(0))
    assert set(out) == {"k3", "k5"}
    assert out["k3"][2].routing_weights.shape == (t, 2, 3)
    assert out["k5"][2].routing_weights.shape == (t, 3, 5)


# --------------------------------------------------------- generic agent loop
def test_agent_tick_on_k2_topology():
    """The full inference-action-learning cycle runs on a non-default
    topology (guards against residual 3-tier assumptions in the agent)."""
    topo = _topo_k2()
    cfg = core.AifConfig(topology=topo)
    st = core.init_agent_state(cfg)
    assert st.belief.shape == (topo.n_states,)
    key = jax.random.key(0)
    obs = jnp.asarray([1, 1, 0, 0], jnp.int32)
    util = jnp.asarray([2, 0], jnp.int32)
    for i in range(11):
        key, k = jax.random.split(key)
        st, info = core.tick(st, obs, jnp.asarray(0.05), k, cfg,
                             util, i == 10)
    assert info.routing_weights.shape == (2,)
    assert float(jnp.sum(st.belief)) == pytest.approx(1.0, abs=1e-4)
    # slow learning fired at t=10: counts moved off the prior
    m0 = generative.init_generative_model(cfg)
    assert float(jnp.sum(st.model.a_counts)) > float(jnp.sum(m0.a_counts))
