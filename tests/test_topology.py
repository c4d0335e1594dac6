"""Topology-parameterized core: generated policy sets, cross-K kernel parity,
golden bit-compatibility of the default (paper) topology, K=5 end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import efe as core_efe
from repro.core import fleet, generative, policies, spaces
from repro.core.topology import (PolicySpec, Topology, default_topology,
                                 five_tier_topology, get_topology)
from repro.envsim import (SimConfig, batched, discretization_for, scenarios,
                          sim_config_for)
from repro.kernels.efe.ops import fleet_efe

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


# ----------------------------------------------------- cross-K kernel parity
@pytest.mark.parametrize("topo", [_topo_k2(), default_topology(),
                                  five_tier_topology()],
                         ids=["k2", "k3", "k5"])
@pytest.mark.parametrize("r", [3, 5])   # odd fleet sizes on purpose
def test_efe_kernel_parity_across_topologies(topo, r):
    """Pallas(interpret) vs jnp oracle vs single-agent core EFE, any K."""
    cfg = generative.AifConfig(topology=topo)
    s, a = topo.n_states, policies.n_actions(topo)
    m, nb = topo.n_modalities, topo.max_bins
    ks = jax.random.split(jax.random.key(topo.n_tiers), 3)
    a_counts = (jax.random.uniform(ks[0], (r, m, nb, s), minval=0.1,
                                   maxval=2.0)
                * spaces.bins_mask(topo)[None, :, :, None])
    b_counts = jax.random.uniform(ks[1], (r, a, s, s), minval=0.01,
                                  maxval=1.0)
    c_log = jnp.tile(generative.nominal_c_log(cfg)[None], (r, 1, 1))
    q = jax.random.dirichlet(ks[2], jnp.ones(s), (r,))

    g_pal = fleet_efe(a_counts, b_counts, c_log, q, cfg, use_pallas=True,
                      interpret=True)
    g_ref = fleet_efe(a_counts, b_counts, c_log, q, cfg, use_pallas=False)
    assert g_pal.shape == (r, a)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=1e-4)
    model = generative.GenerativeModel(a_counts=a_counts[0],
                                       b_counts=b_counts[0],
                                       c_log=c_log[0],
                                       d_prior=jnp.ones(s) / s)
    bd = core_efe.expected_free_energy(model, q[0], cfg)
    np.testing.assert_allclose(np.asarray(g_ref[0]), np.asarray(bd.g),
                               atol=1e-4)


def test_block_size_fallback_pow2_divisor():
    """Odd / prime R run through the full wrapper: the fleet axis is padded
    to whole 8-row router blocks (the TPU sublane tiling) and the padded
    rows are cropped, and a block that is not a multiple of 8 is refused."""
    topo = default_topology()
    cfg = generative.AifConfig()
    s, a = topo.n_states, policies.n_actions(topo)
    m, nb = topo.n_modalities, topo.max_bins
    for r in (1, 7, 13):   # prime fleet sizes through the full wrapper
        key = jax.random.key(r)
        a_counts = (jax.random.uniform(key, (r, m, nb, s)) + 0.1
                    ) * spaces.bins_mask(topo)[None, :, :, None]
        b_counts = jax.random.uniform(jax.random.fold_in(key, 1),
                                      (r, a, s, s)) + 0.01
        c_log = jnp.tile(generative.nominal_c_log(cfg)[None], (r, 1, 1))
        q = jnp.ones((r, s)) / s
        g_pal = fleet_efe(a_counts, b_counts, c_log, q, cfg,
                          use_pallas=True, interpret=True, block_r=8)
        g_ref = fleet_efe(a_counts, b_counts, c_log, q, cfg,
                          use_pallas=False)
        np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                                   atol=1e-4)
    with pytest.raises(ValueError, match="multiple of 8"):
        fleet_efe(a_counts, b_counts, c_log, q, cfg, use_pallas=True,
                  interpret=True, block_r=4)


# -------------------------------------------------- golden bit-compatibility
def test_golden_fleet_rollout_paper_burst():
    """The default topology reproduces the pre-refactor ``fleet_rollout``
    outputs exactly (same seed, R=3, T=30, paper-burst scenario) — pinned
    from commit 0af21fc before the topology refactor."""
    golden_actions = [
        [19, 1, 4], [19, 1, 4], [19, 1, 4], [19, 1, 4], [19, 1, 4],
        [16, 1, 4], [16, 1, 4], [16, 1, 4], [16, 1, 4], [16, 1, 4],
        [2, 19, 2], [2, 19, 2], [2, 19, 2], [2, 19, 2], [2, 19, 2],
        [3, 11, 7], [3, 11, 7], [3, 11, 7], [3, 11, 7], [3, 11, 7],
        [17, 12, 14], [17, 12, 14], [17, 12, 14], [17, 12, 14], [17, 12, 14],
        [4, 14, 17], [4, 14, 17], [4, 14, 17], [4, 14, 17], [4, 14, 17]]
    golden_success = [1510.6968994140625, 1292.2806396484375,
                      1291.2789306640625]

    cfg = core.AifConfig()
    scfg = SimConfig()
    r, t = 3, 30
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_env_step(params, jnp.asarray(sc.arrival_rate),
                                     jnp.asarray(sc.hazard_scale))
    ast, est, trace = fleet.fleet_rollout(
        fleet.init_fleet_state(cfg, r), batched.init_fluid_state(params),
        env_step, t, jax.random.key(42), cfg)
    assert np.asarray(trace.actions).tolist() == golden_actions
    np.testing.assert_allclose(np.asarray(est.n_success), golden_success,
                               rtol=1e-6)


# ----------------------------------------------------------- K=5 end-to-end
def test_five_tier_fleet_rollout_end_to_end():
    """K=5 topology through fleet_rollout + batched env + fused EFE kernel
    (interpret mode), odd fleet size; fused matches the vmapped path."""
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
    outs = {}
    for name, kw in (("vmap", {}),
                     ("fused", dict(fused=True, use_pallas=True))):
        ast, est, trace = fleet.fleet_rollout(
            fleet.init_fleet_state(cfg, r), batched.init_fluid_state(params),
            env_step, t, jax.random.key(7), cfg, disc=disc, **kw)
        assert trace.routing_weights.shape == (t, r, 5)
        acts = np.asarray(trace.actions)
        assert acts.min() >= 0 and acts.max() < policies.n_actions(topo)
        res = batched.summarize(est, trace.env)
        assert np.all(res.n_requests > 0)
        outs[name] = (acts, np.asarray(est.n_success))
    # the fused fleet-kernel path is the same math as the vmapped reference
    np.testing.assert_array_equal(outs["vmap"][0], outs["fused"][0])
    np.testing.assert_allclose(outs["vmap"][1], outs["fused"][1], rtol=1e-4)


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
