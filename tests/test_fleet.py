"""Fleet-mode coverage: batched state, tick determinism, rollout."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core import fleet
from repro.envsim import SimConfig, batched, scenarios

CFG = core.AifConfig()


def _keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


def _per_router_inputs(n, seed=0):
    rng = np.random.default_rng(seed)
    obs = jnp.asarray(rng.integers(0, 2, size=(n, 4)), jnp.int32)
    errs = jnp.asarray(rng.uniform(0.0, 0.3, size=(n,)), jnp.float32)
    return obs, errs


# ------------------------------------------------------------ init_fleet_state
def test_init_fleet_state_broadcast_shapes():
    n = 5
    fst = fleet.init_fleet_state(CFG, n)
    single = core.init_agent_state(CFG)
    for leaf_f, leaf_s in zip(jax.tree_util.tree_leaves(fst),
                              jax.tree_util.tree_leaves(single)):
        assert leaf_f.shape == (n,) + leaf_s.shape
    # every router starts from the identical single-agent state
    np.testing.assert_array_equal(np.asarray(fst.belief[0]),
                                  np.asarray(fst.belief[4]))
    np.testing.assert_allclose(np.asarray(fst.belief[0]),
                               np.asarray(single.belief))


# ------------------------------------------------------------------ fleet_tick
def test_fleet_tick_per_router_matches_single_agent():
    """Router i of the batch must evolve exactly like a lone agent fed the
    same (obs, error, key) — the R-batch is semantically R independent runs."""
    n = 3
    fst = fleet.init_fleet_state(CFG, n)
    obs, errs = _per_router_inputs(n, seed=1)
    keys = _keys(n, seed=7)
    fst2, finfo = fleet.fleet_tick(fst, obs, errs, keys, CFG)
    for i in range(n):
        st_i, info_i = core.tick(core.init_agent_state(CFG), obs[i], errs[i],
                                 keys[i], CFG)
        assert int(finfo.action[i]) == int(info_i.action)
        np.testing.assert_allclose(np.asarray(finfo.efe.g[i]),
                                   np.asarray(info_i.efe.g), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(fst2.belief[i]),
                                   np.asarray(st_i.belief), rtol=1e-5,
                                   atol=1e-7)


def test_fleet_tick_deterministic():
    n = 4
    obs, errs = _per_router_inputs(n)
    keys = _keys(n)
    # fleet_tick donates its state: two fresh (identical) initial states
    s1, i1 = fleet.fleet_tick(fleet.init_fleet_state(CFG, n), obs, errs,
                              keys, CFG)
    s2, i2 = fleet.fleet_tick(fleet.init_fleet_state(CFG, n), obs, errs,
                              keys, CFG)
    np.testing.assert_array_equal(np.asarray(i1.action), np.asarray(i2.action))
    np.testing.assert_array_equal(np.asarray(s1.belief), np.asarray(s2.belief))


def test_fleet_tick_util_scrape_changes_belief():
    n = 2
    obs, errs = _per_router_inputs(n)
    keys = _keys(n)
    util = jnp.asarray([[2, 1, 0]] * n, jnp.int32)
    s_off, _ = fleet.fleet_tick(fleet.init_fleet_state(CFG, n), obs, errs,
                                keys, CFG, util, False)
    s_on, _ = fleet.fleet_tick(fleet.init_fleet_state(CFG, n), obs, errs,
                               keys, CFG, util, True)
    assert not np.allclose(np.asarray(s_off.belief), np.asarray(s_on.belief))


# --------------------------------------------------------------- fleet_rollout
def test_fleet_rollout_closed_loop_shapes_and_sanity():
    scfg = SimConfig()
    r, t = 2, 40
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_env_step(params, sc.arrival_rate, sc.hazard_scale)
    ast, est, trace = fleet.fleet_rollout(
        fleet.init_fleet_state(CFG, r), batched.init_fluid_state(params),
        env_step, t, jax.random.key(0), CFG)
    assert trace.actions.shape == (t, r)
    assert trace.routing_weights.shape == (t, r, 3)
    assert trace.raw_obs.shape == (t, r, 4)
    acts = np.asarray(trace.actions)
    assert acts.min() >= 0 and acts.max() < core.n_actions(CFG.topology)
    res = batched.summarize(est, trace.env)
    assert np.all(res.n_requests > 0)
    assert np.all(res.success_rate > 0.3)
    # agents advanced t fast steps
    np.testing.assert_array_equal(np.asarray(ast.t), t)


def test_fleet_rollout_deterministic():
    scfg = SimConfig()
    r, t = 2, 15
    sc = scenarios.build_scenario("steady", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_env_step(params, sc.arrival_rate, sc.hazard_scale)
    outs = []
    for _ in range(2):
        _, est, trace = fleet.fleet_rollout(
            fleet.init_fleet_state(CFG, r), batched.init_fluid_state(params),
            env_step, t, jax.random.key(5), CFG)
        outs.append((np.asarray(trace.actions), np.asarray(est.n_success)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_allclose(outs[0][1], outs[1][1])
