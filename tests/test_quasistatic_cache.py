"""Quasi-static model cache coverage.

Pins the performance-architecture invariants:

* the normalized-model cache is exactly what :func:`derive_cache` yields
  from the pseudo-counts at every point in a rollout (slow-tick refresh),
* ``predict_prior`` slices the action row before normalizing (bit-identical
  to normalizing the full (A, S, S) stack),
* full-rollout trace parity between the Pallas megakernel and the per-tick
  reference on ``paper-3tier`` and ``continuum-5tier`` (slow-boundary and
  remainder ticks included, odd R),
* the slow learning step executes exactly once per slow period, on the
  per-tick and the mega engine (runtime call-count trace, not a trace-time
  proxy),
* held (non-dwell) ticks evolve state identically with and without the EFE
  evaluation (the invariant behind the rollout's dwell blocking),
* state buffers are donated through ``fleet_rollout``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, core
from repro.core import belief as belief_mod
from repro.core import fleet, generative, policies, spaces
from repro.core import mega as mega_core
from repro.core.topology import default_topology, five_tier_topology
from repro.envsim import (SimConfig, batched, discretization_for, scenarios,
                          sim_config_for)


def _fleet_world(topo, r, t, seed=0):
    cfg = core.AifConfig(topology=topo)
    scfg = SimConfig() if topo.n_tiers == 3 else sim_config_for(topo)
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_env_step(params, jnp.asarray(sc.arrival_rate),
                                     jnp.asarray(sc.hazard_scale))
    disc = None if topo.n_tiers == 3 else discretization_for(scfg)
    return cfg, params, env_step, disc


def _rollout(cfg, params, env_step, disc, r, t, **kw):
    return fleet.fleet_rollout(
        fleet.init_fleet_state(cfg, r), batched.init_fluid_state(params),
        env_step, t, jax.random.key(11), cfg, disc=disc, **kw)


# ------------------------------------------------------------ cache contents
def test_cache_matches_derived_model_after_rollout():
    """At any point the cache must equal derive_cache(model): it is refreshed
    on exactly the ticks that write the pseudo-counts."""
    topo = default_topology()
    cfg, params, env_step, disc = _fleet_world(topo, 2, 25)
    ast, _, _ = _rollout(cfg, params, env_step, disc, 2, 25)
    for i in range(2):
        model_i = jax.tree_util.tree_map(lambda x: x[i], ast.model)
        fresh = generative.derive_cache(model_i, topo)
        np.testing.assert_array_equal(np.asarray(ast.cache.nb[i]),
                                      np.asarray(fresh.nb))
        np.testing.assert_array_equal(np.asarray(ast.cache.na[i]),
                                      np.asarray(fresh.na))
        # the entropy reduction fuses differently inside the jitted rollout
        # (1-ulp reassociation); nb/na divisions stay bitwise
        np.testing.assert_allclose(np.asarray(ast.cache.amb[i]),
                                   np.asarray(fresh.amb), rtol=1e-6)
    # the model did learn (cache is not the init cache)
    init = fleet.init_fleet_state(cfg, 2)
    assert not np.allclose(np.asarray(ast.cache.nb), np.asarray(init.cache.nb))


def test_predict_prior_slices_before_normalizing():
    """Slice-then-normalize must be bit-identical to the old
    normalize-everything-then-slice (elementwise in the action axis)."""
    topo = default_topology()
    s, a = topo.n_states, policies.n_actions(topo)
    key = jax.random.key(3)
    b_counts = jax.random.uniform(key, (a, s, s), minval=0.01, maxval=2.0)
    belief = jax.random.dirichlet(jax.random.fold_in(key, 1), jnp.ones(s))
    for act in (0, 7, a - 1):
        full = generative.normalize_b(b_counts)[act] @ belief
        full = full / jnp.maximum(jnp.sum(full), 1e-30)
        got = belief_mod.predict_prior(b_counts, belief, act)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


# ------------------------------------------------------- rollout trace parity
@pytest.mark.parametrize("topo", [default_topology(), five_tier_topology()],
                         ids=["paper-3tier", "continuum-5tier"])
def test_megakernel_rollout_trace_parity(topo):
    """Pallas megakernel (interpret mode) vs per-tick reference full-rollout
    parity: identical action/weight traces, beliefs within 1e-5.  T=23
    crosses the slow boundaries at t=10, 20 and leaves a 3-tick remainder
    (one dwell block + held ticks); R=3 pads the kernel's 8-router block."""
    r, t = 3, 23
    cfg, params, env_step, disc = _fleet_world(topo, r, t)
    ref = _rollout(cfg, params, env_step, disc, r, t)
    st, est, tr_k = api.rollout(
        api.AifRouter(cfg=cfg, disc=disc, mega=True, use_pallas=True), None,
        batched.init_fluid_state(params), env_step, t, jax.random.key(11))
    dense = mega_core.to_agent_state(st, cfg)
    tr_v = ref[2]
    np.testing.assert_array_equal(np.asarray(tr_v.actions),
                                  np.asarray(tr_k.actions))
    np.testing.assert_array_equal(np.asarray(tr_v.routing_weights),
                                  np.asarray(tr_k.routing_weights))
    np.testing.assert_array_equal(np.asarray(tr_v.unstable),
                                  np.asarray(tr_k.unstable))
    np.testing.assert_allclose(np.asarray(ref[0].belief),
                               np.asarray(dense.belief), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref[0].model.b_counts),
                               np.asarray(dense.model.b_counts),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref[1].n_success),
                               np.asarray(est.n_success), rtol=1e-5)


# ------------------------------------------------- slow-step execution count
@pytest.mark.parametrize("mega", [False, True], ids=["vmap", "mega"])
def test_slow_step_executes_once_per_period(mega, monkeypatch):
    """Runtime call-count trace: the rollout's slow learning path must fire
    n_steps // period times (once per slow period), not once per tick."""
    calls = []
    mod, name = (mega_core, "mega_slow_step") if mega else (fleet,
                                                              "_slow_learn")
    orig = getattr(mod, name)

    def counting(*args, **kw):
        jax.debug.callback(lambda: calls.append(1))
        return orig(*args, **kw)

    monkeypatch.setattr(mod, name, counting)
    topo = default_topology()
    r, t = 2, 25                           # 2 slow periods + 5-tick remainder
    cfg, params, env_step, disc = _fleet_world(topo, r, t)
    if mega:
        ast, _, _ = api.rollout(
            api.AifRouter(cfg=cfg, disc=disc, mega=True), None,
            batched.init_fluid_state(params), env_step, t,
            jax.random.key(11))
        a_counts = ast.a_counts
    else:
        ast, _, _ = _rollout(cfg, params, env_step, disc, r, t)
        a_counts = ast.model.a_counts
    jax.block_until_ready(ast)
    jax.effects_barrier()
    period = int(cfg.slow_period_s / cfg.fast_period_s)
    assert len(calls) == t // period == 2
    # ...and learning really happened on those boundaries
    init = fleet.init_fleet_state(cfg, r)
    assert float(jnp.sum(a_counts)) > float(jnp.sum(init.model.a_counts))


# --------------------------------------------------- held-tick equivalence
def test_light_step_matches_fast_step_on_held_ticks():
    """On a tick with t % dwell != 0 the sampled action is discarded, so
    skipping the EFE evaluation (fleet_light_step) must evolve the state
    exactly like the full fast step — the invariant behind the rollout's
    dwell blocking."""
    cfg = core.AifConfig()
    n = 3
    state = fleet.init_fleet_state(cfg, n)
    rng = np.random.default_rng(0)
    obs = jnp.asarray(rng.integers(0, 2, size=(n, 4)), jnp.int32)
    errs = jnp.asarray(rng.uniform(0.0, 0.2, size=(n,)), jnp.float32)
    # advance off the dwell cadence (t=0 -> 2 ticks -> t=2, 2 % 5 != 0)
    for step in range(2):
        keys = jax.random.split(jax.random.key(step), n)
        state, _ = fleet.fleet_tick(state, obs, errs, keys, cfg)
    assert int(state.t[0]) % int(cfg.action_dwell_s) != 0

    keys = jax.random.split(jax.random.key(99), n)
    s_full, info_full = fleet.fleet_fast_step(state, obs, errs, keys, cfg)
    s_light, info_light = fleet.fleet_light_step(state, obs, errs, cfg)
    np.testing.assert_array_equal(np.asarray(info_full.action),
                                  np.asarray(info_light.action))
    for leaf_f, leaf_l in zip(jax.tree_util.tree_leaves(s_full),
                              jax.tree_util.tree_leaves(s_light)):
        np.testing.assert_allclose(np.asarray(leaf_f), np.asarray(leaf_l),
                                   atol=1e-6)


# ------------------------------------------------------- chained rollouts
def test_chained_rollout_keeps_dwell_and_slow_cadence(monkeypatch):
    """Feeding a rollout's returned state into a second rollout must keep
    the dwell/slow schedules phased to the fleet clock (inferred from the
    concrete state.t): the second leg matches a per-tick fleet_tick
    reference loop exactly, and learning fires on the true boundaries."""
    r, t1, t2 = 2, 23, 17
    topo = default_topology()
    cfg, params, env_step, disc = _fleet_world(topo, r, max(t1, t2))
    ast, est, _ = _rollout(cfg, params, env_step, disc, r, t1)
    assert int(ast.t[0]) == t1                     # mid-flight clock (23)
    copy = lambda tree: jax.tree_util.tree_map(jnp.copy, tree)
    ast2, est2 = copy(ast), copy(est)

    calls = []
    orig = fleet._slow_learn

    def counting(state, keys, cfg_):
        jax.debug.callback(lambda: calls.append(1))
        return orig(state, keys, cfg_)

    monkeypatch.setattr(fleet, "_slow_learn", counting)
    # second leg: t runs 23 -> 40; slow boundaries at t=30, 40 -> 2 firings
    ast_b, est_b, trace = fleet.fleet_rollout(ast, est, env_step, t2,
                                              jax.random.key(5), cfg)
    jax.block_until_ready(ast_b)
    jax.effects_barrier()
    assert len(calls) == 2
    monkeypatch.setattr(fleet, "_slow_learn", orig)

    # per-tick reference loop over the same key chain and environment
    k = jax.random.key(5)
    raw_obs = jnp.zeros((r, topo.n_modalities), jnp.float32)
    tier_util = jnp.zeros((r, topo.n_tiers), jnp.float32)
    edges = jnp.asarray(topo.util_edges, jnp.float32)
    actions = []
    for i in range(t2):
        k, k_env, k_agents = jax.random.split(k, 3)
        keys = jax.random.split(k_agents, r)
        obs_bins = spaces.discretize_observation(
            raw_obs, disc or core.DiscretizationConfig())
        util_bins = jnp.sum(tier_util[:, ::-1][..., None] >= edges,
                            axis=-1).astype(jnp.int32)
        ast2, info = fleet.fleet_tick(ast2, obs_bins, raw_obs[:, 3], keys,
                                      cfg, util_bins,
                                      (i % 10 == 0) & (i > 0))
        est2, win = env_step(est2, info.routing_weights, i, k_env)
        raw_obs, tier_util = win.raw_obs, win.tier_utilization
        actions.append(np.asarray(info.action))
    np.testing.assert_array_equal(np.asarray(trace.actions),
                                  np.stack(actions))
    np.testing.assert_allclose(np.asarray(ast_b.belief),
                               np.asarray(ast2.belief), atol=1e-6)


def test_rollout_rejects_traced_clock_without_t0():
    """Under an outer jit the fleet clock cannot be introspected; requiring
    an explicit t0 keeps the dwell/slow schedules from silently compiling
    against the wrong phase."""
    cfg = core.AifConfig()
    with pytest.raises(ValueError, match="traced"):
        jax.jit(lambda a: fleet.fleet_rollout(
            a, None, lambda *x: None, 5, jax.random.key(0), cfg)
        )(fleet.init_fleet_state(cfg, 2))


# ------------------------------------------------------------ buffer donation
def test_fleet_rollout_donates_state_buffers():
    """The rollout consumes its input state pytrees (no entry copy of the
    replay-buffer-dominated fleet state)."""
    topo = default_topology()
    r, t = 2, 7
    cfg, params, env_step, disc = _fleet_world(topo, r, t)
    ast_in = fleet.init_fleet_state(cfg, r)
    est_in = batched.init_fluid_state(params)
    ast, est, _ = fleet.fleet_rollout(ast_in, est_in, env_step, t,
                                      jax.random.key(0), cfg, disc=disc)
    assert int(ast.t[0]) == t
    # donation happened: the input buffers are gone (CPU/TPU/GPU all
    # support donation in current jaxlib)
    assert ast_in.belief.is_deleted()
    assert est_in.backlog.is_deleted()
