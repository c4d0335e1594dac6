"""Unreliable-telemetry closed loop: masked partial observability end-to-end.

Pins the invariants:

* an all-ones mask schedule is equal to the unmasked rollout (and the
  unmasked rollout itself is pinned bit-exactly by the golden test in
  test_topology.py),
* masked modalities contribute zero belief evidence and zero A-counts,
* the batched engine's telemetry pipeline re-emits the last published value
  for masked windows and couples the mask to pod liveness under
  ``restart_blackout``,
* under the ``flaky-telemetry`` preset (≥30% modality dropout) the closed
  loop stays finite — no NaN/collapsed-belief ticks — and degrades
  gracefully vs the clean run,

on the per-tick reference engine and on the mega engine.  (The masked
megakernel is compared with its oracle in ``tests/test_mega_window_k*.py``.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, core
from repro.core import agent as agent_mod
from repro.core import belief as belief_mod
from repro.core import fleet, generative, learning, spaces
from repro.core.topology import default_topology
from repro.envsim import SimConfig, batched, scenarios


# --------------------------------------------------- masked belief semantics
def test_masked_modality_contributes_zero_evidence():
    """A masked modality must not move the posterior: masking modality m is
    equivalent to it never having been observed."""
    topo = default_topology()
    cfg = generative.AifConfig()
    st = core.init_agent_state(cfg)
    obs_a = jnp.asarray([2, 1, 0, 1], jnp.int32)
    obs_b = jnp.asarray([0, 1, 0, 1], jnp.int32)   # differs only in mod 0
    mask = jnp.asarray([0.0, 1.0, 1.0, 1.0])
    q_a = belief_mod.update_belief(st.model, st.belief, 0, obs_a, topo,
                                   cache=st.cache, obs_mask=mask)
    q_b = belief_mod.update_belief(st.model, st.belief, 0, obs_b, topo,
                                   cache=st.cache, obs_mask=mask)
    np.testing.assert_allclose(np.asarray(q_a), np.asarray(q_b), atol=1e-7)
    # all-ones mask is the unmasked update
    q_full = belief_mod.update_belief(st.model, st.belief, 0, obs_a, topo,
                                      cache=st.cache)
    q_ones = belief_mod.update_belief(st.model, st.belief, 0, obs_a, topo,
                                      cache=st.cache,
                                      obs_mask=jnp.ones(4))
    np.testing.assert_array_equal(np.asarray(q_full), np.asarray(q_ones))
    # fully-masked tick: posterior == predicted prior (finite, normalized)
    q_dark = belief_mod.update_belief(st.model, st.belief, 0, obs_a, topo,
                                      cache=st.cache, obs_mask=jnp.zeros(4))
    assert np.all(np.isfinite(np.asarray(q_dark)))
    np.testing.assert_allclose(float(jnp.sum(q_dark)), 1.0, atol=1e-5)


def test_masked_observations_accumulate_no_a_counts():
    """Replayed slow learning must not move A-counts of masked modalities."""
    cfg = generative.AifConfig()
    topo = cfg.topology
    model = generative.init_generative_model(cfg)
    buf = learning.init_replay(32, topo)
    q = jnp.ones(topo.n_states) / topo.n_states
    obs = jnp.asarray([2, 1, 0, 1], jnp.int32)
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0])      # modalities 1, 3 dark
    for _ in range(8):
        buf = learning.push_transition(buf, q, q, obs, 3, 10.0,
                                       obs_mask=mask)
    new = learning.slow_update(jax.random.key(0), model, buf, cfg)
    da = np.asarray(new.a_counts - model.a_counts)
    assert np.abs(da[0]).max() > 0                 # fresh modality learned
    assert np.abs(da[2]).max() > 0
    np.testing.assert_array_equal(da[1], 0.0)      # masked: untouched
    np.testing.assert_array_equal(da[3], 0.0)


def test_masked_error_modality_holds_preference_ema():
    """The adaptive-preference error EMA must treat a masked error modality
    as 'no sample' — a stale replayed error rate held through a scrape gap
    would otherwise keep the instability detector tracking phantom data."""
    cfg = core.AifConfig()
    obs = jnp.asarray([1, 1, 0, 1], jnp.int32)
    key = jax.random.key(0)
    err = jnp.asarray(0.9)                         # stale-held high error
    dark = jnp.asarray([1.0, 1.0, 1.0, 0.0])       # error modality masked
    st_dark, _ = core.fast_step(core.init_agent_state(cfg), obs, err, key,
                                cfg, obs_mask=dark)
    assert float(st_dark.error_ema) == 0.0         # EMA held at its init
    st_fresh, _ = core.fast_step(core.init_agent_state(cfg), obs, err, key,
                                 cfg, obs_mask=jnp.ones(4))
    assert float(st_fresh.error_ema) > 0.0         # fresh sample ingested
    st_none, _ = core.fast_step(core.init_agent_state(cfg), obs, err, key,
                                cfg)
    assert float(st_none.error_ema) == float(st_fresh.error_ema)


def test_observe_and_discretize_returns_mask():
    disc = spaces.DiscretizationConfig()
    raw = jnp.asarray([0.5, 50.0, 10.0, 0.01])
    bins, mask = agent_mod.observe_and_discretize(raw, disc)
    assert bins.shape == (4,) and mask.shape == (4,)
    np.testing.assert_array_equal(np.asarray(mask), 1.0)
    _, mask2 = agent_mod.observe_and_discretize(
        raw, disc, jnp.asarray([1.0, 0.0, 1.0, 0.0]))
    np.testing.assert_array_equal(np.asarray(mask2), [1.0, 0.0, 1.0, 0.0])


# ------------------------------------------------- engine telemetry pipeline
def _world(scenario, r, t, seed=0):
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, t, seed=seed)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    return sc, params, env_step


def test_engine_stale_hold_and_mask_emission():
    """Masked windows re-emit the last published value and flag it."""
    scfg = SimConfig()
    r, t = 2, 30
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    # freeze every modality of cell 0 during windows 10..19
    ov = np.ones((t, r, 4), np.float32)
    ov[10:20, 0, :] = 0.0
    w = jnp.asarray([0.15, 0.23, 0.62], jnp.float32)
    _, trace = batched.run_fluid(params, jnp.asarray(sc.arrival_rate),
                                 jnp.asarray(sc.hazard_scale), w,
                                 jax.random.key(0), obs_valid=jnp.asarray(ov))
    raw = np.asarray(trace.raw_obs)
    mask = np.asarray(trace.obs_mask)
    np.testing.assert_array_equal(mask, ov)
    # frozen cell repeats window 9's published values through the gap
    for k in range(10, 20):
        np.testing.assert_array_equal(raw[k, 0], raw[9, 0])
    # the unmasked cell keeps moving (rps EMA ramps up from 0)
    assert not np.array_equal(raw[15, 1], raw[9, 1])
    # no-degradation run is bit-identical on the published stream
    _, trace_clean = batched.run_fluid(params, jnp.asarray(sc.arrival_rate),
                                       jnp.asarray(sc.hazard_scale), w,
                                       jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(trace_clean.raw_obs)[:, 1],
                                  raw[:, 1])
    np.testing.assert_array_equal(np.asarray(trace_clean.obs_mask), 1.0)


def test_restart_blackout_couples_mask_to_liveness():
    """With restart_blackout, a cell with a down tier publishes nothing."""
    scfg = SimConfig()
    r, t = 3, 40
    sc = scenarios.build_scenario("scrape-blackout", scfg, r, t)
    assert sc.restart_blackout
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    w = jnp.asarray([0.15, 0.23, 0.62], jnp.float32)
    final, trace = batched.run_fluid(
        params, jnp.asarray(sc.arrival_rate), jnp.asarray(sc.hazard_scale),
        w, jax.random.key(1), obs_valid=None if sc.obs_valid is None
        else jnp.asarray(sc.obs_valid), restart_blackout=True)
    up = np.asarray(trace.tier_up)          # (T, R, K)
    mask = np.asarray(trace.obs_mask)       # (T, R, M)
    cell_up = up.all(axis=-1)               # (T, R)
    # the cascade's deterministic wave took tiers down at some point
    assert (~cell_up).any()
    np.testing.assert_array_equal(mask.min(axis=-1), mask.max(axis=-1))
    np.testing.assert_array_equal(mask[:, :, 0], cell_up.astype(np.float32))
    # the 10 s utilization scrape is dark too: while a cell is down its
    # published scrape holds (no live state leaks through the side channel)
    util = np.asarray(trace.tier_utilization)      # (T, R, K)
    for k in range(1, t):
        down_cells = np.where(~cell_up[k])[0]
        for c in down_cells:
            np.testing.assert_array_equal(util[k, c], util[k - 1, c])


# --------------------------------------------------- rollout-level invariants
def _rollout(mega, cfg, r, params, env_step, t, key, **kw):
    """A closed-loop rollout on the per-tick reference engine (the
    deprecated shim, which the golden pin covers) or on the mega engine."""
    if mega:
        return api.rollout(api.AifRouter(cfg=cfg, mega=True), None,
                           batched.init_fluid_state(params), env_step, t, key,
                           **kw)
    return fleet.fleet_rollout(
        fleet.init_fleet_state(cfg, r), batched.init_fluid_state(params),
        env_step, t, key, cfg, **kw)


@pytest.mark.parametrize("mega", [False, True], ids=["vmap", "mega"])
def test_all_ones_mask_rollout_equals_unmasked(mega):
    """A degradation schedule of all ones must reproduce the mask-free
    rollout exactly: same actions, same success counters, obs_frac == 1."""
    scfg = SimConfig()
    r, t = 3, 25
    cfg = core.AifConfig()
    sc = scenarios.build_scenario("paper-burst", scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    outs = {}
    for name, ov in (("clean", None),
                     ("ones", np.ones((t, r, 4), np.float32))):
        env_step = batched.make_env_step(
            params, jnp.asarray(sc.arrival_rate),
            jnp.asarray(sc.hazard_scale), obs_valid=ov)
        assert env_step.emits_mask == (ov is not None)
        outs[name] = _rollout(mega, cfg, r, params, env_step, t,
                              jax.random.key(42))
    # explicit override: a wrapped closure losing the emits_mask attribute
    # can still opt in via obs_masked=True (same program as auto-detect)
    env_wrapped = batched.make_env_step(
        params, jnp.asarray(sc.arrival_rate), jnp.asarray(sc.hazard_scale),
        obs_valid=np.ones((t, r, 4), np.float32))
    del env_wrapped.emits_mask
    ast_w, est_w, tr_w = _rollout(mega, cfg, r, params, env_wrapped, t,
                                  jax.random.key(42), obs_masked=True)
    tr_c, tr_o = outs["clean"][2], outs["ones"][2]
    np.testing.assert_array_equal(np.asarray(tr_w.actions),
                                  np.asarray(tr_o.actions))
    np.testing.assert_array_equal(np.asarray(tr_c.actions),
                                  np.asarray(tr_o.actions))
    np.testing.assert_array_equal(np.asarray(tr_c.raw_obs),
                                  np.asarray(tr_o.raw_obs))
    np.testing.assert_array_equal(np.asarray(outs["clean"][1].n_success),
                                  np.asarray(outs["ones"][1].n_success))
    np.testing.assert_array_equal(np.asarray(outs["clean"][0].belief),
                                  np.asarray(outs["ones"][0].belief))
    np.testing.assert_array_equal(np.asarray(tr_o.obs_frac), 1.0)


@pytest.mark.parametrize("mega", [False, True], ids=["vmap", "mega"])
def test_flaky_telemetry_rollout_stays_finite_and_degrades_gracefully(mega):
    """The acceptance scenario: ≥30% modality dropout through the whole
    closed loop — finite beliefs, no collapsed posteriors, sane success."""
    r, t = 3, 45
    cfg = core.AifConfig()
    sc, params, env_step = _world("flaky-telemetry", r, t, seed=3)
    assert sc.obs_valid is not None
    assert 1.0 - sc.obs_valid.mean() >= 0.30
    ast, est, trace = _rollout(mega, cfg, r, params, env_step, t,
                               jax.random.key(7))
    # finite, normalized beliefs at the end; no NaN anywhere in the trace
    beliefs = np.asarray(ast.belief)
    assert np.all(np.isfinite(beliefs))
    np.testing.assert_allclose(beliefs.sum(-1), 1.0, atol=1e-4)
    assert np.all(np.isfinite(np.asarray(trace.raw_obs)))
    # the trace records the effective-observation fraction actually applied
    frac = np.asarray(trace.obs_frac)
    assert frac.shape == (t, r)
    assert 0.45 < frac[1:].mean() < 0.75       # ~65% of modalities fresh
    np.testing.assert_array_equal(frac[0], 1.0)  # warm-up tick: no mask yet
    # the router still routes (actions vary) and serves most traffic
    res = batched.summarize(est, trace.env)
    assert np.all(res.n_requests > 0)
    assert np.all(res.success_rate > 0.3)
    # degradation is graceful: within 25pp of the clean run's success
    _, params_c, env_c = _world("paper-burst", r, t)
    _, est_c, trace_c = _rollout(mega, cfg, r, params_c, env_c, t,
                                 jax.random.key(7))
    res_c = batched.summarize(est_c, trace_c.env)
    gap = res_c.success_rate.mean() - res.success_rate.mean()
    assert gap < 0.25


def test_fleet_tick_accepts_mask_and_matches_single_agent():
    """fleet_tick with per-router masks == per-router single-agent ticks."""
    cfg = core.AifConfig()
    n = 3
    rng = np.random.default_rng(2)
    obs = jnp.asarray(rng.integers(0, 2, size=(n, 4)), jnp.int32)
    errs = jnp.asarray(rng.uniform(0.0, 0.3, size=(n,)), jnp.float32)
    mask = jnp.asarray((rng.random((n, 4)) > 0.4), jnp.float32)
    keys = jax.random.split(jax.random.key(11), n)
    fst, finfo = fleet.fleet_tick(fleet.init_fleet_state(cfg, n), obs, errs,
                                  keys, cfg, obs_mask=mask)
    np.testing.assert_array_equal(np.asarray(finfo.obs_mask),
                                  np.asarray(mask))
    for i in range(n):
        st_i, info_i = core.tick(core.init_agent_state(cfg), obs[i], errs[i],
                                 keys[i], cfg, obs_mask=mask[i])
        assert int(finfo.action[i]) == int(info_i.action)
        np.testing.assert_allclose(np.asarray(fst.belief[i]),
                                   np.asarray(st_i.belief), rtol=1e-5,
                                   atol=1e-7)
