"""Fault injection + self-healing runtime coverage.

Pins the robustness contract end to end: chaos schedules compose into the
jitted scan without a Python step in the loop, the in-scan numerical
watchdog quarantines poisoned cells without touching healthy ones, stop +
resume at a checkpoint boundary is bit-identical to the uninterrupted
program on all three engine paths (per-tick, mega, sharded), the
Checkpointer survives torn writes, and the Experiment surface reports
finite recovery metrics for chaos scenarios.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.checkpoint import Checkpointer, CorruptCheckpointError
from repro.core import agent as agent_mod
from repro.core import belief as belief_mod
from repro.core import fleet as fleet_mod
from repro.core import generative
from repro.core import mega as mega_mod
from repro.core.topology import Topology, PolicySpec, default_topology
from repro.envsim import SimConfig, batched, chaos, scenarios

R, T = 4, 40


def _world(scenario, r=R, t=T, seed=0):
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, t, seed=seed)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    return params, batched.make_scenario_env_step(params, sc)


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _copy(tree):
    return jax.tree_util.tree_map(lambda a: jnp.array(np.asarray(a)), tree)


# ------------------------------------------------------------ chaos schedules
def test_chaos_presets_registered():
    for name in chaos.CHAOS_PRESETS:
        assert name in scenarios.SCENARIOS
        assert name in chaos.CHAOS_INFO


def test_zone_outage_schedule_confined_to_fault_window():
    scfg = SimConfig()
    sc = scenarios.build_scenario("zone-outage", scfg, R, T, seed=0)
    fd = np.asarray(sc.forced_down)
    assert fd.shape[0] == T and fd.shape[1] == R
    lo, hi = int(0.3 * T), int(0.5 * T)
    assert fd[lo:hi].max() == 1.0          # the outage actually fires
    assert fd[:lo].max() == 0.0 and fd[hi:].max() == 0.0
    # zone 0 of 2: only the first half of the cells ever goes admin-down
    assert fd[:, R // 2:].max() == 0.0


def test_straggler_storm_slows_but_never_stops():
    scfg = SimConfig()
    sc = scenarios.build_scenario("straggler-storm", scfg, R, T, seed=0)
    sp = np.asarray(sc.speed)
    assert sp.min() < 1.0 and sp.min() > 0.0
    assert sp.max() <= 1.0
    assert sc.forced_down is None


def test_clean_scenario_has_no_chaos_tensors():
    scfg = SimConfig()
    sc = scenarios.build_scenario("paper-burst", scfg, R, T, seed=0)
    assert sc.forced_down is None and sc.speed is None


# --------------------------------------------------------- degenerate beliefs
def _small_topo(k: int) -> Topology:
    if k == 3:
        return default_topology()
    names = tuple(f"t{i}" for i in range(k))
    return Topology(tier_names=names, tier_classes=names, n_levels=2,
                    util_edges=(0.8,), policy_spec=PolicySpec())


@pytest.mark.parametrize("k", [2, 3, 5])
def test_update_belief_all_masked_falls_back_to_prior(k):
    """With every modality masked (and no scrape) the posterior must be
    exactly the renormalized one-step prior — never a 0/0 artifact."""
    topo = _small_topo(k)
    cfg = generative.AifConfig(topology=topo)
    s = agent_mod.init_agent_state(cfg)
    # peak the belief so the prior is far from uniform
    belief = jnp.zeros_like(s.belief).at[0].set(1.0)
    obs_bins = jnp.zeros((topo.n_modalities,), jnp.int32)
    mask0 = jnp.zeros((topo.n_modalities,), jnp.float32)
    q = belief_mod.update_belief(s.model, belief, 0, obs_bins, topo,
                                 obs_mask=mask0)
    assert np.isfinite(np.asarray(q)).all()
    np.testing.assert_allclose(np.asarray(q).sum(), 1.0, rtol=1e-5)
    prior = belief_mod.predict_prior(s.model.b_counts, belief, 0)
    expect = prior / jnp.maximum(jnp.sum(prior), 1e-30)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(expect))


@pytest.mark.parametrize("k", [2, 3, 5])
def test_update_belief_guard_is_noop_with_evidence(k):
    """An all-ones mask must stay bit-identical to obs_mask=None."""
    topo = _small_topo(k)
    cfg = generative.AifConfig(topology=topo)
    s = agent_mod.init_agent_state(cfg)
    obs_bins = jnp.ones((topo.n_modalities,), jnp.int32)
    q_none = belief_mod.update_belief(s.model, s.belief, 0, obs_bins, topo)
    q_ones = belief_mod.update_belief(
        s.model, s.belief, 0, obs_bins, topo,
        obs_mask=jnp.ones((topo.n_modalities,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(q_none), np.asarray(q_ones))


# ----------------------------------------------------------- watchdog healing
def _warm_pieces(scenario="paper-burst"):
    params, env_step = _world(scenario)
    router = api.AifRouter(cfg=generative.AifConfig())
    key = jax.random.key(3)
    carry, est, _ = engine.rollout(
        router, router.init_carry(R), batched.init_fluid_state(params),
        env_step, 10, key)
    return router, env_step, jax.device_get(carry), jax.device_get(est)


def test_watchdog_quarantines_poisoned_cell_and_spares_neighbors():
    router, env_step, carry, est = _warm_pieces()
    key2 = jax.random.key(7)

    poisoned = _copy(carry)
    poisoned = poisoned._replace(
        belief=poisoned.belief.at[2].set(jnp.nan))
    c_clean, e_clean, tr_clean = engine.rollout(
        router, _copy(carry), _copy(est), env_step, 10, key2)
    c_bad, e_bad, tr_bad = engine.rollout(
        router, poisoned, _copy(est), env_step, 10, key2)

    wd = np.asarray(tr_bad.watchdog)
    assert wd.shape == (10, R)
    assert wd[0, 2] == 1.0                 # healed on the first tick
    assert wd[1:, 2].max() == 0.0          # and stays healthy
    assert wd[:, [0, 1, 3]].max() == 0.0   # neighbors never flagged
    for leaf in jax.tree_util.tree_leaves(c_bad):
        arr = np.asarray(leaf)
        if arr.dtype.kind == "f":
            assert np.isfinite(arr).all()
    # neighbors' final states are bit-identical to the uninjured run
    for name in ("belief", "error_ema", "prev_action"):
        a = np.asarray(getattr(c_bad, name))
        b = np.asarray(getattr(c_clean, name))
        np.testing.assert_array_equal(a[[0, 1, 3]], b[[0, 1, 3]])
    assert np.asarray(tr_clean.watchdog).max() == 0.0


def test_watchdog_off_lets_nan_propagate():
    router, env_step, carry, est = _warm_pieces()
    router_off = api.AifRouter(cfg=generative.AifConfig(watchdog=False))
    poisoned = _copy(carry)._replace(
        belief=_copy(carry).belief.at[2].set(jnp.nan))
    c_bad, _, tr = engine.rollout(
        router_off, poisoned, _copy(est), env_step, 10, jax.random.key(7))
    assert tr.watchdog is None
    assert not np.isfinite(np.asarray(c_bad.belief)[2]).all()


def test_watchdog_identity_branch_is_bit_exact():
    """A healthy fleet must run bit-identically with the watchdog on/off."""
    params, env_step = _world("paper-burst")
    on = api.AifRouter(cfg=generative.AifConfig(watchdog=True))
    off = api.AifRouter(cfg=generative.AifConfig(watchdog=False))
    key = jax.random.key(0)
    c_on, e_on, t_on = engine.rollout(
        on, on.init_carry(R), batched.init_fluid_state(params), env_step,
        20, key)
    c_off, e_off, t_off = engine.rollout(
        off, off.init_carry(R), batched.init_fluid_state(params), env_step,
        20, key)
    assert _tree_equal(c_on, c_off)
    assert _tree_equal(e_on, e_off)
    np.testing.assert_array_equal(np.asarray(t_on.actions),
                                  np.asarray(t_off.actions))


def test_mega_watchdog_quarantine_unit():
    cfg = generative.AifConfig()
    state = mega_mod.init_mega_state(cfg, R, T)
    state = state._replace(belief=state.belief.at[1].set(jnp.nan))
    bad = mega_mod.mega_watchdog_bad(state)
    np.testing.assert_array_equal(np.asarray(bad),
                                  [False, True, False, False])
    healed = mega_mod.mega_quarantine(state, bad, cfg)
    b = np.asarray(healed.belief)
    assert np.isfinite(b).all()
    np.testing.assert_allclose(b[1].sum(), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(b[0], np.asarray(state.belief)[0])
    # the fleet clock is shared and must not rewind
    np.testing.assert_array_equal(np.asarray(healed.t), np.asarray(state.t))


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["oracle", "kernel-interpret"])
def test_mega_watchdog_quarantines_inside_the_rollout(use_pallas):
    """The window loop's watchdog heals a cell that enters the rollout
    diverged: the first window's event names that cell alone, the cell
    ends finite, and every other cell's actions and slots are a clean
    run's, bit for bit."""
    params, env_step = _world("paper-burst", t=20)
    cfg = generative.AifConfig()
    router = api.AifRouter(cfg=cfg, mega=True, use_pallas=use_pallas)

    def run(state_in):
        state, _, trace, _ = engine._mega_rollout(
            router, None, batched.init_fluid_state(params), env_step, 20,
            jax.random.key(7), obs_masked=None, t0=None, state_in=state_in)
        return state, trace

    clean_state, clean = run(mega_mod.init_mega_state(cfg, R, 20))
    poisoned = mega_mod.init_mega_state(cfg, R, 20)
    healed_state, healed = run(
        poisoned._replace(belief=poisoned.belief.at[1].set(jnp.nan)))

    events = np.zeros((20, R), np.float32)
    events[router.period - 1, 1] = 1.0
    np.testing.assert_array_equal(np.asarray(healed.watchdog), events)
    assert not np.asarray(clean.watchdog).any()
    for leaf in jax.tree_util.tree_leaves(
            (healed_state.belief, healed_state.a_counts,
             healed_state.slots)):
        assert np.isfinite(np.asarray(leaf)[1]).all()
    keep = [0, 2, 3]
    np.testing.assert_array_equal(np.asarray(healed.actions)[:, keep],
                                  np.asarray(clean.actions)[:, keep])
    for a, b in zip(healed_state.slots, clean_state.slots):
        np.testing.assert_array_equal(np.asarray(a)[keep],
                                      np.asarray(b)[keep])


# ----------------------------------------------------- stop/resume bit-parity
def test_resume_bit_identical_per_tick():
    params, env_step = _world("zone-outage")
    router = api.AifRouter(cfg=generative.AifConfig())
    key = jax.random.key(42)

    c_u, e_u, tr_u = engine.rollout(
        router, router.init_carry(R), batched.init_fluid_state(params),
        env_step, T, key)

    c1, e1, tr1, snap = engine.resumable_rollout(
        router, router.init_carry(R), batched.init_fluid_state(params),
        env_step, 20, key)
    c2, e2, tr2, _ = engine.resumable_rollout(
        router, c1, e1, env_step, 20, key, t_begin=20, snapshot=snap)

    assert _tree_equal(c_u, c2)
    assert _tree_equal(e_u, e2)
    joined = jax.tree_util.tree_map(
        lambda a, b: np.concatenate([np.asarray(a), np.asarray(b)], 0),
        jax.device_get(tr1), jax.device_get(tr2))
    assert _tree_equal(jax.device_get(tr_u), joined)


def test_resume_bit_identical_mega():
    params, env_step = _world("zone-outage")
    router = api.AifRouter(cfg=generative.AifConfig(), mega=True)
    key = jax.random.key(42)

    c_u, e_u, _ = engine.rollout(
        router, None, batched.init_fluid_state(params), env_step, T, key)

    c1, e1, _, snap = engine.resumable_rollout(
        router, None, batched.init_fluid_state(params), env_step, 20, key,
        n_total=T)
    c2, e2, _, _ = engine.resumable_rollout(
        router, c1, e1, env_step, 20, key, t_begin=20, snapshot=snap)

    assert _tree_equal(c_u, c2)
    assert _tree_equal(e_u, e2)


def test_resume_bit_identical_sharded():
    spec = api.ShardSpec(devices=jax.local_device_count())
    r = 2 * jax.local_device_count()
    r_pad, _ = spec.padded(r)
    scfg = SimConfig()
    sc = scenarios.build_scenario("zone-outage", scfg, r, T, seed=0)
    sc = scenarios.pad_scenario(sc, r_pad)
    params = batched.params_from_config(scfg, r_pad, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    router = api.AifRouter(cfg=generative.AifConfig())
    red = experiment_mod.FleetMetricsReducer(n_cells=r)
    key = jax.random.key(42)

    c_u, e_u, stats_u = engine.sharded_rollout(
        router, batched.init_fluid_state(params), env_step, T, key,
        shard=spec, n_cells=r, reducer=red)

    c1, e1, s1, snap = engine.sharded_resumable_rollout(
        router, None, batched.init_fluid_state(params), env_step, 20, key,
        shard=spec, n_cells=r, reducer=red)
    c2, e2, s2, _ = engine.sharded_resumable_rollout(
        router, c1, e1, env_step, 20, key, shard=spec, n_cells=r,
        reducer=red, t_begin=20, snapshot=snap)
    stats_c = engine.sharded_finalize(s2, shard=spec, reducer=red)

    assert _tree_equal(c_u, c2)
    assert _tree_equal(e_u, e2)
    assert _tree_equal(stats_u, stats_c)


def test_resume_boundary_validation():
    params, env_step = _world("paper-burst")
    router = api.AifRouter(cfg=generative.AifConfig())
    with pytest.raises(ValueError, match="boundary"):
        engine.resumable_rollout(
            router, router.init_carry(R), batched.init_fluid_state(params),
            env_step, 10, jax.random.key(0), t_begin=7,
            snapshot=((),) * 6)
    with pytest.raises(ValueError, match="snapshot"):
        engine.resumable_rollout(
            router, router.init_carry(R), batched.init_fluid_state(params),
            env_step, 10, jax.random.key(0), t_begin=20, snapshot=None)


# ----------------------------------------------------- checkpointer hardening
def _save_two(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_n=5)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.int32)}
    ck.save(10, tree, extra={"t": 10}, blocking=True)
    tree2 = {"a": tree["a"] + 1.0, "b": tree["b"] * 2}
    ck.save(20, tree2, extra={"t": 20}, blocking=True)
    return ck, tree, tree2


def test_restore_falls_back_past_torn_leaf(tmp_path):
    ck, tree, _ = _save_two(tmp_path)
    # torn write: newest checkpoint's array file truncated mid-stream
    victim = os.path.join(str(tmp_path), "step_00000020", "a.npy")
    with open(victim, "wb") as f:
        f.write(b"\x93NUMPY")
    like = {"a": jnp.zeros((2, 3), jnp.float32), "b": jnp.zeros((4,),
                                                                jnp.int32)}
    with pytest.warns(RuntimeWarning, match="unreadable"):
        out, extra = ck.restore(like)
    assert extra["t"] == 10
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    # explicitly naming the torn step stays strict
    with pytest.raises(CorruptCheckpointError):
        ck.restore(like, step=20)


def test_restore_falls_back_past_corrupt_manifest(tmp_path):
    ck, tree, _ = _save_two(tmp_path)
    with open(os.path.join(str(tmp_path), "step_00000020",
                           "manifest.json"), "w") as f:
        f.write("{not json")
    like = {"a": jnp.zeros((2, 3), jnp.float32), "b": jnp.zeros((4,),
                                                                jnp.int32)}
    with pytest.warns(RuntimeWarning):
        out, extra = ck.restore(like)
    assert extra["t"] == 10


def test_all_checkpoints_corrupt_raises(tmp_path):
    ck, *_ = _save_two(tmp_path)
    for step in (10, 20):
        with open(os.path.join(str(tmp_path), f"step_{step:08d}",
                               "manifest.json"), "w") as f:
            f.write("")
    like = {"a": jnp.zeros((2, 3), jnp.float32), "b": jnp.zeros((4,),
                                                                jnp.int32)}
    with pytest.warns(RuntimeWarning):
        with pytest.raises(CorruptCheckpointError, match="all 2"):
            ck.restore(like)


def test_interrupted_tmp_dir_is_invisible(tmp_path):
    ck, tree, tree2 = _save_two(tmp_path)
    os.makedirs(os.path.join(str(tmp_path), "step_00000030.tmp"))
    assert ck.all_steps() == [10, 20]
    like = {"a": jnp.zeros((2, 3), jnp.float32), "b": jnp.zeros((4,),
                                                                jnp.int32)}
    out, extra = ck.restore(like)
    assert extra["t"] == 20
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree2["a"]))


# --------------------------------------------------------- Experiment surface
@pytest.mark.slow
def test_experiment_checkpoint_resume_and_recovery(tmp_path):
    base = dict(router="aif", scenario="zone-outage", n_cells=3,
                n_windows=T)
    r0 = api.run(api.Experiment(**base))
    assert r0.recovery is not None
    for k, v in r0.recovery.items():
        if isinstance(v, float):
            assert np.isfinite(v), (k, v)
    assert r0.recovery["regret_vs_control"] >= 0.0

    ck = str(tmp_path / "ck")
    r1 = api.run(api.Experiment(**base, checkpoint_every=20,
                                checkpoint_dir=ck))
    assert r1.resume_points == (20,)
    assert _tree_equal(r0.final_carry, r1.final_carry)
    np.testing.assert_array_equal(r0.fluid.n_success, r1.fluid.n_success)

    r2 = api.run(api.Experiment(**base, resume_from=ck))
    assert _tree_equal(r0.final_carry, r2.final_carry)
    np.testing.assert_array_equal(r0.fluid.n_success, r2.fluid.n_success)
    # the resumed trace covers the post-resume windows only
    assert np.asarray(r2.trace.env.success).shape[0] == T - 20

    row = r1.summary()
    assert "recovery" in row and "watchdog_events" in row
    json.dumps(row)     # JSON-safe


def test_experiment_checkpoint_validation():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        api.run(api.Experiment(router="aif", scenario="paper-burst",
                               n_cells=2, n_windows=20, checkpoint_every=10))
    with pytest.raises(ValueError, match="boundary"):
        api.run(api.Experiment(router="aif", scenario="paper-burst",
                               n_cells=2, n_windows=20, checkpoint_every=7,
                               checkpoint_dir="/tmp/unused"))
