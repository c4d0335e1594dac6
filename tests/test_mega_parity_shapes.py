"""Mega engine against the per-tick reference engine off the clean paper
world: telemetry blackout, K=2 and K=5 topologies, bfloat16 slot storage,
the densified carry, and warm promotion of a per-tick carry onto the mega
path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.api.aif import AifRouter
from repro.core import generative
from repro.core import mega as mega_core
from repro.core.topology import default_topology, five_tier_topology
from repro.envsim import batched

from mega_helpers import TWO_TIER, _assert_rollouts_match, _mega_carry, _pair


# ------------------------------------------------------- engine-level parity
def test_mega_blackout_scenario():
    """restart_blackout coupling (telemetry dies with the pods)."""
    _assert_rollouts_match(*_pair(scenario="scrape-blackout", t=25, r=5))


@pytest.mark.parametrize("topo", [TWO_TIER, five_tier_topology()],
                         ids=["k2", "k5"])
def test_mega_parity_across_topologies(topo):
    """Parity holds off the paper's K=3: K=2 (no pairwise policies) and the
    K=5 continuum (odd util factors, 37 actions, |S|=128)."""
    _assert_rollouts_match(*_pair(t=15, r=4, topology=topo))


def test_mega_bf16_slots_bounded_drift():
    """bfloat16 slot storage: same world stays finite and close to the f32
    engine at a short horizon (fp32 accumulate bounds the drift)."""
    r1, r2 = _pair(t=20, r=4, mega_slot_dtype="bfloat16")
    assert np.all(np.isfinite(np.asarray(r2.trace.raw_obs)))
    belief = np.asarray(r2.final_carry.belief)
    np.testing.assert_allclose(belief.sum(-1), 1.0, atol=1e-3)
    assert abs(r1.success_pct - r2.success_pct) < 10.0


def test_to_agent_state_roundtrip():
    """Densifying the factored mega carry reproduces the legacy AgentState
    (belief, clocks, and the never-materialized B pseudo-counts)."""
    r1, r2 = _pair(t=20, r=4)
    dense = mega_core.to_agent_state(
        r2.final_carry, AifRouter(mega=True).cfg)
    legacy = r1.final_carry
    for f in ("belief", "error_ema", "dt_since_change"):
        np.testing.assert_allclose(np.asarray(getattr(legacy, f)),
                                   np.asarray(getattr(dense, f)), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(legacy.prev_action),
                                  np.asarray(dense.prev_action))
    np.testing.assert_array_equal(np.asarray(legacy.t), np.asarray(dense.t))
    np.testing.assert_allclose(np.asarray(legacy.model.a_counts),
                               np.asarray(dense.model.a_counts), atol=1e-4)
    np.testing.assert_allclose(np.asarray(legacy.model.b_counts),
                               np.asarray(dense.model.b_counts), atol=1e-4)


# -------------------------------------------------- warm-fleet promotion
def test_warm_promotion_roundtrip():
    """``init_mega_state(from_agent_state=to_agent_state(s))`` is an exact
    round-trip: dense counts, belief, clocks and slot payloads bit-equal,
    and densifying again reproduces the same AgentState bitwise."""
    r, t = 4, 20
    cfg = generative.AifConfig(topology=default_topology())
    state = _mega_carry(n_cells=r, n_windows=t)
    dense = mega_core.to_agent_state(state, cfg)
    back = mega_core.init_mega_state(cfg, r, t, from_agent_state=dense)
    # the source's dense counts become the promoted cache's baseline, bitwise
    np.testing.assert_array_equal(np.asarray(dense.model.b_counts),
                                  np.asarray(back.cache.b_base))
    for f in ("a_counts", "belief", "prev_action", "dt_since_change",
              "error_ema", "unstable", "t"):
        np.testing.assert_array_equal(np.asarray(getattr(state, f)),
                                      np.asarray(getattr(back, f)),
                                      err_msg=f)
    for f in ("q_prev", "q_next", "obs_bins", "obs_mask", "action",
              "dt_since_change"):
        np.testing.assert_array_equal(np.asarray(getattr(state.slots, f)),
                                      np.asarray(getattr(back.slots, f)),
                                      err_msg=f"slots.{f}")
    # colsum rebuilds as the baseline's column sum (vs the run's
    # incremental scalar-prior form) — equal up to reassociation
    np.testing.assert_allclose(np.asarray(state.cache.colsum, np.float64),
                               np.asarray(back.cache.colsum, np.float64),
                               rtol=1e-5, atol=1e-5)
    dense2 = mega_core.to_agent_state(back, cfg)
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(dense)[0],
            jax.tree_util.tree_flatten_with_path(dense2)[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(p))


def test_warm_promotion_continues_per_tick_run():
    """A warm per-tick carry promoted onto the mega path routes bitwise like
    the per-tick engine resumed from the same snapshot (same world, same
    chain key, same telemetry carry)."""
    from repro.envsim import scenarios
    from repro.envsim.config import SimConfig
    r, t1, t2 = 5, 20, 20
    scfg = SimConfig()
    sc = scenarios.build_scenario("paper-burst", scfg, r, t1 + t2)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    pt = experiment_mod._make_aif(default_topology(), scfg, False, False)
    mg = experiment_mod._make_aif(default_topology(), scfg, False, True)
    key = jax.random.key(0)
    cA, eA, _, snapA = engine.resumable_rollout(
        pt, pt.init_carry(r), batched.init_fluid_state(params), env_step,
        t1, key)
    copy = jax.tree_util.tree_map(jnp.array, (cA, eA))
    # per-tick continuation (resumable_rollout donates its inputs)
    _, eB, trB, _ = engine.resumable_rollout(
        pt, cA, eA, env_step, t2, key, t_begin=t1, snapshot=snapA)
    cA2, eA2 = copy
    state, eM, trM, _ = engine._mega_rollout(
        mg, cA2, eA2, env_step, t2, snapA[5], obs_masked=None, t0=None,
        obs_carry=snapA[:5])
    np.testing.assert_array_equal(np.asarray(trB.actions),
                                  np.asarray(trM.actions))
    np.testing.assert_array_equal(np.unique(np.asarray(state.t)), [t1 + t2])
    for f in eB._fields:
        np.testing.assert_allclose(np.asarray(getattr(eB, f), np.float64),
                                   np.asarray(getattr(eM, f), np.float64),
                                   atol=1e-4, err_msg=f"env.{f}")


def test_warm_promotion_rejects_off_boundary_and_pallas():
    r = 3
    cfg = generative.AifConfig(topology=default_topology())
    dense = AifRouter().init_carry(r)
    # mixed-phase fleet clocks cannot share the slot==tick invariant
    with pytest.raises(ValueError, match="uniform fleet clock"):
        mega_core.init_mega_state(cfg, r, 20, from_agent_state=dense._replace(
            t=jnp.asarray([7, 8, 7], jnp.int32)))
    # Pallas kernel cannot represent a promoted dense baseline
    from repro.envsim import scenarios
    from repro.envsim.config import SimConfig
    scfg = SimConfig()
    sc = scenarios.build_scenario("paper-burst", scfg, r, 40)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    env_step = batched.make_scenario_env_step(params, sc)
    warm = dense._replace(t=jnp.full((r,), 20, jnp.int32))
    mg = AifRouter(mega=True, use_pallas=True)
    with pytest.raises(ValueError, match="use_pallas"):
        engine._mega_rollout(mg, warm, batched.init_fluid_state(params),
                             env_step, 20, jax.random.key(0),
                             obs_masked=None, t0=None)
