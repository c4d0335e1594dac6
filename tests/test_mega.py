"""Whole-window megakernel path: PRNG hoisting contracts, engine parity
(clean + masked telemetry, odd R, dwell/slow boundaries, K sweeps), mixed
precision, streaming slow boundaries, warm-fleet promotion, chunked
super-launches, the sharded super-launch, carry densification, Pallas
interpret parity and guards."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import engine
from repro.api import experiment as experiment_mod
from repro.api.aif import AifRouter
from repro.api.experiment import Experiment, FleetMetricsReducer, run
from repro.api.shard import ShardSpec
from repro.core import generative
from repro.core import mega as mega_core
from repro.core.topology import Topology, default_topology, five_tier_topology
from repro.envsim import batched
from repro.kernels.efe.ops import on_tpu

KEY = jax.random.key(0)

TWO_TIER = Topology(tier_names=("edge", "cloud"),
                    tier_classes=("edge-medium", "server"))


def _pair(scenario="paper-burst", t=25, r=6, topology="paper-3tier",
          seed=0, **mega_kw):
    """(legacy fused run, mega run) on the same world."""
    base = dict(router="aif", fused=True, scenario=scenario, n_cells=r,
                n_windows=t, seed=seed, topology=topology)
    return (run(Experiment(**base)),
            run(Experiment(**base, mega=True, **mega_kw)))


def _assert_rollouts_match(r1, r2, atol=1e-4):
    a1, a2 = np.asarray(r1.trace.actions), np.asarray(r2.trace.actions)
    np.testing.assert_array_equal(a1, a2)
    for name in ("routing_weights", "raw_obs", "unstable", "obs_frac"):
        np.testing.assert_allclose(
            np.asarray(getattr(r1.trace, name), np.float64),
            np.asarray(getattr(r2.trace, name), np.float64),
            atol=atol, err_msg=f"trace.{name}")
    for f in r1.trace.env._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(r1.trace.env, f), np.float64),
            np.asarray(getattr(r2.trace.env, f), np.float64),
            atol=atol, err_msg=f"env.{f}")
    assert np.all(np.isfinite(r2.fluid.n_requests))


# ------------------------------------------------------------ PRNG contracts
def test_key_block_replays_chain():
    """The hoisted per-window key block is the per-tick split chain verbatim
    (satellite: pre-split key blocks must not change a single draw)."""
    n, r = 7, 5
    k = jax.random.key(42)
    kk, naive = k, []
    for _ in range(n):
        kk, k_env, k_agents = jax.random.split(kk, 3)
        ks = jax.vmap(jax.random.split)(jax.random.split(k_agents, r))
        naive.append((k_env, ks[:, 0], ks[:, 1]))
    k_out, (k_env_b, k_fast_b, k_slow_b) = engine._key_block(k, n, r)
    np.testing.assert_array_equal(jax.random.key_data(k_out),
                                  jax.random.key_data(kk))
    for w, (k_env, k_fast, k_slow) in enumerate(naive):
        np.testing.assert_array_equal(jax.random.key_data(k_env_b[w]),
                                      jax.random.key_data(k_env))
        np.testing.assert_array_equal(jax.random.key_data(k_fast_b[w]),
                                      jax.random.key_data(k_fast))
        np.testing.assert_array_equal(jax.random.key_data(k_slow_b[w]),
                                      jax.random.key_data(k_slow))


def test_categorical_matches_gumbel_argmax():
    """In-window sampling contract: argmax(log p + gumbel(key)) is bitwise
    ``jax.random.categorical(key, log p)`` (the legacy sampler)."""
    a_n = 20
    keys = jax.random.split(KEY, 64)
    probs = jax.random.dirichlet(jax.random.key(3), jnp.ones(a_n), (64,))
    logp = jnp.log(jnp.maximum(probs, 1e-30))
    legacy = jax.vmap(jax.random.categorical)(keys, logp)
    gum = jax.vmap(lambda k: jax.random.gumbel(k, (a_n,)))(keys)
    mega = jnp.argmax(logp + gum, axis=-1)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(mega))


# ------------------------------------------------------- engine-level parity
def test_mega_matches_legacy_clean():
    """Oracle megakernel vs per-tick engine: bit-equal actions, <=1e-4
    telemetry/env parity on the clean-scenario paper world."""
    _assert_rollouts_match(*_pair())


def test_mega_matches_legacy_masked():
    """Masked-telemetry scenario (PR-4 path): stale-hold, obs_mask and the
    gated error EMA all survive the window fusion."""
    r1, r2 = _pair(scenario="flaky-telemetry", t=25, r=6)
    assert np.asarray(r1.trace.obs_frac)[1:].min() < 1.0  # mask exercised
    _assert_rollouts_match(r1, r2)


def test_mega_blackout_scenario():
    """restart_blackout coupling (telemetry dies with the pods)."""
    _assert_rollouts_match(*_pair(scenario="scrape-blackout", t=25, r=5))


@pytest.mark.parametrize("topo", [TWO_TIER, five_tier_topology()],
                         ids=["k2", "k5"])
def test_mega_parity_across_topologies(topo):
    """Parity holds off the paper's K=3: K=2 (no pairwise policies) and the
    K=5 continuum (odd util factors, 37 actions, |S|=128)."""
    _assert_rollouts_match(*_pair(t=15, r=4, topology=topo))


def test_mega_odd_r_and_boundaries():
    """Odd fleet size + horizon not a multiple of the period (T=23 ends with
    a 3-tick remainder window: slow boundaries at 10/20, dwell-held tail)."""
    _assert_rollouts_match(*_pair(t=23, r=5))


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
        r2.final_carry, AifRouter(fused=True, mega=True).cfg)
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


# ----------------------------------------------- streaming slow boundaries
def _mega_carry(**kw):
    return run(Experiment(router="aif", fused=True, mega=True, **kw)
               ).final_carry


@pytest.mark.parametrize("kw", [
    dict(n_cells=6, n_windows=25),
    dict(n_cells=6, n_windows=25, scenario="flaky-telemetry"),
    dict(n_cells=5, n_windows=25, scenario="zone-outage"),
    dict(n_cells=4, n_windows=15, topology=TWO_TIER),
    dict(n_cells=4, n_windows=15, topology=five_tier_topology()),
    dict(n_cells=5, n_windows=23),
], ids=["clean", "masked", "chaos", "k2", "k5", "odd-r"])
def test_streaming_slow_step_matches_full_refresh(kw):
    """The streaming slow boundary (incremental cache advance) is the legacy
    from-scratch refresh, mathematically: a run-warm state's accumulated
    cache re-derives from its slots, and one more boundary produces
    bit-equal A / slot-hit stats and ulp-close cache tensors either way."""
    topo = kw.get("topology", default_topology())
    cfg = generative.AifConfig(topology=topo)
    state = _mega_carry(**kw)
    # the whole run's incremental colsum advances re-derive from the slots
    # alone (the slot-hit counts are sufficient statistics)
    ref = mega_core._refresh_cache(state.a_counts, state.slots, cfg)
    np.testing.assert_allclose(
        np.asarray(state.cache.colsum, np.float64),
        np.asarray(ref.colsum, np.float64),
        rtol=1e-5, atol=1e-5, err_msg="run-accumulated cache.colsum")
    np.testing.assert_array_equal(np.asarray(state.cache.coefact),
                                  np.asarray(ref.coefact))
    # one more boundary: streaming twin vs the legacy full-refresh twin —
    # the recomputed rows are bit-equal, the streamed colsum ulp-close
    ks = jax.random.split(jax.random.key(9), state.belief.shape[0])
    s_inc = mega_core.mega_slow_step(state, ks, cfg, incremental=True)
    s_full = mega_core.mega_slow_step(state, ks, cfg, incremental=False)
    np.testing.assert_array_equal(np.asarray(s_inc.a_counts),
                                  np.asarray(s_full.a_counts))
    np.testing.assert_array_equal(np.asarray(s_inc.slots.wcount),
                                  np.asarray(s_full.slots.wcount))
    for name in ("proj", "projsum", "logna", "qnproj", "sumqn", "coefw",
                 "coefact"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s_inc.cache, name)),
            np.asarray(getattr(s_full.cache, name)),
            err_msg=f"cache.{name}")
    np.testing.assert_allclose(
        np.asarray(s_inc.cache.colsum, np.float64),
        np.asarray(s_full.cache.colsum, np.float64),
        rtol=1e-5, atol=1e-5, err_msg="cache.colsum")


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
    pt = experiment_mod._make_aif(default_topology(), scfg, True, False,
                                  False)
    mg = experiment_mod._make_aif(default_topology(), scfg, True, False,
                                  True)
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
    dense = AifRouter(fused=True).init_carry(r)
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
    mg = AifRouter(fused=True, mega=True, use_pallas=True)
    with pytest.raises(ValueError, match="use_pallas"):
        engine._mega_rollout(mg, warm, batched.init_fluid_state(params),
                             env_step, 20, jax.random.key(0),
                             obs_masked=None, t0=None)


# ------------------------------------------------- chunked super-launches
def test_launch_periods_matches_single_launch():
    """Chunking the super-launch changes only the host dispatch granularity:
    every routing decision and the final factored state are bit-identical
    to the single launch.  The recorded raw-telemetry floats may differ by
    ulps — each chunk shape compiles its own XLA program, so the env EMA
    chain fuses differently — hence the tight allclose on the trace."""
    base = dict(router="aif", fused=True, mega=True, n_cells=6,
                n_windows=25)
    r1 = run(Experiment(**base))
    r2 = run(Experiment(**base, launch_periods=1))
    np.testing.assert_array_equal(np.asarray(r1.trace.actions),
                                  np.asarray(r2.trace.actions))
    np.testing.assert_array_equal(np.asarray(r1.trace.routing_weights),
                                  np.asarray(r2.trace.routing_weights))
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(r1.final_carry)[0],
            jax.tree_util.tree_flatten_with_path(r2.final_carry)[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(p))
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(r1.trace)[0],
            jax.tree_util.tree_flatten_with_path(r2.trace)[0]):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), atol=1e-4,
                                   err_msg=jax.tree_util.keystr(p))


def test_launch_periods_rejected_off_mega():
    with pytest.raises(ValueError, match="launch_periods"):
        run(Experiment(router="least_loaded", launch_periods=2, n_cells=2,
                       n_windows=10))


# ------------------------------------------------------------------- guards
def test_mega_horizon_exceeds_capacity_raises():
    cfg = generative.AifConfig(topology=default_topology(),
                               replay_capacity=16)
    with pytest.raises(ValueError, match="replay_capacity"):
        run(Experiment(router=AifRouter(cfg=cfg, fused=True, mega=True),
                       n_cells=2, n_windows=20))


def test_capacity_error_names_actionable_remedies():
    """A horizon just over capacity names every way out — raising the
    capacity, re-promoting between shorter rollouts, and chunking with
    ``launch_periods`` (satellite: actionable overflow message)."""
    cfg = generative.AifConfig(topology=default_topology(),
                               replay_capacity=16)
    with pytest.raises(ValueError, match="launch_periods"):
        mega_core.init_mega_state(cfg, 2, 17)
    with pytest.raises(ValueError, match="from_agent_state"):
        mega_core.init_mega_state(cfg, 2, 17)


# ------------------------------------------------------------- sharded mega
def test_mega_sharded_single_device_bit_identity():
    """``Experiment(mega=True, shard=...)`` on a 1-device mesh reproduces
    the unsharded super-launch bit-for-bit (router carry and env state),
    and the reducer's obs accumulator matches the dense trace."""
    topo = default_topology()
    r, t = 6, 25
    scfg, params, env_step = experiment_mod._build_world(
        topo, "paper-burst", r, t, 1.0, 0)
    router = experiment_mod._make_aif(topo, scfg, True, False, True)
    key = jax.random.key(0)
    s1, e1, tr1 = engine.rollout(
        router, None, batched.init_fluid_state(params), env_step, t, key)
    s2, e2, stats = engine.sharded_rollout(
        router, batched.init_fluid_state(params), env_step, t, key,
        shard=ShardSpec(devices=1), n_cells=r,
        reducer=FleetMetricsReducer(n_cells=r))
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path((s1, e1))[0],
            jax.tree_util.tree_flatten_with_path((s2, e2))[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(p))
    ref_obs = float(np.asarray(tr1.obs_frac)[1:].sum())
    assert abs(float(stats[2]) - ref_obs) < 1e-4


def test_mega_sharded_experiment_metrics_match_unsharded():
    base = dict(router="aif", fused=True, mega=True, n_cells=6,
                n_windows=25)
    r0 = run(Experiment(**base))
    r1 = run(Experiment(**base, shard=ShardSpec(devices=1)))
    assert abs(r1.success_pct - r0.success_pct) < 1e-5
    assert abs(r1.obs_frac - r0.obs_frac) < 1e-5
    np.testing.assert_allclose(r1.tier_share, r0.tier_share, atol=1e-5)
    np.testing.assert_allclose(r1.routed_share, r0.routed_share, atol=1e-5)
    assert r1.trace is None


@pytest.mark.skipif(jax.local_device_count() < 2,
                    reason="needs >=2 devices (CI runs this under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
def test_mega_sharded_multi_device_matches_unsharded():
    """Device-count invariance of the sharded super-launch: metrics agree
    with the unsharded engine to fp tolerance (EMA leaves may differ by
    ulps across shard widths)."""
    base = dict(router="aif", fused=True, mega=True, n_cells=6,
                n_windows=25)
    r0 = run(Experiment(**base))
    rn = run(Experiment(**base, shard="auto"))
    assert abs(rn.success_pct - r0.success_pct) < 1e-4
    assert abs(rn.obs_frac - r0.obs_frac) < 1e-4
    np.testing.assert_allclose(rn.tier_share, r0.tier_share, atol=1e-4)
    np.testing.assert_allclose(rn.routed_share, r0.routed_share, atol=1e-4)


def test_reducer_update_window_matches_sequential():
    """The sharded mega path's vectorized window deposit equals W sequential
    per-tick updates (same mass, same bins, same steady-tick gating)."""
    w, r_local, k = 4, 6, 3
    red = FleetMetricsReducer(n_cells=5)          # row 5 is a phantom pad
    stats0 = red.init(r_local, jnp.asarray(0))
    rng = np.random.default_rng(0)
    comp = jnp.asarray(rng.uniform(0.0, 5.0, (w, r_local, k)), jnp.float32)
    lat = jnp.asarray(rng.uniform(1e-3, 2.0, (w, r_local, k)), jnp.float32)
    p95 = jnp.asarray(rng.uniform(1e-3, 5.0, (w, r_local, k)), jnp.float32)
    of = jnp.asarray(rng.uniform(0.0, 1.0, (w, r_local)), jnp.float32)

    def ys(sl):
        return SimpleNamespace(
            env=SimpleNamespace(tier_completed=comp[sl], tier_latency_s=lat[sl],
                                tier_p95_s=p95[sl]),
            obs_frac=of[sl])

    seq = stats0
    for i in range(w):
        seq = red.update(seq, jnp.asarray(i), ys(i))
    vec = red.update_window(stats0, jnp.asarray(0), ys(slice(None)))
    for a, b in zip(seq, vec):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------- Pallas megakernel
def test_mega_pallas_interpret_matches_oracle():
    """Interpret-mode Pallas megakernel vs the XLA oracle twin: bit-equal
    actions, <=1e-4 everywhere (CI smoke for the kernel body)."""
    base = dict(router="aif", fused=True, mega=True, n_cells=2,
                n_windows=12)
    r1 = run(Experiment(**base))
    r2 = run(Experiment(**base, use_pallas=True))
    _assert_rollouts_match(r1, r2)


@pytest.mark.parametrize("kw,case", [
    (dict(scenario="zone-outage"), "forced_down"),
    (dict(scenario="ring-spillover"), "graph"),
    (dict(shard=ShardSpec(devices=1)), "row_block"),
], ids=["chaos", "graph", "sharded"])
def test_mega_pallas_refuses_unported_windows(kw, case):
    """``use_pallas=True`` never quietly runs the XLA oracle: a window the
    megakernel does not implement raises and names the case."""
    with pytest.raises(ValueError, match=case):
        run(Experiment(router="aif", mega=True, use_pallas=True, n_cells=4,
                       n_windows=10, **kw))


@pytest.mark.skipif(not on_tpu(), reason="compiled Pallas megakernel needs "
                    "a TPU backend (interpret-only on CPU)")
def test_mega_pallas_compiled_matches_oracle():
    """Accelerator-gated non-interpret parity (scaffolding for TPU CI)."""
    base = dict(router="aif", fused=True, mega=True, n_cells=8,
                n_windows=20)
    r1 = run(Experiment(**base))
    r2 = run(Experiment(**base, use_pallas=True))
    _assert_rollouts_match(r1, r2)
