"""Whole-window megakernel path: PRNG hoisting contracts, engine parity
(clean + masked telemetry, odd R, dwell/slow boundaries, K sweeps), mixed
precision, streaming slow boundaries, warm-fleet promotion, chunked
super-launches, the sharded super-launch, carry densification, Pallas
interpret parity and guards."""
import functools
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
from repro.kernels.efe import mega as mega_kernel
from repro.kernels.efe.efe import block_vmem_bytes
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


def _gathered_slow_step(state, k_slow, cfg):
    """The slow boundary in its earlier form: the replayed batch gathered
    draw by draw, the A update and the colsum delta summed over draws, and
    ``wcount`` bumped by a scatter-add.  Returns (a_counts, wcount, colsum).
    """
    topo, slots = cfg.topology, state.slots
    r, j = slots.action.shape
    batch = cfg.replay_batch
    size = jnp.minimum(state.t, j)
    idx = jax.vmap(lambda k, n: jax.random.randint(
        k, (batch,), 0, jnp.maximum(n, 1)))(k_slow, size)
    valid = (size > 0).astype(jnp.float32)[:, None] * jnp.ones((1, batch))

    def take(arr):
        ix = idx if arr.ndim == 2 else idx[..., None]
        return jnp.take_along_axis(arr, ix, axis=1)

    qp_b = take(slots.q_prev.astype(jnp.float32))
    qn_b = take(slots.q_next.astype(jnp.float32))
    onehot = (take(slots.obs_bins)[..., None] == jnp.arange(topo.max_bins))
    wgt = onehot * valid[..., None, None] * take(slots.obs_mask)[..., None]
    a_counts = state.a_counts + cfg.alpha_a * mega_core._einsum(
        "rnmb,rns->rmbs", wgt.astype(jnp.float32), qn_b)
    wcount = slots.wcount.at[jnp.arange(r)[:, None], idx].add(valid)
    w = jax.nn.sigmoid((take(slots.dt_since_change) - cfg.settle_midpoint_s)
                       / cfg.settle_scale_s) * valid
    oh = jax.nn.one_hot(take(slots.action), cfg.n_actions) * w[..., None]
    d_col = cfg.alpha_b * mega_core._einsum(
        "rna,rns->ras", oh * jnp.sum(qn_b, -1)[..., None], qp_b)
    return a_counts, wcount, state.cache.colsum + d_col


def _filled_state(cfg, r, j, t, *, masked=False, slot_dtype=jnp.float32,
                  seed=0):
    """A fleet state whose first ``min(t, j)`` slots hold random
    transitions, with earlier slot hits and a learnt A already in place."""
    topo = cfg.topology
    s, m = topo.n_states, topo.n_modalities
    ks = jax.random.split(jax.random.key(seed), 8)
    st = mega_core.init_mega_state(cfg, r, j, slot_dtype=slot_dtype)
    filled = (jnp.arange(j) < min(t, j))[None, :]

    def dist(k):
        q = jax.random.dirichlet(k, jnp.full((s,), 0.3), (r, j))
        return (q * filled[..., None]).astype(slot_dtype)

    obs_bins = jnp.stack([jax.random.randint(jax.random.fold_in(ks[2], i),
                                             (r, j), 0, nb)
                          for i, nb in enumerate(topo.n_bins)], axis=-1)
    obs_mask = (jax.random.bernoulli(ks[3], 0.7, (r, j, m)).astype(
        jnp.float32) if masked else jnp.ones((r, j, m), jnp.float32))
    slots = st.slots._replace(
        q_prev=dist(ks[0]), q_next=dist(ks[1]), obs_bins=obs_bins,
        obs_mask=obs_mask,
        action=jax.random.randint(ks[4], (r, j), 0, cfg.n_actions),
        dt_since_change=jax.random.uniform(ks[5], (r, j), maxval=60.0),
        wcount=jax.random.randint(ks[6], (r, j), 0, 4).astype(jnp.float32)
        * filled)
    a_counts = st.a_counts * jax.random.uniform(ks[7], st.a_counts.shape,
                                                minval=1.0, maxval=3.0)
    return st._replace(a_counts=a_counts, slots=slots,
                       cache=mega_core._refresh_cache(a_counts, slots, cfg),
                       t=jnp.full((r,), t, jnp.int32))


@pytest.mark.parametrize("topology,r,j,t,batch,kw", [
    (None, 6, 40, 25, 100, {}),
    (None, 6, 40, 0, 100, {}),
    (None, 6, 40, 40, 100, dict(masked=True)),
    (TWO_TIER, 4, 30, 30, 100, {}),
    (five_tier_topology(), 4, 30, 20, 100, {}),
    (None, 5, 23, 23, 100, {}),
    (None, 6, 40, 40, 100, dict(slot_dtype=jnp.bfloat16)),
    (None, 6, 60, 60, 3, dict(masked=True)),
    (None, 6, 61, 50, 3, dict(masked=True)),
    (None, 6, 61, 61, 3, dict(slot_dtype=jnp.bfloat16)),
], ids=["repeated-draws", "t0", "masked", "k2", "k5", "odd-r", "bf16-slots",
        "small-batch-masked", "long-tape", "long-tape-bf16-slots"])
def test_replay_histogram_matches_gathered_batch(topology, r, j, t, batch, kw):
    """Folding the replayed batch as its slot-hit histogram is the gathered
    batch summed over slots: the same draws bump ``wcount`` bit for bit, and
    the A update and the colsum delta agree up to float32 association, on
    tapes short and long against the batch.  The step gathers nothing."""
    cfg = generative.AifConfig(topology=topology or default_topology(),
                               replay_batch=batch)
    state = _filled_state(cfg, r, j, t, **kw)
    ks = jax.random.split(jax.random.key(7), r)
    a_ref, wcount_ref, colsum_ref = _gathered_slow_step(state, ks, cfg)
    step = functools.partial(mega_core.mega_slow_step, cfg=cfg)
    assert "gather" not in str(jax.make_jaxpr(step)(state, ks))
    new = step(state, ks)
    np.testing.assert_array_equal(np.asarray(new.slots.wcount),
                                  np.asarray(wcount_ref))
    drawn = np.asarray(new.slots.wcount - state.slots.wcount)
    assert drawn.sum() == (r * batch if t else 0)
    if t and batch > t:
        assert drawn.max() > 1
    np.testing.assert_allclose(np.asarray(new.a_counts),
                               np.asarray(a_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new.cache.colsum),
                               np.asarray(colsum_ref), rtol=1e-6)


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
def _router_world(r, t, scenario="paper-burst"):
    topo = default_topology()
    scfg, params, env_step = experiment_mod._build_world(
        topo, scenario, r, t, 1.0, 0)
    return experiment_mod._make_aif(topo, scfg, False, True, True), env_step


def _vmem_with(cfg, t, slot_dtype, chunk, n_resident):
    """The VMEM ceiling at which a launch over a ``t``-slot tape holds
    ``n_resident`` whole chunks resident and streams the rest: the other
    blocks, the two buffers of each tape operand and the resident blocks,
    all priced by hand."""
    s = cfg.topology.n_states
    widths = mega_kernel.tape_widths(cfg, slot_dtype).values()
    fixed = mega_kernel.mega_vmem_bytes(
        mega_kernel._fixed_blocks(cfg, 10, False),
        block_vmem_bytes((8, chunk, s), jnp.float32))
    buffers = sum(block_vmem_bytes((2, 8, chunk, w), d) for w, d in widths)
    per_chunk = 2 * sum(block_vmem_bytes((8, chunk, w), d) for w, d in widths)
    return fixed + buffers + n_resident * per_chunk


def _kernel_run(experiment, chunk, **kernel_kw):
    """``experiment`` through the interpret-mode kernel folding its tape in
    ``chunk``-slot chunks (``kernel_kw``: more keywords of the launch)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mega_kernel, "mega_window_pallas", functools.partial(
            mega_kernel.mega_window_pallas, slot_chunk=chunk, **kernel_kw))
        jax.clear_caches()
        try:
            return run(experiment)
        finally:
            jax.clear_caches()


def _assert_bit_equal(r1, r2):
    for name in ("final_carry", "trace"):
        for x, y in zip(jax.tree_util.tree_leaves(getattr(r1, name)),
                        jax.tree_util.tree_leaves(getattr(r2, name))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


@pytest.mark.parametrize("slot_dtype,n_resident", [
    ("float32", 0), ("float32", 1), ("bfloat16", 0), ("bfloat16", 2)],
    ids=["f32-streamed", "f32-prefix", "bf16-streamed", "bf16-prefix"])
def test_mega_pallas_streamed_tape_matches_oracle(slot_dtype, n_resident):
    """The kernel with its tape streamed from HBM (every chunk, or past a
    resident prefix), in interpret mode: against the XLA oracle, bit-equal
    actions and <=1e-4 everywhere; against the kernel holding the whole
    tape resident in the same chunks, bit-equal throughout (same
    arithmetic, same order; a skipped unfilled chunk would add zeros).  A
    60-slot tape in 16-slot chunks ends in a 12-slot chunk, which the last
    window (t0=50) reads."""
    r, t, chunk = 3, 60, 16
    router, _ = _router_world(r, t)
    limit = _vmem_with(router.cfg, t, slot_dtype, chunk, n_resident)
    plan = mega_kernel.tape_plan(router.cfg, t, 10, jnp.dtype(slot_dtype),
                                 False, slot_chunk=chunk, vmem_limit=limit)
    assert (plan.j_res, plan.j_chunk) == (n_resident * chunk, chunk)
    base = dict(router="aif", fused=True, mega=True, n_cells=r,
                n_windows=t, mega_slot_dtype=slot_dtype)
    kernel = Experiment(**base, use_pallas=True)
    streamed = _kernel_run(kernel, chunk, vmem_limit=limit)
    _assert_rollouts_match(run(Experiment(**base)), streamed)
    _assert_bit_equal(_kernel_run(kernel, chunk), streamed)


@pytest.fixture(scope="module")
def oracle_60():
    """The XLA oracle's run of three cells over 60 windows."""
    return run(Experiment(router="aif", fused=True, mega=True, n_cells=3,
                          n_windows=60))


@pytest.mark.parametrize("chunk", [16, 20, 40],
                         ids=["tail", "on-boundaries", "one-whole-chunk"])
def test_mega_pallas_resident_fold_skips_unfilled_chunks(oracle_60, chunk):
    """The kernel holding a 60-slot tape resident folds only the chunks
    that hold a filled slot (slot < t0), in interpret mode: bit-equal to
    the kernel streaming the whole tape in the same chunks, which follows
    the same rule, and bit-equal actions and <=1e-4 everywhere against the
    XLA oracle, which folds every slot.  Windows start at t0 = 0 (nothing
    folded), 10, ..., 50.  16-slot chunks: 3 whole and a 12-slot tail
    [48, 60), t0=10 inside the first chunk and t0=50 reaching the tail;
    20-slot chunks: 3 whole, t0=20 and 40 on chunk boundaries; 40-slot
    chunks: one whole chunk (its own path in the kernel), skipped at t0=0,
    and a 20-slot tail."""
    r, t = 3, 60
    router, _ = _router_world(r, t)
    limit = _vmem_with(router.cfg, t, jnp.float32, chunk, 0)
    assert (mega_kernel.tape_plan(router.cfg, t, 10, jnp.dtype(jnp.float32),
                                  False, slot_chunk=chunk).j_res == t)
    assert (mega_kernel.tape_plan(router.cfg, t, 10, jnp.dtype(jnp.float32),
                                  False, slot_chunk=chunk,
                                  vmem_limit=limit).j_res == 0)
    kernel = Experiment(router="aif", fused=True, mega=True, n_cells=r,
                        n_windows=t, use_pallas=True)
    resident = _kernel_run(kernel, chunk)
    _assert_rollouts_match(oracle_60, resident)
    _assert_bit_equal(resident, _kernel_run(kernel, chunk, vmem_limit=limit))


@pytest.mark.parametrize("j,j_res", [(600, 600), (3600, 2048)],
                         ids=["r2048", "t3600"])
def test_live_chunks_agree_with_the_hosts_live_slots(j, j_res):
    """The kernel's live-chunk rule and the host's count of live slots
    agree at every t0 of both cells' tapes, for the resident prefix and
    the streamed rest."""
    router, _ = _router_world(8, 10)
    jc = mega_kernel.SLOT_CHUNK
    assert mega_kernel.tape_plan(router.cfg, j, 10, jnp.dtype(jnp.float32),
                                 False).j_res == j_res
    t0 = np.arange(j + 1)
    for start, stop in ((0, j_res), (j_res, j)):
        whole, tail_live = mega_kernel.live_chunks(
            jnp.asarray(t0, jnp.int32), start, stop, jc)
        got = (np.asarray(whole) * jc
               + np.where(tail_live, (stop - start) % jc, 0))
        want = [mega_kernel._live_slots(int(t), start, stop, jc) for t in t0]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("t,n_resident", [(40, None), (60, 0), (60, 2)],
                         ids=["resident", "streamed", "prefix"])
def test_tape_plan_prices_every_block_of_the_launch(monkeypatch, t,
                                                    n_resident):
    """The VMEM a launch asks for is what its pipelined blocks (each
    double-buffered), its scratch buffers and four chunk temporaries take,
    read off the ``pallas_call`` it makes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r, chunk = 3, 16
    router, env_step = _router_world(r, t)
    cfg, fl = router.cfg, env_step.fluid
    limit = (mega_kernel.VMEM_LIMIT if n_resident is None
             else _vmem_with(cfg, t, jnp.float32, chunk, n_resident))
    seen = {}
    real = pl.pallas_call

    def spy(kernel, **kw):
        call = real(kernel, **kw)

        def go(*operands):
            seen.update(kw, operands=operands)
            return call(*operands)
        return go
    monkeypatch.setattr(pl, "pallas_call", spy)
    w, m, k = router.period, router.n_modalities, router.n_tiers
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    args = (jax.eval_shape(lambda: mega_core.init_mega_state(cfg, r, t)),
            jax.eval_shape(lambda: batched.init_fluid_state(fl.params)),
            tuple(sds(x, f32) for x in ((r, m), (r, k), (r, k), (r, k),
                                         (r, m))),
            fl.params, sds((w, r), f32), sds((w, r, k), f32), None,
            jax.eval_shape(lambda: jax.random.split(KEY, w)),
            sds((w, r, cfg.n_actions), f32), sds((), jnp.int32))
    jax.eval_shape(functools.partial(
        mega_kernel.mega_window_pallas, cfg=cfg, disc=router.resolved_disc,
        util_edges=router.resolved_util_edges,
        util_period=router.util_period, dt=fl.dt,
        scrape_every=fl.scrape_every, restart_blackout=False,
        emits_mask=False, interpret=True, slot_chunk=chunk,
        vmem_limit=limit), *args)
    plan = mega_kernel.tape_plan(cfg, t, w, jnp.dtype(f32), False,
                                 slot_chunk=chunk, vmem_limit=limit)
    pipelined = [(sp.block_shape, x.dtype)
                 for sp, x in zip(seen["in_specs"], seen["operands"])
                 if sp.block_shape is not None]
    pipelined += [(sp.block_shape, o.dtype)
                  for sp, o in zip(seen["out_specs"], seen["out_shape"])]
    scratch = sum(block_vmem_bytes(sc.shape, sc.dtype)
                  for sc in seen["scratch_shapes"]
                  if sc.memory_space == pltpu.VMEM)
    want = (2 * sum(block_vmem_bytes(sh, d) for sh, d in pipelined)
            + 4 * block_vmem_bytes((8, chunk, cfg.topology.n_states), f32)
            + scratch)
    assert plan.vmem == want
    assert seen["compiler_params"].vmem_limit_bytes == want <= limit
    assert plan.j_res == (t if n_resident is None else n_resident * chunk)
    assert bool(scratch) == (plan.j_res < t)


def test_tape_plan_refuses_only_what_cannot_fit():
    """A long tape no longer bounds the launch (only the replay capacity
    bounds the horizon); a ceiling below the other blocks and the tape
    buffers alone is refused."""
    router, _ = _router_world(8, 10)
    cfg = router.cfg
    plan = mega_kernel.tape_plan(cfg, 5000, 10, jnp.dtype(jnp.float32), False)
    assert 0 < plan.j_res < 5000 and plan.vmem <= mega_kernel.VMEM_LIMIT
    assert plan.j_res % plan.j_chunk == 0
    floor = _vmem_with(cfg, 5000, jnp.float32, mega_kernel.SLOT_CHUNK, 0)
    assert mega_kernel.tape_plan(cfg, 5000, 10, jnp.dtype(jnp.float32), False,
                                 vmem_limit=floor).j_res == 0
    with pytest.raises(ValueError, match="non-tape blocks"):
        mega_kernel.tape_plan(cfg, 5000, 10, jnp.dtype(jnp.float32), False,
                              vmem_limit=floor - 1)


def test_tape_bytes_matches_hand_count():
    """A 60-slot float32 tape, 16 slots resident and 16-slot chunks: the
    streamed part [16, 60) is two whole chunks and a 12-slot one.

    Bytes a slot: q_prev, q_next 243·4 = 972 each, qnproj|sumqn 17·4 = 68,
    coefact 20·4 = 80: 2,092 in all; the prior reads coefact, q_prev, q_next
    (2,024), the EFE q_prev, coefact, qnproj (1,120).  A window reads the
    16 resident slots once (33,472) and each live streamed slot at its 10
    ticks and 2 selecting ticks (10·2,024 + 2·1,120 = 22,480).  Live slots
    by t0: 0, 10 -> 0; 20, 30 -> 16 (one chunk); 40 -> 32; 50 -> 32 + 12
    (the short chunk [48, 60) holds slot 49).  Per row 6·33,472 +
    (16 + 16 + 32 + 44)·22,480 = 2,628,672; eight padded rows."""
    router, _ = _router_world(3, 60)
    cfg = router.cfg
    limit = _vmem_with(cfg, 60, jnp.float32, 16, 1)
    got = sum(mega_kernel.tape_bytes(cfg, 3, 60, t0, 10, jnp.float32, False,
                                     slot_chunk=16, vmem_limit=limit)
              for t0 in range(0, 60, 10))
    assert got == 8 * 2_628_672


def test_folded_slots_matches_hand_count():
    """The 60-slot float32 tape in 16-slot chunks, held whole (3 chunks and
    a 12-slot tail) or behind a 16-slot resident prefix: the same slots are
    live either way.  Live slots by t0: 0 -> 0; 10 -> 16; 20, 30 -> 32;
    40 -> 48; 50 -> 60 (the tail [48, 60) holds slot 49).  (With the
    prefix: 16 resident from t0=10 on, and the streamed 0, 0, 16, 16, 32,
    44 of :func:`test_tape_bytes_matches_hand_count`.)  188 slots, each
    folded at 10 ticks for eight padded rows."""
    router, _ = _router_world(3, 60)
    cfg = router.cfg
    for limit in (mega_kernel.VMEM_LIMIT, _vmem_with(cfg, 60, jnp.float32,
                                                     16, 1)):
        got = sum(mega_kernel.folded_slots(cfg, 3, 60, t0, 10, jnp.float32,
                                           False, slot_chunk=16,
                                           vmem_limit=limit)
                  for t0 in range(0, 60, 10))
        assert got == 8 * 10 * 188


@pytest.fixture(scope="module")
def dispatched():
    """A kernel run and an oracle run of a resident 12-slot tape (two
    windows: 10 ticks from t0=0, 2 from t0=10): each counter's change over
    each run, and the arguments of every ``run.dispatch`` span."""
    from repro import obs
    seen = []
    real = obs.span

    def spy(name, **args):
        if name == "run.dispatch":
            seen.append(args)
        return real(name, **args)
    deltas = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs, "span", spy)
        for use_pallas in (True, False):
            before = obs.counters()
            run(Experiment(router="aif", mega=True, use_pallas=use_pallas,
                           n_cells=3, n_windows=12))
            after = obs.counters()
            deltas.append({k: after[k] - before.get(k, 0) for k in after})
    return deltas, seen


def test_dispatch_counts_the_tape_bytes(dispatched):
    """``tape_bytes`` on the ``run.dispatch`` span and in the counter: a
    resident 12-slot tape read once per window, two windows, eight padded
    rows of 2,092 bytes a slot; none off the kernel."""
    deltas, seen = dispatched
    assert [d.get("tape_bytes", 0) for d in deltas] == [2 * 8 * 12 * 2_092, 0]
    assert [a.get("tape_bytes") for a in seen] == [2 * 8 * 12 * 2_092, None]


def test_dispatch_counts_the_folded_slots(dispatched):
    """``folded_slots`` on the ``run.dispatch`` span and in the counter:
    the window at t0=0 folds nothing, the one at t0=10 the tape's one
    12-slot chunk at its 2 ticks, for eight padded rows; none off the
    kernel."""
    deltas, seen = dispatched
    assert [d.get("folded_slots", 0) for d in deltas] == [12 * 2 * 8, 0]
    assert [a.get("folded_slots") for a in seen] == [12 * 2 * 8, None]


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
