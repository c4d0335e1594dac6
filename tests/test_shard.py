"""Device-sharded engine coverage: ShardSpec semantics, single-device
bit-identity with the unsharded engine, multi-device parity of the reduced
metrics (run the 4-way cases under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``), odd-R padding
correctness and the sharded world-builder's memoization key."""
import jax
import numpy as np
import pytest

from repro import api
from repro.api import engine, experiment as experiment_mod
from repro.api import shard as shard_mod
from repro.core.topology import default_topology
from repro.envsim import SimConfig, batched, scenarios

multi_device = pytest.mark.skipif(
    jax.local_device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")

R, T = 6, 40


def _world(r, scenario="paper-burst", r_pad=None):
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, T, seed=0)
    if r_pad is not None:
        sc = scenarios.pad_scenario(sc, r_pad)
        r = r_pad
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    return params, batched.make_scenario_env_step(params, sc)


# ---------------------------------------------------------------- ShardSpec
def test_shardspec_validation():
    with pytest.raises(ValueError, match="pad policy"):
        api.ShardSpec(pad="bogus")
    with pytest.raises(ValueError, match="devices"):
        api.ShardSpec(devices=0)
    with pytest.raises(ValueError, match="devices"):
        api.ShardSpec(devices=10_000).n_devices()
    assert api.ShardSpec(devices=1).padded(7) == (7, 7)
    assert shard_mod.resolve(None) is None
    assert shard_mod.resolve("auto") == api.ShardSpec()
    spec = api.ShardSpec(devices=1)
    assert shard_mod.resolve(spec) is spec
    with pytest.raises(ValueError, match="shard must be"):
        shard_mod.resolve(4)
    # hashable: usable as a static jit argument and a dataclass field
    assert hash(api.ShardSpec()) == hash(api.ShardSpec())


def test_padding_math():
    spec = api.ShardSpec(devices=1)
    assert spec.padded(1) == (1, 1)
    assert spec.padded(8) == (8, 8)


@multi_device
def test_padding_math_multi():
    spec = api.ShardSpec(devices=4)
    assert spec.padded(8) == (8, 2)
    assert spec.padded(7) == (8, 2)
    with pytest.raises(ValueError, match="not divisible"):
        api.ShardSpec(devices=4, pad="strict").padded(7)


# ------------------------------------------------- engine guards + identity
def test_sharded_rollout_rejects_shard_blind_env():
    def naked_env(est, w, t, k):
        return est, None

    with pytest.raises(ValueError, match="supports_shard"):
        engine.sharded_rollout(
            api.LeastLoadedRouter(tiers=3), (), naked_env, 4,
            jax.random.key(0), shard=api.ShardSpec(devices=1), n_cells=4,
            reducer=api.FleetMetricsReducer(n_cells=4))


def test_sharded_rollout_rejects_unpadded_state():
    params, env_step = _world(R)
    with pytest.raises(ValueError, match="padded fleet size"):
        engine.sharded_rollout(
            api.LeastLoadedRouter(tiers=3),
            batched.init_fluid_state(params), env_step, T,
            jax.random.key(0), shard=api.ShardSpec(devices=1), n_cells=R + 1,
            reducer=api.FleetMetricsReducer(n_cells=R + 1))


def test_single_device_bit_identity():
    """A 1-device mesh reproduces the unsharded engine's final env state
    bit-for-bit (same PRNG stream, same program order)."""
    params, env_step = _world(R)
    router = api.LeastLoadedRouter(tiers=3)
    _, est_ref, trace = engine.rollout(
        router, router.init_carry(R), batched.init_fluid_state(params),
        env_step, T, jax.random.key(0))
    _, est_sh, stats = engine.sharded_rollout(
        router, batched.init_fluid_state(params), env_step, T,
        jax.random.key(0), shard=api.ShardSpec(devices=1), n_cells=R,
        reducer=api.FleetMetricsReducer(n_cells=R))
    for name, a, b in zip(est_ref._fields, est_ref, est_sh):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # the reducer's obs accumulator equals the trace's steady-tick total
    ref_obs = float(np.asarray(trace.obs_frac)[1:].sum())
    assert abs(float(stats[2]) - ref_obs) < 1e-4


def test_single_device_experiment_metrics_match_unsharded():
    r0 = api.run(api.Experiment(router="least_loaded", n_cells=R,
                                n_windows=T))
    r1 = api.run(api.Experiment(router="least_loaded", n_cells=R,
                                n_windows=T, shard=api.ShardSpec(devices=1)))
    assert abs(r1.success_pct - r0.success_pct) < 1e-5
    assert abs(r1.obs_frac - r0.obs_frac) < 1e-5
    np.testing.assert_allclose(r1.tier_share, r0.tier_share, atol=1e-5)
    np.testing.assert_allclose(r1.routed_share, r0.routed_share, atol=1e-5)
    assert r1.restarts == r0.restarts
    # histogram quantiles are quantized to ~±1.6 %; per-cell-mean quantiles
    # are a different (unquantized) statistic — order-of-magnitude agreement
    assert 0.5 < r1.p95_ms / max(r0.p95_ms, 1e-9) < 2.0
    assert r1.cells_per_device == R
    assert r1.trace is None


# ------------------------------------------------------- multi-device parity
@multi_device
@pytest.mark.parametrize("router,scenario", [
    ("aif", "paper-burst"),
    ("aif", "flaky-telemetry"),
    ("thompson", "paper-burst"),
    ("thompson", "flaky-telemetry"),
    ("least_loaded", "paper-burst"),
    ("least_loaded", "flaky-telemetry"),
])
def test_four_device_parity(router, scenario):
    """Reduced metrics are invariant to the device count (±1e-5): the same
    experiment on a 1-way and a 4-way mesh, plus the unsharded reference
    for everything the final env state determines."""
    kw = dict(router=router, scenario=scenario, n_cells=R, n_windows=T)
    r0 = api.run(api.Experiment(**kw))
    r1 = api.run(api.Experiment(**kw, shard=api.ShardSpec(devices=1)))
    r4 = api.run(api.Experiment(**kw, shard=api.ShardSpec(devices=4)))
    assert r4.cells_per_device == R // 4 + 1  # padded: ceil(6/4) = 2
    for a, b in [(r4, r1), (r4, r0)]:
        assert abs(a.success_pct - b.success_pct) < 1e-5
        assert abs(a.obs_frac - b.obs_frac) < 1e-5
        np.testing.assert_allclose(a.tier_share, b.tier_share, atol=1e-5)
        np.testing.assert_allclose(a.routed_share, b.routed_share, atol=1e-5)
    # the histogram quantiles must agree across meshes (same statistic)
    assert abs(r4.p50_ms - r1.p50_ms) <= 1e-5 * max(r1.p50_ms, 1.0)
    assert abs(r4.p95_ms - r1.p95_ms) <= 1e-5 * max(r1.p95_ms, 1.0)


@multi_device
def test_odd_r_padding_inert():
    """R=7 on 4 devices pads one phantom cell: real rows bit-identical to
    the 1-way mesh, phantom rows see zero traffic and zero restarts."""
    r_true = 7
    spec = api.ShardSpec(devices=4)
    r_pad, _ = spec.padded(r_true)
    assert r_pad == 8
    router = api.LeastLoadedRouter(tiers=3)
    reducer = api.FleetMetricsReducer(n_cells=r_true)

    params1, env1 = _world(r_true)
    _, est1, stats1 = engine.sharded_rollout(
        router, batched.init_fluid_state(params1), env1, T,
        jax.random.key(0), shard=api.ShardSpec(devices=1), n_cells=r_true,
        reducer=reducer)

    params4, env4 = _world(r_true, r_pad=r_pad)
    _, est4, stats4 = engine.sharded_rollout(
        router, batched.init_fluid_state(params4), env4, T,
        jax.random.key(0), shard=spec, n_cells=r_true, reducer=reducer)

    for name, a, b in zip(est1._fields, est1, est4):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a, b[:r_true]), name
    pad = jax.tree_util.tree_map(lambda x: np.asarray(x)[r_true:], est4)
    assert pad.n_requests.sum() == 0.0
    assert pad.tier_requests.sum() == 0.0
    assert pad.n_restarts.sum() == 0.0
    # reductions identical: the phantom cell contributed nothing
    for s1, s4 in zip(stats1, stats4):
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s4),
                                   rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------- memoization key
def test_padded_world_memo_key_includes_shard():
    """The sharded world-builder cache must key on (r_pad, n_devices) — a
    re-padded world must not replay a stale env_step closure."""
    topo = default_topology()
    a = experiment_mod._build_world_padded(
        topo, "paper-burst", R, 10, 1.0, 0, R, 1)
    b = experiment_mod._build_world_padded(
        topo, "paper-burst", R, 10, 1.0, 0, R, 1)
    c = experiment_mod._build_world_padded(
        topo, "paper-burst", R, 10, 1.0, 0, R + 2, 4)
    assert a[2] is b[2]          # cache hit: identical env_step closure
    assert a[2] is not c[2]      # different padding -> different world
    r_pad_leaf = jax.tree_util.tree_leaves(
        batched.init_fluid_state(c[1]))[0]
    assert r_pad_leaf.shape[0] == R + 2
