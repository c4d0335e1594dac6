"""Mega engine launches: chunked super-launches, capacity guards, the
sharded super-launch and its on-device metric reducer, and the Pallas
megakernel's whole-rollout smoke and the windows it refuses."""
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
from repro.core.topology import default_topology
from repro.envsim import batched

from mega_helpers import _assert_rollouts_match


# ------------------------------------------------- chunked super-launches
def test_launch_periods_matches_single_launch():
    """Chunking the super-launch changes only the host dispatch granularity:
    every routing decision and the final factored state are bit-identical
    to the single launch.  The recorded raw-telemetry floats may differ by
    ulps — each chunk shape compiles its own XLA program, so the env EMA
    chain fuses differently — hence the tight allclose on the trace."""
    base = dict(router="aif", mega=True, n_cells=6,
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
        run(Experiment(router=AifRouter(cfg=cfg, mega=True),
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
    router = experiment_mod._make_aif(topo, scfg, False, True)
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
    base = dict(router="aif", mega=True, n_cells=6,
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
    base = dict(router="aif", mega=True, n_cells=6,
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


# ------------------------------------------------------ megakernel launches
def test_mega_pallas_interpret_matches_oracle():
    """Interpret-mode Pallas megakernel vs the XLA oracle twin: bit-equal
    actions, <=1e-4 everywhere (CI smoke for the kernel body)."""
    base = dict(router="aif", mega=True, n_cells=2,
                n_windows=12)
    r1 = run(Experiment(**base))
    r2 = run(Experiment(**base, use_pallas=True))
    _assert_rollouts_match(r1, r2)


@pytest.mark.parametrize("kw,case", [
    (dict(scenario="zone-outage"), "forced_down"),
    (dict(scenario="ring-spillover"), "graph"),
    (dict(shard=ShardSpec(devices=1)), "row_block"),
    (dict(mega=False), "mega=True"),
], ids=["chaos", "graph", "sharded", "per-tick"])
def test_mega_pallas_refuses_unported_windows(kw, case):
    """``use_pallas=True`` never quietly runs the XLA oracle: a window the
    megakernel does not implement raises and names the case, and so does
    the per-tick engine, which has no kernel to dispatch."""
    with pytest.raises(ValueError, match=case):
        run(Experiment(**{**dict(router="aif", mega=True, use_pallas=True,
                                 n_cells=4, n_windows=10), **kw}))
