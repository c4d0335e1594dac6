"""repro.api coverage: baseline parity vs the NumPy twins, engine rollouts,
the AIF adapter's bit-identity with the old entry point, Experiment/compare,
and the deprecation / kwarg-validation shims."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, baselines, core
from repro.api.router import RouterObs
from repro.core import fleet
from repro.envsim import SimConfig, batched, scenarios

CFG = core.AifConfig()


def _obs(raw_obs, tier_queue=None, tier_up=None, tier_util=None, t_idx=0):
    raw_obs = jnp.asarray(raw_obs, jnp.float32)
    r = raw_obs.shape[0]
    k = 3 if tier_queue is None else np.asarray(tier_queue).shape[-1]
    return RouterObs(
        raw_obs=raw_obs,
        tier_utilization=jnp.zeros((r, k)) if tier_util is None
        else jnp.asarray(tier_util, jnp.float32),
        tier_up=jnp.ones((r, k)) if tier_up is None
        else jnp.asarray(tier_up, jnp.float32),
        tier_queue=jnp.zeros((r, k)) if tier_queue is None
        else jnp.asarray(tier_queue, jnp.float32),
        t_idx=jnp.asarray(t_idx, jnp.int32))


def _snapshot(p95=0.0, err=0.0, queue=None, up=None):
    return types.SimpleNamespace(
        p95_latency_s=p95, error_rate=err,
        tier_queue_depth=None if queue is None else np.asarray(queue, float),
        tier_up=None if up is None else np.asarray(up, float))


# ------------------------------------------------------- deterministic parity
def test_uniform_parity():
    ref = baselines.UniformRouter()
    router = api.UniformRouter()
    _, w, info = router.step((), _obs(np.zeros((4, 4))), None, None)
    assert w.shape == (4, 3)
    np.testing.assert_allclose(np.asarray(w), ref(None)[None].repeat(4, 0),
                               atol=1e-7)
    assert not np.any(np.asarray(info.unstable))


def test_capacity_parity():
    ref = baselines.CapacityRouter()
    router = api.CapacityRouter()
    _, w, _ = router.step((), _obs(np.zeros((2, 4))), None, None)
    np.testing.assert_allclose(np.asarray(w), ref(None)[None].repeat(2, 0),
                               atol=1e-7)


def test_round_robin_parity():
    ref = baselines.RoundRobinRouter()
    router = api.RoundRobinRouter()
    carry = router.init_carry(1)
    for t in range(7):
        carry, w, info = router.step(carry, _obs(np.zeros((1, 4))), None,
                                     None)
        np.testing.assert_allclose(np.asarray(w[0]), ref(None), atol=1e-7)
        assert int(info.action[0]) == t % 3


def test_least_loaded_parity():
    rng = np.random.default_rng(3)
    ref = [baselines.LeastLoadedRouter() for _ in range(3)]
    router = api.LeastLoadedRouter()
    for _ in range(20):
        queue = rng.uniform(0.0, 50.0, size=(3, 3))
        up = (rng.random((3, 3)) > 0.2).astype(float)
        _, w, _ = router.step((), _obs(np.zeros((3, 4)), tier_queue=queue,
                                       tier_up=up), None, None)
        for i in range(3):
            np.testing.assert_allclose(
                np.asarray(w[i]),
                ref[i](_snapshot(queue=queue[i], up=up[i])), atol=1e-6)


def test_least_loaded_all_down_falls_back_uniform():
    router = api.LeastLoadedRouter()
    _, w, _ = router.step((), _obs(np.zeros((1, 4)),
                                   tier_queue=np.zeros((1, 3)),
                                   tier_up=np.zeros((1, 3))), None, None)
    np.testing.assert_allclose(np.asarray(w[0]), np.full(3, 1 / 3), atol=1e-6)


# ------------------------------------------------------------- bandit parity
def test_ucb_parity_exact():
    """UCB1 is deterministic: identical observation sequences must produce
    the identical arm trajectory and weight rows as the NumPy twin — for
    every cell of an R=2 fleet fed two different streams."""
    rng = np.random.default_rng(11)
    refs = [baselines.UcbRouter() for _ in range(2)]
    router = api.UcbRouter()
    carry = router.init_carry(2)
    for _ in range(30):
        p95 = rng.uniform(0.0, 8.0, size=2)
        err = rng.uniform(0.0, 0.5, size=2)
        raw = np.zeros((2, 4), np.float32)
        raw[:, 0], raw[:, 3] = p95, err
        carry, w, info = router.step(carry, _obs(raw), None, None)
        for i in range(2):
            w_ref = refs[i](_snapshot(p95=float(p95[i]), err=float(err[i])))
            assert int(info.action[i]) == refs[i].active_arm
            np.testing.assert_allclose(np.asarray(w[i]), w_ref, atol=1e-6)
    for i in range(2):
        np.testing.assert_allclose(np.asarray(carry.counts[i]),
                                   refs[i].counts, atol=1e-6)
        np.testing.assert_allclose(np.asarray(carry.sums[i]),
                                   refs[i].sums, rtol=1e-5, atol=1e-6)


class _FakeRng:
    """Replays the JAX router's standard-normal draws into the NumPy twin."""

    def __init__(self, eps_seq):
        self._eps = iter(eps_seq)

    def normal(self, loc, scale):
        return np.asarray(loc) + np.asarray(scale) * next(self._eps)


def test_thompson_parity_matched_draws():
    """With the PRNG draws matched (the NumPy twin replays the JAX noise),
    Thompson sampling is deterministic too: posterior tables and the arm
    trajectory must agree exactly."""
    rng = np.random.default_rng(5)
    router = api.ThompsonRouter()
    carry = router.init_carry(1)
    n_arms = carry.mu.shape[1]
    keys = jax.random.split(jax.random.key(17), 25)
    ref = baselines.ThompsonRouter()
    ref.rng = _FakeRng([np.asarray(jax.random.normal(k, (n_arms,)))
                        for k in keys])
    for t in range(25):
        p95 = float(rng.uniform(0.0, 8.0))
        err = float(rng.uniform(0.0, 0.5))
        raw = np.zeros((1, 4), np.float32)
        raw[0, 0], raw[0, 3] = p95, err
        carry, w, info = router.step(carry, _obs(raw), None, keys[t][None])
        w_ref = ref(_snapshot(p95=p95, err=err))
        assert int(info.action[0]) == ref.active_arm
        np.testing.assert_allclose(np.asarray(w[0]), w_ref, atol=1e-6)
        np.testing.assert_allclose(np.asarray(carry.mu[0]), ref.mu,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(carry.var[0]), ref.var,
                                   rtol=1e-5, atol=1e-6)


# -------------------------------------------------------- engine + baselines
def _world(r, t, scenario="paper-burst"):
    scfg = SimConfig()
    sc = scenarios.build_scenario(scenario, scfg, r, t)
    params = batched.params_from_config(scfg, r, sc.capacity_scale)
    return params, batched.make_scenario_env_step(params, sc)


@pytest.mark.parametrize("name", sorted(set(api.TABLE1_ROUTERS) - {"aif"}))
def test_baselines_run_in_jitted_scan(name):
    r, t = 3, 25
    params, env_step = _world(r, t)
    router = api.ROUTERS[name](core.default_topology(), SimConfig(), False,
                               False)
    carry, est, trace = api.rollout(router, router.init_carry(r),
                                    batched.init_fluid_state(params),
                                    env_step, t, jax.random.key(0))
    assert trace.routing_weights.shape == (t, r, 3)
    assert trace.actions.shape == (t, r)
    res = batched.summarize(est, trace.env)
    assert np.all(res.n_requests > 0)
    assert np.all(res.success_rate > 0.2)
    w = np.asarray(trace.routing_weights)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-5)


def test_engine_deterministic_for_bandits():
    r, t = 2, 20
    router = api.ThompsonRouter()
    outs = []
    for _ in range(2):
        params, env_step = _world(r, t)
        _, est, trace = api.rollout(router, router.init_carry(r),
                                    batched.init_fluid_state(params),
                                    env_step, t, jax.random.key(3))
        outs.append((np.asarray(trace.actions), np.asarray(est.n_success)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_allclose(outs[0][1], outs[1][1])


def test_window_info_tier_queue_consistent():
    """The new per-tier queue signal must sum to the published queue-depth
    modality on clean telemetry."""
    r, t = 2, 30
    params, env_step = _world(r, t)
    router = api.UniformRouter()
    _, _, trace = api.rollout(router, router.init_carry(r),
                              batched.init_fluid_state(params),
                              env_step, t, jax.random.key(0))
    np.testing.assert_allclose(
        np.asarray(trace.env.tier_queue).sum(-1),
        np.asarray(trace.env.raw_obs)[:, :, 2], rtol=1e-5, atol=1e-5)


# ------------------------------------------------- AIF adapter bit-identity
def test_aif_api_rollout_bit_identical_to_shim():
    """api.rollout(AifRouter(...)) and the old fleet_rollout signature must
    be the same program bit-for-bit."""
    r, t = 3, 25
    params, env_step = _world(r, t)
    with pytest.warns(DeprecationWarning):
        ast_a, est_a, tr_a = fleet.fleet_rollout(
            fleet.init_fleet_state(CFG, r), batched.init_fluid_state(params),
            env_step, t, jax.random.key(9), CFG)
    router = api.AifRouter(cfg=CFG)
    ast_b, est_b, tr_b = api.rollout(
        router, router.init_carry(r), batched.init_fluid_state(params),
        env_step, t, jax.random.key(9))
    np.testing.assert_array_equal(np.asarray(tr_a.actions),
                                  np.asarray(tr_b.actions))
    np.testing.assert_array_equal(np.asarray(ast_a.belief),
                                  np.asarray(ast_b.belief))
    np.testing.assert_array_equal(np.asarray(est_a.n_success),
                                  np.asarray(est_b.n_success))


def test_aif_router_validates_shapes():
    with pytest.raises(ValueError, match="util_edges"):
        api.AifRouter(cfg=CFG, util_edges=(0.5,))


# --------------------------------------------------------------- shims
def test_hetero_fleet_rollout_rejects_unknown_kwargs():
    """A typo'd engine option (`use_palas=True`) must raise at the entry
    point with the valid option list, not as an opaque signature error deep
    inside the per-group loop."""
    with pytest.raises(TypeError, match="use_palas"):
        fleet.hetero_fleet_rollout([], 5, jax.random.key(0), use_palas=True)
    with pytest.raises(TypeError, match="fused"):
        fleet.hetero_fleet_rollout([], 5, jax.random.key(0), fused=True)


def test_fleet_rollout_shim_warns_and_points_at_api():
    r, t = 2, 6
    params, env_step = _world(r, t)
    with pytest.warns(DeprecationWarning, match="repro.api"):
        fleet.fleet_rollout(fleet.init_fleet_state(CFG, r),
                            batched.init_fluid_state(params), env_step, t,
                            jax.random.key(0), CFG)


# ------------------------------------------------------- Experiment surface
def test_experiment_run_and_summary():
    res = api.run(api.Experiment(router="least_loaded", n_cells=2,
                                 n_windows=25))
    s = res.summary()
    assert s["router"] == "least_loaded"
    assert 0.0 < s["success_pct"] <= 100.0
    assert len(s["tier_share_of_success"]) == 3
    assert s["obs_frac"] == 1.0


def test_experiment_degraded_scenario_reports_obs_frac():
    res = api.run(api.Experiment(router="uniform", scenario="flaky-telemetry",
                                 n_cells=2, n_windows=40))
    assert res.obs_frac < 0.9   # >= 35% dropout scenario


def test_experiment_unknown_names_raise():
    with pytest.raises(KeyError, match="unknown router"):
        api.run(api.Experiment(router="nope", n_cells=2, n_windows=5))
    with pytest.raises(ValueError, match="tiers"):
        api.run(api.Experiment(router=api.UniformRouter(tiers=5),
                               n_cells=2, n_windows=5))


def test_compare_markdown_and_json():
    exps = [api.Experiment(router=r, scenario=s, n_cells=2, n_windows=20)
            for s in ("steady", "flaky-telemetry")
            for r in ("uniform", "least_loaded")]
    comp = api.compare(exps)
    md = comp.markdown()
    assert md.count("\n") == 5   # header + rule + 4 rows
    for token in ("uniform", "least_loaded", "steady", "flaky-telemetry"):
        assert token in md
    js = comp.to_json()
    assert set(js) == {"steady", "flaky-telemetry"}
    assert set(js["steady"]) == {"uniform", "least_loaded"}
    assert js["flaky-telemetry"]["uniform"]["obs_frac"] < 1.0


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"],
                         ids=["repo-default", "env-var"])
def test_compile_cache_directory(monkeypatch, env_dir):
    """The entry points' compile cache follows JAX_COMPILATION_CACHE_DIR
    when it is set (and sets nothing itself), else the fixed in-checkout
    ``.jax_cache/``."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == str(compile_cache.REPO_CACHE_DIR)
            assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
            assert (compile_cache.REPO_CACHE_DIR.parent
                    / "pyproject.toml").exists()
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
