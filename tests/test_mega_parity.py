"""Mega engine against the per-tick reference engine on the paper's K=3
worlds: PRNG hoisting contracts, rollout parity on the clean and
masked-telemetry worlds and at an odd fleet size across dwell and slow
boundaries, and the streaming slow boundary against the full refresh on
run-warm states (the clean, masked and odd-R states are the rollouts the
parity tests compile)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import engine
from repro.core import generative
from repro.core import mega as mega_core
from repro.core.topology import default_topology, five_tier_topology

from mega_helpers import (KEY, TWO_TIER, _assert_rollouts_match, _mega_carry,
                          _pair)


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


def test_mega_odd_r_and_boundaries():
    """Odd fleet size + horizon not a multiple of the period (T=23 ends with
    a 3-tick remainder window: slow boundaries at 10/20, dwell-held tail)."""
    _assert_rollouts_match(*_pair(t=23, r=5))


# ----------------------------------------------- streaming slow boundaries
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
