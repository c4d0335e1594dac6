"""Fleet mode: thousands of AIF routers as one batched, shardable program.

The paper runs one router at 1 Hz on a CPU.  At datacenter scale each *service
cell* (model family × pod slice × region) gets its own router; all of them
share the same control cadence.  Because the agent is purely functional we
get the fleet for free with ``jax.vmap``: one control tick here is the
``jax.vmap`` of the single-agent :func:`repro.core.agent.fast_step`.  This
per-tick engine is the reference semantics; the production path is the
whole-window mega engine (:mod:`repro.core.mega`, with its Pallas megakernel
in :mod:`repro.kernels.efe.mega`), which the tests compare against it.

The tick reads the quasi-static :class:`~repro.core.generative.ModelCache`
(normalized A/B + per-state ambiguity) that
:func:`repro.core.agent.slow_step` refreshes once per slow period — the
paper's 1 s / 10 s timescale separation (§4.4) means nothing else about the
model changes between slow ticks, so the fast loop never re-normalizes
pseudo-counts.

The closed loop itself lives in the engine layer
(:func:`repro.api.engine.rollout`, behind the Router protocol): the outer
scan walks slow periods, the inner scan runs the ``slow_period_s /
fast_period_s`` fast ticks of one period, and the slow learning step
executes exactly once per period (instead of being computed-and-discarded
every tick).  :func:`fleet_rollout` remains as a deprecation shim over that
engine.  Agent and environment state buffers are donated through
:func:`fleet_tick` and the rollout, so entering a tick never copies the
(replay-buffer-dominated) fleet state.

All functions below take/return a *batched* :class:`~repro.core.agent.AgentState`
whose leaves carry a leading router dimension R.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import agent as agent_mod
from repro.core import belief as belief_mod
from repro.core import efe as efe_mod
from repro.core import generative, policies, spaces


def init_fleet_state(cfg: generative.AifConfig,
                     n_routers: int) -> agent_mod.AgentState:
    """Batched agent state with leading router axis R = n_routers."""
    single = agent_mod.init_agent_state(cfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_routers,) + x.shape), single)


# ------------------------------------------------------------------ one tick
def fleet_fast_step(state: agent_mod.AgentState,
                    obs_bins: jnp.ndarray,
                    raw_error_rate: jnp.ndarray,
                    keys: jax.Array,
                    cfg: generative.AifConfig,
                    util_bins: jnp.ndarray | None = None,
                    util_valid=False,
                    obs_mask: jnp.ndarray | None = None):
    """One fast step (belief → EFE → action) for the fleet; no slow learning.

    ``keys`` are the per-router *fast* keys (one categorical draw each);
    ``obs_mask`` is the (R, M) telemetry-validity mask for this tick (None =
    every modality fresh — the exact pre-mask program).
    """
    # None arguments are empty pytrees — vmap maps only the array leaves.
    return jax.vmap(
        lambda s, o, e, k, u, m: agent_mod.fast_step(s, o, e, k, cfg, u,
                                                     util_valid, m)
    )(state, obs_bins, raw_error_rate, keys, util_bins, obs_mask)


# -------------------------------------------------------- light (held) ticks
def _zero_breakdown(r: int, cfg: generative.AifConfig) -> efe_mod.EfeBreakdown:
    z = jnp.zeros((r, policies.n_actions(cfg.topology)), jnp.float32)
    return efe_mod.EfeBreakdown(g=z, risk=z, ambiguity=z, cost=z,
                                action_probs=z)


def _light_step_single(state: agent_mod.AgentState,
                       obs_bins: jnp.ndarray,
                       raw_error_rate: jnp.ndarray,
                       cfg: generative.AifConfig,
                       util_bins, util_valid, obs_mask):
    """Single-agent fast step on a *held* (non-dwell) tick: belief update and
    bookkeeping only — the EFE term is skipped because ``apply_action`` would
    discard the sampled action anyway (``t % dwell != 0``).  Bit-identical to
    :func:`repro.core.agent.fast_step` state evolution on such ticks."""
    model, q_next, replay, error_ema, unstable = agent_mod.pre_action(
        state, obs_bins, raw_error_rate, cfg, util_bins, util_valid, obs_mask)
    new_state, action = agent_mod.apply_action(
        state, model, q_next, replay, error_ema, unstable,
        state.prev_action, cfg)
    return new_state, (action, q_next, unstable)


def fleet_light_step(state: agent_mod.AgentState,
                     obs_bins: jnp.ndarray,
                     raw_error_rate: jnp.ndarray,
                     cfg: generative.AifConfig,
                     util_bins: jnp.ndarray | None = None,
                     util_valid=False,
                     obs_mask: jnp.ndarray | None = None):
    """Fleet fast step for a tick whose clock is off the action-dwell cadence
    (``t % dwell != 0`` for every router): the sampled action would be
    discarded, so the EFE evaluation — the dominant per-tick cost, streaming
    the whole (R, A, S, S) cached B — is skipped entirely.  State evolution
    is bit-identical to :func:`fleet_fast_step` on such ticks; the returned
    ``StepInfo.efe`` diagnostics read zero (the closed-loop rollout does not
    trace them).
    """
    new_state, (action, q_next, unstable) = jax.vmap(
        lambda s, o, e, u, m: _light_step_single(s, o, e, cfg, u,
                                                 util_valid, m)
    )(state, obs_bins, raw_error_rate, util_bins, obs_mask)
    info = agent_mod.StepInfo(
        action=action,
        routing_weights=policies.routing_weights(action, cfg.topology),
        efe=_zero_breakdown(action.shape[0], cfg),
        belief_entropy=jax.vmap(belief_mod.belief_entropy)(q_next),
        unstable=unstable,
        obs_bins=obs_bins,
        obs_mask=(agent_mod.all_valid_mask(obs_bins)
                  if obs_mask is None else obs_mask),
    )
    return new_state, info


def _select_learned(state, learned, do_learn):
    """Per-router select of the slow-updated state (vmap-of-cond semantics)."""
    def pick(a, b):
        cond = do_learn.reshape(do_learn.shape + (1,) * (a.ndim - 1))
        return jnp.where(cond, b, a)
    return jax.tree_util.tree_map(pick, state, learned)


def _slow_learn(state: agent_mod.AgentState, keys: jax.Array,
                cfg: generative.AifConfig) -> agent_mod.AgentState:
    """Vmapped slow learning step (module-level so tests can instrument the
    per-execution call count of the slow path)."""
    return jax.vmap(lambda s, k: agent_mod.slow_step(s, k, cfg))(state, keys)


def fleet_slow_step(state: agent_mod.AgentState, keys: jax.Array,
                    cfg: generative.AifConfig) -> agent_mod.AgentState:
    """Slow learning + model-cache refresh for routers whose clock is on a
    slow-period boundary (``t % period == 0``); other routers pass through.

    ``slow_step`` only writes the model and its cache, so only those leaves
    are selected — the replay buffer (the bulk of the state) passes through
    untouched.  For the common all-aligned fleet the select degenerates to
    taking the learned tensors outright (no copy).
    """
    period = max(int(cfg.slow_period_s / cfg.fast_period_s), 1)
    do_learn = (state.t % period) == 0                     # (R,)
    learned = _slow_learn(state, keys, cfg)
    new_model, new_cache = jax.lax.cond(
        jnp.all(do_learn),
        lambda: (learned.model, learned.cache),
        lambda: _select_learned((state.model, state.cache),
                                (learned.model, learned.cache), do_learn))
    return state._replace(model=new_model, cache=new_cache)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("state",))
def fleet_tick(state: agent_mod.AgentState,
               obs_bins: jnp.ndarray,
               raw_error_rate: jnp.ndarray,
               keys: jax.Array,
               cfg: generative.AifConfig,
               util_bins: jnp.ndarray | None = None,
               util_valid=False,
               obs_mask: jnp.ndarray | None = None):
    """One control tick for the whole fleet (fast step + gated slow step).

    ``state`` is donated: the caller's buffers are consumed and must not be
    reused after the call (re-init or keep the returned state instead).
    Prefer :func:`fleet_rollout` for closed loops — its nested scan runs the
    slow step once per slow period instead of computing-and-discarding it on
    the 9 intermediate ticks the way this single-tick entry point must.

    Args:
      state: batched AgentState (leading dim R on every leaf).
      obs_bins: (R, M) int32.
      raw_error_rate: (R,) float32.
      keys: (R,) typed PRNG keys (one per router).
      cfg: static hyper-parameters (carries the topology).
      util_bins: optional (R, K) int32 utilization scrape in state-factor
        order (heaviest tier first).
      util_valid: scalar gate for util_bins (True on scrape ticks; traced ok).
      obs_mask: optional (R, M) float 0/1 telemetry-validity mask for this
        tick's observations (None = all modalities fresh).
    """
    return jax.vmap(
        lambda s, o, e, k, u, m: agent_mod.tick(s, o, e, k, cfg, u,
                                                util_valid, m)
    )(state, obs_bins, raw_error_rate, keys, util_bins, obs_mask)


def fleet_routing_weights(info) -> jnp.ndarray:
    """(R, 3) routing weights extracted from a batched StepInfo."""
    return info.routing_weights


# ------------------------------------------------------------------ watchdog
def fleet_watchdog_bad(state: agent_mod.AgentState) -> jnp.ndarray:
    """(R,) bool — cells whose carry has diverged numerically.

    A cell is flagged when its posterior stops being a finite distribution
    (NaN/Inf, negative mass, or a sum far from 1 — healthy posteriors are
    normalized to float32 roundoff every tick), when its observation
    pseudo-counts go non-finite (the A-model is the learning state that
    actually diverges; a poisoned A reaches the belief within one tick), or
    when the error EMA driving the adaptive-preference switch is
    non-finite.  Deliberately cheap — O(R·M·bins·S) reads, no (R, A, S, S)
    traffic — so the check can run on *every* tick's incoming carry without
    denting clean-path throughput.
    """
    r = state.belief.shape[0]

    def rows_finite(a):
        return jnp.all(jnp.isfinite(a.reshape(r, -1)), axis=-1)

    ok = (rows_finite(state.belief)
          & jnp.all(state.belief >= 0.0, axis=-1)
          & (jnp.abs(jnp.sum(state.belief, axis=-1) - 1.0) <= 0.5)
          & rows_finite(state.model.a_counts)
          & rows_finite(state.cache.amb)
          & jnp.isfinite(state.error_ema))
    return ~ok


def fleet_quarantine(state: agent_mod.AgentState, bad: jnp.ndarray,
                     cfg: generative.AifConfig) -> agent_mod.AgentState:
    """Reinit the flagged cells to their priors; healthy cells bit-unchanged.

    The quarantined cells restart as fresh agents — prior belief, prior
    generative model (and its derived cache), an *emptied* replay buffer
    (contents zeroed, not just size-reset: a NaN slot would re-poison the
    next slow update's einsum through ``NaN * 0``), balanced action,
    cleared EMA.  ``t`` is left untouched so the fleet clock (slow/dwell
    phase) stays aligned across cells.
    """
    r = state.belief.shape[0]

    def where_r(fresh, old):
        b = bad.reshape((r,) + (1,) * (old.ndim - 1))
        return jnp.where(b, jnp.asarray(fresh, old.dtype), old)

    single = agent_mod.init_agent_state(cfg)

    def sel(fresh_leaf, old_leaf):
        return where_r(jnp.broadcast_to(fresh_leaf, old_leaf.shape), old_leaf)

    model = jax.tree_util.tree_map(sel, single.model, state.model)
    cache = jax.tree_util.tree_map(sel, single.cache, state.cache)
    replay = jax.tree_util.tree_map(sel, single.replay, state.replay)
    return agent_mod.AgentState(
        model=model,
        cache=cache,
        belief=sel(single.belief, state.belief),
        replay=replay,
        prev_action=where_r(policies.BALANCED_ACTION, state.prev_action),
        dt_since_change=where_r(0.0, state.dt_since_change),
        error_ema=where_r(0.0, state.error_ema),
        unstable=where_r(False, state.unstable),
        t=state.t,
    )


# ------------------------------------------------------------------- rollout
class FleetTrace(NamedTuple):
    """Per-window traces of a fleet rollout (leading time axis T)."""

    actions: jnp.ndarray          # (T, R) int32 selected policies
    routing_weights: jnp.ndarray  # (T, R, K) applied weights
    raw_obs: jnp.ndarray          # (T, R, M) metrics the routers observed
    unstable: jnp.ndarray         # (T, R) adaptive-preference mode flag
    # effective-observation fraction: share of modalities that delivered
    # fresh telemetry into *this tick's* belief update (1.0 without
    # degradation).  Like raw_obs, this lags the env stream by one window:
    # env.obs_mask[t] is emitted by window t and feeds tick t+1, so
    # obs_frac[t] == mean(env.obs_mask[t-1]) for mask-emitting engines
    # (obs_frac[0] is the all-valid warm-up mask).
    obs_frac: jnp.ndarray         # (T, R)
    env: Any                      # environment info pytree (engine-specific)
    # (T, R) float 0/1 quarantine events of the numerical watchdog (None for
    # routers without one; the mega engine scatters its window-boundary
    # events onto each window's last tick)
    watchdog: Any = None


def fleet_rollout(agent_state: agent_mod.AgentState,
                  env_state,
                  env_step: Callable,
                  n_steps: int,
                  key: jax.Array,
                  cfg: generative.AifConfig,
                  disc: spaces.DiscretizationConfig | None = None,
                  util_edges: tuple[float, ...] | None = None,
                  util_period: int = 10,
                  *,
                  obs_masked: bool | None = None,
                  t0: int | None = None):
    """Deprecated AIF-only entry point — use :mod:`repro.api` instead.

    The closed-loop engine now lives in :func:`repro.api.engine.rollout`
    behind the Router protocol; this shim keeps the old hand-assembled
    cfg/disc/util_edges signature working by packing it
    into a :class:`repro.api.aif.AifRouter` spec and delegating (same
    program bit-for-bit — the golden rollout test pins it).  Prefer::

        from repro import api
        router = api.AifRouter(cfg=cfg, disc=disc)
        api.rollout(router, agent_state, env_state, env_step, n_steps, key)

    or the declarative :func:`repro.api.run` / :class:`repro.api.Experiment`
    surface, which also owns the scenario/env assembly.
    """
    warnings.warn(
        "repro.core.fleet.fleet_rollout is deprecated: build a "
        "repro.api.AifRouter and call repro.api.rollout (or run a "
        "declarative repro.api.Experiment); this shim keeps the old "
        "signature working unchanged",
        DeprecationWarning, stacklevel=2)
    from repro.api.aif import AifRouter
    from repro.api.engine import rollout
    router = AifRouter(cfg=cfg, disc=disc,
                       util_edges=(None if util_edges is None
                                   else tuple(util_edges)),
                       util_period=util_period)
    return rollout(router, agent_state, env_state, env_step, n_steps, key,
                   obs_masked=obs_masked, t0=t0)




# ------------------------------------------------------- heterogeneous fleet
class FleetGroup(NamedTuple):
    """One topology-homogeneous shard of a heterogeneous fleet.

    Array shapes differ across topologies (|S|, A, K), so cells of different
    topologies cannot share one batched scan.  A heterogeneous fleet is
    therefore *statically sharded*: cells are grouped by topology and each
    group runs its own jitted ``fleet_rollout`` (its own scan / kernel
    shapes); groups are independent programs that XLA can dispatch
    concurrently (or pjit onto different mesh shards).
    """

    name: str
    cfg: generative.AifConfig
    agent_state: agent_mod.AgentState    # batched, leading dim R_g
    env_state: Any
    env_step: Callable
    # Per-shard observation discretization (None = paper defaults); shards
    # serving different offered loads need different bin edges.
    disc: spaces.DiscretizationConfig | None = None


#: Engine options hetero_fleet_rollout forwards to every group's rollout
#: (the per-group option, disc, lives on the FleetGroup).
_HETERO_ROLLOUT_KWARGS = frozenset(
    {"util_edges", "util_period", "obs_masked", "t0"})


def hetero_fleet_rollout(groups, n_steps: int, key: jax.Array,
                         **kwargs) -> dict:
    """Run a heterogeneous fleet: one engine rollout per topology group.

    Args:
      groups: sequence of :class:`FleetGroup` (cells pre-grouped by
        topology).  Each group's
        ``agent_state`` / ``env_state`` are donated to its rollout.
      n_steps: shared number of control windows.
      key: PRNG key; folded per group so groups stay independent.
      **kwargs: engine options shared by every group — one of
        ``util_edges``, ``util_period``, ``obs_masked``, ``t0``.  Unknown
        keys (e.g. a typo'd ``use_palas=True``) raise ``TypeError`` here at
        the entry point, naming the valid options, instead of surfacing as
        an opaque signature error deep inside the per-group loop.

    Returns:
      dict group name -> (final agent state, final env state, FleetTrace).
    """
    unknown = set(kwargs) - _HETERO_ROLLOUT_KWARGS
    if unknown:
        raise TypeError(
            f"hetero_fleet_rollout got unknown engine option(s) "
            f"{sorted(unknown)}; shared options are "
            f"{sorted(_HETERO_ROLLOUT_KWARGS)} and per-group options "
            f"(disc) belongs on the FleetGroup")
    names = [g.name for g in groups]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate FleetGroup names: {names}")
    from repro.api.aif import AifRouter
    from repro.api.engine import rollout
    rollout_kwargs = {k: kwargs[k] for k in ("obs_masked", "t0")
                      if k in kwargs}
    out = {}
    for i, g in enumerate(groups):
        router = AifRouter(
            cfg=g.cfg, disc=g.disc,
            util_edges=(tuple(kwargs["util_edges"])
                        if kwargs.get("util_edges") is not None else None),
            util_period=kwargs.get("util_period", 10))
        out[g.name] = rollout(
            router, g.agent_state, g.env_state, g.env_step, n_steps,
            jax.random.fold_in(key, i), **rollout_kwargs)
    return out
