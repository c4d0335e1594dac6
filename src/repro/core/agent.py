"""The AIF-Router agent: inference–action–learning cycle (paper §4, Fig. 1).

The agent is purely functional: all mutable state lives in an
:class:`AgentState` pytree and every transition is a jit-compiled pure
function, so agents vmap into fleets (:mod:`repro.core.fleet`) and the whole
control loop can run on-device.

Fast loop (1 s)  — ``fast_step``: observe → adapt preferences → Bayesian
belief update (Eq. 2) → EFE action selection (Eq. 1) → record transition.
Slow loop (10 s) — ``slow_step``: replay-buffer batch update of A and B.

``tick`` composes both with the paper's timescale separation: the slow update
fires every ``slow_period_s / fast_period_s`` fast steps.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import belief as belief_mod
from repro.core import efe as efe_mod
from repro.core import generative, learning, policies, preferences, spaces


class AgentState(NamedTuple):
    model: generative.GenerativeModel
    # Quasi-static normalized model (refreshed by slow_step only — the fast
    # loop reads it instead of re-normalizing pseudo-counts every tick).
    cache: generative.ModelCache
    belief: jnp.ndarray              # (S,) current posterior q(s_t)
    replay: learning.ReplayBuffer
    prev_action: jnp.ndarray         # () int32 — action currently applied
    dt_since_change: jnp.ndarray     # () float32 — seconds since action change
    error_ema: jnp.ndarray           # () float32 — smoothed error rate
    unstable: jnp.ndarray            # () bool — adaptive-preference mode
    t: jnp.ndarray                   # () int32 — fast steps elapsed


class StepInfo(NamedTuple):
    """Diagnostics emitted by each fast step (all per-step scalars/vectors)."""

    action: jnp.ndarray
    routing_weights: jnp.ndarray     # (K,) applied weights, lightest first
    efe: efe_mod.EfeBreakdown
    belief_entropy: jnp.ndarray
    unstable: jnp.ndarray
    obs_bins: jnp.ndarray
    obs_mask: jnp.ndarray            # (M,) validity of this tick's evidence


def init_agent_state(cfg: generative.AifConfig) -> AgentState:
    model = generative.init_generative_model(cfg)
    return AgentState(
        model=model,
        cache=generative.derive_cache(model, cfg.topology),
        # materialized copy: belief and d_prior must be distinct buffers or
        # donating the state through tick/fleet_rollout would donate one
        # buffer twice
        belief=jnp.array(model.d_prior, copy=True),
        replay=learning.init_replay(cfg.replay_capacity, cfg.topology),
        prev_action=jnp.asarray(policies.BALANCED_ACTION, jnp.int32),
        dt_since_change=jnp.zeros((), jnp.float32),
        error_ema=jnp.zeros((), jnp.float32),
        unstable=jnp.zeros((), bool),
        t=jnp.zeros((), jnp.int32),
    )


def all_valid_mask(obs_bins: jnp.ndarray) -> jnp.ndarray:
    """(..., M) all-ones validity mask matching a batch of observation bins.

    The single definition of the "every modality fresh" default shared by the
    single-agent and fleet paths, so the ``StepInfo.obs_mask`` trace cannot
    diverge between them.
    """
    return jnp.ones(jnp.shape(obs_bins), jnp.float32)


def masked_error_ema(prev_ema: jnp.ndarray,
                     raw_error_rate: jnp.ndarray,
                     cfg: generative.AifConfig,
                     obs_mask: jnp.ndarray | None) -> jnp.ndarray:
    """Adaptive-preference error EMA that respects the telemetry mask.

    ``raw_error_rate`` comes off the published telemetry stream, which
    re-emits the last value while the error modality is masked — ingesting
    it would keep the instability detector tracking a phantom-healthy (or
    phantom-failing) error rate through a scrape gap.  A masked error
    modality is treated as *no sample*: the EMA holds.  Elementwise over any
    leading batch shape; ``obs_mask=None`` (and topologies without an
    ``error`` modality) keep the exact unmasked update.
    """
    new = preferences.ema_update(prev_ema, raw_error_rate, cfg)
    if obs_mask is None:
        return new
    try:
        err_ix = cfg.topology.modalities.index("error")
    except ValueError:
        return new
    return jnp.where(obs_mask[..., err_ix] > 0, new, prev_ema)


def pre_action(state: AgentState,
               obs_bins: jnp.ndarray,
               raw_error_rate: jnp.ndarray,
               cfg: generative.AifConfig,
               util_bins: jnp.ndarray | None = None,
               util_valid=False,
               obs_mask: jnp.ndarray | None = None):
    """Everything in a fast step *before* action selection.

    Adaptive preferences (paper §4.2) → Bayesian belief update (Eq. 2) →
    replay-buffer push.  Split out so fleet mode's held ticks can skip the
    EFE term between this and :func:`apply_action` while sharing one copy of
    the control-step logic.

    ``obs_mask`` ((M,) float 0/1) flags which modalities delivered fresh
    telemetry this tick: masked modalities contribute zero evidence to the
    belief update, are excluded from the replayed A-count learning, and (for
    the error modality) hold the adaptive-preference EMA.

    Returns (model, q_next, replay, error_ema, unstable).
    """
    error_ema = masked_error_ema(state.error_ema, raw_error_rate, cfg,
                                 obs_mask)
    c_log, unstable = preferences.adapt_preferences(error_ema, cfg)
    model = state.model._replace(c_log=c_log)

    q_prev = state.belief
    q_next = belief_mod.update_belief(model, q_prev, state.prev_action,
                                      obs_bins, cfg.topology, util_bins,
                                      util_valid, cache=state.cache,
                                      obs_mask=obs_mask)

    replay = learning.push_transition(
        state.replay, q_prev, q_next, obs_bins, state.prev_action,
        state.dt_since_change, obs_mask=obs_mask)
    return model, q_next, replay, error_ema, unstable


def dwell_gate(t: jnp.ndarray,
               prev_action: jnp.ndarray,
               dt_since_change: jnp.ndarray,
               sampled: jnp.ndarray,
               cfg: generative.AifConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dwell-gate a sampled action against the agent clock.

    The single definition of the dwell rule — shared by
    :func:`apply_action` and the whole-window megakernel path
    (:mod:`repro.core.mega`), so the two engines cannot drift on when an
    action may change.  Elementwise over any leading batch shape.

    Returns (applied action (int32), new dt_since_change).
    """
    dwell_ticks = max(int(cfg.action_dwell_s / cfg.fast_period_s), 1)
    do_select = (t % dwell_ticks) == 0
    action = jnp.where(do_select, sampled, prev_action)
    changed = action != prev_action
    dt = jnp.where(changed, 0.0, dt_since_change + cfg.fast_period_s)
    return action.astype(jnp.int32), dt


def apply_action(state: AgentState,
                 model: generative.GenerativeModel,
                 q_next: jnp.ndarray,
                 replay: learning.ReplayBuffer,
                 error_ema: jnp.ndarray,
                 unstable: jnp.ndarray,
                 sampled: jnp.ndarray,
                 cfg: generative.AifConfig) -> tuple[AgentState, jnp.ndarray]:
    """Dwell-gate the sampled action and assemble the next AgentState.

    The policy is re-evaluated on the dwell cadence only and held in between
    (the settle-weighted transition learning needs actions to persist).
    Elementwise over any leading batch shape — fleet mode calls it directly
    on (R,)-batched states.

    Returns (new_state, applied action).
    """
    action, dt = dwell_gate(state.t, state.prev_action, state.dt_since_change,
                            sampled, cfg)

    new_state = AgentState(
        model=model,
        cache=state.cache,
        belief=q_next,
        replay=replay,
        prev_action=action.astype(jnp.int32),
        dt_since_change=dt,
        error_ema=error_ema,
        unstable=unstable,
        t=state.t + 1,
    )
    return new_state, action


@functools.partial(jax.jit, static_argnames=("cfg",))
def fast_step(state: AgentState,
              obs_bins: jnp.ndarray,
              raw_error_rate: jnp.ndarray,
              key: jax.Array,
              cfg: generative.AifConfig,
              util_bins: jnp.ndarray | None = None,
              util_valid=False,
              obs_mask: jnp.ndarray | None = None
              ) -> tuple[AgentState, StepInfo]:
    """One 1-second control step.

    Args:
      state: current agent state.
      obs_bins: (M,) int32 discretized observation o_t.
      raw_error_rate: () float — undiscretized error rate for the EMA that
        drives adaptive preferences (the discretized bin is too coarse).
      key: PRNG key for action sampling.
      cfg: static hyper-parameters (carries the topology).
      util_bins: optional (K,) int32 utilization scrape in state-factor
        order (heaviest tier first) — the paper's 10-second resource-metric
        query (§3).
      util_valid: gate for util_bins (True on scrape ticks only).
      obs_mask: optional (M,) float 0/1 telemetry-validity mask — masked
        modalities contribute zero belief evidence, no A-counts, and drop
        out of the EFE risk/ambiguity terms.
    """
    model, q_next, replay, error_ema, unstable = pre_action(
        state, obs_bins, raw_error_rate, cfg, util_bins, util_valid, obs_mask)

    # --- action selection via EFE (Eq. 1) ----------------------------------
    sampled, bd = efe_mod.select_action(key, model, q_next, cfg, state.cache,
                                        obs_mask)
    new_state, action = apply_action(state, model, q_next, replay, error_ema,
                                     unstable, sampled, cfg)

    info = StepInfo(
        action=action,
        routing_weights=policies.routing_weights(action, cfg.topology),
        efe=bd,
        belief_entropy=belief_mod.belief_entropy(q_next),
        unstable=unstable,
        obs_bins=obs_bins,
        obs_mask=all_valid_mask(obs_bins) if obs_mask is None else obs_mask,
    )
    return new_state, info


@functools.partial(jax.jit, static_argnames=("cfg",))
def slow_step(state: AgentState, key: jax.Array,
              cfg: generative.AifConfig) -> AgentState:
    """One 10-second model-learning step (replay batch update of A, B).

    The only in-loop writer of the pseudo-counts — refreshing the normalized
    :class:`~repro.core.generative.ModelCache` here keeps the fast loop's
    cached tensors consistent by construction.
    """
    model = learning.slow_update(key, state.model, state.replay, cfg)
    return state._replace(model=model,
                          cache=generative.derive_cache(model, cfg.topology))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("state",))
def tick(state: AgentState,
         obs_bins: jnp.ndarray,
         raw_error_rate: jnp.ndarray,
         key: jax.Array,
         cfg: generative.AifConfig,
         util_bins: jnp.ndarray | None = None,
         util_valid=False,
         obs_mask: jnp.ndarray | None = None) -> tuple[AgentState, StepInfo]:
    """fast_step + conditionally the slow learning step (timescale separation)."""
    k_fast, k_slow = jax.random.split(key)
    state, info = fast_step(state, obs_bins, raw_error_rate, k_fast, cfg,
                            util_bins, util_valid, obs_mask)
    period = max(int(cfg.slow_period_s / cfg.fast_period_s), 1)
    do_learn = (state.t % period) == 0
    state = jax.lax.cond(
        do_learn,
        lambda s: slow_step(s, k_slow, cfg),
        lambda s: s,
        state,
    )
    return state, info


def observe_and_discretize(raw_metrics: jnp.ndarray,
                           disc: spaces.DiscretizationConfig,
                           obs_mask: jnp.ndarray | None = None
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Raw (latency_s, rps, queue, err) -> (observation bins, validity mask).

    Out-of-range raw metrics clamp to the edge bins
    (:func:`repro.core.spaces.discretize_observation`).  ``obs_mask`` is the
    telemetry pipeline's per-modality validity (e.g.
    ``WindowInfo.obs_mask``); None means every modality is fresh and the
    returned mask is all ones, so callers can thread the pair into
    :func:`fast_step` / :func:`tick` unconditionally.
    """
    bins = spaces.discretize_observation(raw_metrics, disc)
    if obs_mask is None:
        obs_mask = all_valid_mask(bins)
    return bins, jnp.asarray(obs_mask, jnp.float32)
