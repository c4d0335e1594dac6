"""Jit'd public wrappers for the fleet EFE kernel stack.

Two entry layers:

* ``fleet_efe`` adapts a batched generative model (pseudo-counts, as carried
  by :class:`repro.core.agent.AgentState`) into the kernel's normalized
  inputs and dispatches to the Pallas kernel (TPU) or the pure-jnp oracle
  (CPU/unit tests).  Matches ``repro.core.efe.expected_free_energy``
  term-for-term for every :class:`~repro.core.topology.Topology`.
* ``fleet_efe_cached`` / ``fleet_belief_efe`` skip the normalization: they
  take the quasi-static :class:`~repro.core.generative.ModelCache` tensors
  that :func:`repro.core.agent.slow_step` refreshes once per slow period, so
  the fast loop never re-materializes a normalized (R, A, S, S) transition
  stack.  ``fleet_belief_efe`` additionally fuses the Bayesian belief update
  (Eq. 2) into the same kernel launch, so the posterior never round-trips to
  HBM between inference and action selection.

Shapes come from the config's topology, block sizes from the operand shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import generative, policies
from repro.core import mega as mega_core
from repro.kernels.efe.efe import (SUBLANES, belief_efe_fleet_pallas,
                                   efe_fleet_pallas)
from repro.kernels.efe.ref import (belief_efe_fleet_ref, belief_posterior_ref,
                                   efe_fleet_ref)


def on_tpu() -> bool:
    """Whether the default backend is a TPU (compiled kernels) — elsewhere
    the Pallas kernels run in interpret mode."""
    return jax.default_backend() == "tpu"


def _auto_interpret() -> bool:
    return not on_tpu()


def _gather_prev_b(nb: jnp.ndarray, prev_action: jnp.ndarray) -> jnp.ndarray:
    """(R, S', S) transition row of each router's currently-applied action."""
    return jnp.take_along_axis(
        nb, prev_action[:, None, None, None], axis=1)[:, 0]


def fleet_belief_posterior(nb: jnp.ndarray, beliefs: jnp.ndarray,
                           prev_action: jnp.ndarray,
                           loglik: jnp.ndarray) -> jnp.ndarray:
    """Cached-model belief update alone (held ticks — no EFE launch)."""
    return belief_posterior_ref(_gather_prev_b(nb, prev_action), beliefs,
                                loglik)


def _normalized_inputs(a_counts: jnp.ndarray, b_counts: jnp.ndarray,
                       c_log: jnp.ndarray, cfg: generative.AifConfig):
    """Batched (R, ...) counts -> kernel inputs (normalized, fused terms).

    The fast loop avoids this work entirely (it reads the slow-tick
    :class:`~repro.core.generative.ModelCache`); this adapter remains for
    direct count-space callers and parity tests.
    """
    topo = cfg.topology
    na = jax.vmap(lambda a: generative.normalize_a(a, topo))(a_counts)
    nb = jax.vmap(generative.normalize_b)(b_counts)    # (R, A, S', S)
    # kernel computes B_a q with contraction over the last dim: transpose so
    # that out[s'] = sum_s b[s', s] q[s]  — already (S', S) ✓
    logc = generative.masked_log_c(c_log, topo)
    amb = generative.ambiguity_from_normalized(na, topo)   # (R, S)
    return nb, na, logc, amb


def fleet_efe_cached(nb: jnp.ndarray, na: jnp.ndarray, logc: jnp.ndarray,
                     amb: jnp.ndarray, beliefs: jnp.ndarray,
                     cfg: generative.AifConfig, *,
                     obs_mask: jnp.ndarray | None = None,
                     use_pallas: bool = True, interpret: bool | None = None,
                     block_r: int | None = None) -> jnp.ndarray:
    """G (R, A) from pre-normalized (cached) model tensors.

    Args:
      nb:   (R, A, S, S) normalized transitions (``ModelCache.nb``).
      na:   (R, M, max_bins, S) normalized observations (``ModelCache.na``).
      logc: (R, M, max_bins) masked log σ(C) (per-tick; see
        :func:`repro.core.generative.masked_log_c`).
      amb:  (R, S) per-state ambiguity (``ModelCache.amb``); with
        ``obs_mask`` this must be the *mask-effective* ambiguity
        (:func:`repro.core.generative.masked_ambiguity` over
        ``ModelCache.amb_m``).
      beliefs: (R, S) posteriors.
      obs_mask: optional (R, M) observation-validity mask — dispatches the
        mask-aware kernel/oracle (masked modalities drop out of the risk
        term).
      interpret: None (default) auto-detects — compiled kernel on TPU,
        interpret-mode emulation elsewhere (Pallas does not lower to CPU).
      block_r: router block size, a multiple of 8 (the TPU sublane
        count); R is padded to a whole number of blocks.  None picks 8.
    """
    cost = cfg.cost_weight * policies.policy_concentration_cost(cfg.topology)
    if use_pallas:
        if interpret is None:
            interpret = _auto_interpret()
        return efe_fleet_pallas(nb, beliefs, na, logc, amb, cost, obs_mask,
                                block_r=block_r or SUBLANES,
                                interpret=interpret)
    return efe_fleet_ref(nb, beliefs, na, logc, amb, cost, obs_mask)


def fleet_belief_efe(nb: jnp.ndarray, na: jnp.ndarray, logc: jnp.ndarray,
                     amb: jnp.ndarray, beliefs: jnp.ndarray,
                     prev_action: jnp.ndarray, loglik: jnp.ndarray,
                     cfg: generative.AifConfig, *,
                     obs_mask: jnp.ndarray | None = None,
                     use_pallas: bool = True, interpret: bool | None = None,
                     block_r: int | None = None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused belief update → EFE for one fleet tick.

    Same cached inputs as :func:`fleet_efe_cached` plus:

      beliefs:     (R, S) posteriors *before* the tick.
      prev_action: (R,) int32 currently-applied action per router.
      loglik:      (R, S) observation log-likelihood for this tick (gathered
        from the cached normalized A, plus any gated utilization evidence —
        see :func:`repro.core.belief.log_likelihood_from_normalized`).
        Under partial observability the masked modalities must already be
        zeroed out of this sum (pass the same ``obs_mask`` to the gather),
        so the kernel's VMEM-carried posterior sees only valid evidence.

    Returns (G (R, A), posterior (R, S)).
    """
    b_prev = _gather_prev_b(nb, prev_action)                  # (R, S', S)
    cost = cfg.cost_weight * policies.policy_concentration_cost(cfg.topology)
    if use_pallas:
        if interpret is None:
            interpret = _auto_interpret()
        return belief_efe_fleet_pallas(b_prev, beliefs, loglik, nb, na,
                                       logc, amb, cost, obs_mask,
                                       block_r=block_r or SUBLANES,
                                       interpret=interpret)
    return belief_efe_fleet_ref(b_prev, beliefs, loglik, nb, na, logc, amb,
                                cost, obs_mask)


def fleet_efe(a_counts: jnp.ndarray, b_counts: jnp.ndarray,
              c_log: jnp.ndarray, beliefs: jnp.ndarray,
              cfg: generative.AifConfig, *,
              obs_mask: jnp.ndarray | None = None,
              use_pallas: bool = True, interpret: bool | None = None,
              block_r: int | None = None) -> jnp.ndarray:
    """G (R, A) for a fleet of routers, from raw pseudo-counts.

    Args:
      a_counts: (R, M, max_bins, S) observation-model pseudo-counts.
      b_counts: (R, A, S, S) transition pseudo-counts.
      c_log:    (R, M, max_bins) current log-preferences.
      beliefs:  (R, S) posteriors.
      obs_mask: optional (R, M) observation-validity mask (the effective
        ambiguity is derived here — count-space callers need no cache).
      interpret/block_r: see :func:`fleet_efe_cached`.
    """
    nb, na, logc, amb = _normalized_inputs(a_counts, b_counts, c_log, cfg)
    if obs_mask is not None:
        amb_m = generative.modality_ambiguity_from_normalized(na,
                                                              cfg.topology)
        amb = generative.masked_ambiguity(amb_m, obs_mask)
    return fleet_efe_cached(nb, na, logc, amb, beliefs, cfg,
                            obs_mask=obs_mask,
                            use_pallas=use_pallas, interpret=interpret,
                            block_r=block_r)


def mega_window(state, est, obs_carry, params,
                arrival: jnp.ndarray, hazard: jnp.ndarray,
                obs_valid: jnp.ndarray | None,
                k_env: jnp.ndarray, gumbel: jnp.ndarray, t0: jnp.ndarray, *,
                cfg: generative.AifConfig, disc, util_edges, util_period: int,
                dt: float, scrape_every: int, restart_blackout: bool,
                emits_mask: bool, use_pallas: bool = False,
                interpret: bool | None = None,
                forced_down: jnp.ndarray | None = None,
                speed: jnp.ndarray | None = None,
                row_block: tuple | None = None,
                graph=None,
                shard_axis: str | None = None):
    """One whole-window launch: W fused fast ticks of the mega engine path.

    Dispatch twin of :func:`fleet_belief_efe` at window granularity — the
    XLA oracle is :func:`repro.core.mega.mega_window` (the factored
    belief→EFE→sample→env tick, Python-unrolled over the window); with
    ``use_pallas`` the window runs as the Pallas megakernel
    (:mod:`repro.kernels.efe.mega`), which keeps the posterior, factored
    transition cache, preference tables and env carry resident in VMEM for
    all W ticks.  Chaos (``forced_down``/``speed``), sharded (``row_block``)
    and graph windows are not ported to the kernel: with ``use_pallas``
    they raise ``ValueError`` instead of running the oracle.  Inputs/outputs
    are identical either way:

      state:     :class:`repro.core.mega.MegaFleetState`.
      est:       batched env :class:`~repro.envsim.batched.FluidState`.
      obs_carry: (raw_obs, tier_util, tier_up, tier_queue, obs_mask) tuple
        carried across windows (the *published* telemetry of the previous
        tick, which this window's first belief update consumes).
      arrival/hazard/obs_valid: (W, ...) schedule slices for this window.
      k_env:     (W,) per-tick env keys; gumbel: (W, R, A) pre-drawn policy
        noise (in-kernel categorical = argmax(logp + gumbel), bitwise equal
        to ``jax.random.categorical``).
      t0:        global tick index of the window's first tick (traced ok).

    Returns ``(state, est, obs_carry, ys)`` with ys a per-tick trace tuple
    of (action, weights, raw_obs, unstable, obs_frac, env_window).
    """
    if use_pallas:
        # The megakernel's in-VMEM env port predates the fault-injection
        # schedules, draws restart randomness at the local R (not the
        # sharded engine's draw-at-true-R row_block contract) and has no
        # lane for the graph spillover's cross-cell exchange: those windows
        # need the XLA oracle, which the caller selects with use_pallas=False.
        unported = [name for name, v in (("forced_down", forced_down),
                                         ("speed", speed),
                                         ("row_block (sharded)", row_block),
                                         ("graph", graph)) if v is not None]
        if unported:
            raise ValueError(
                f"the Pallas megakernel does not implement "
                f"{', '.join(unported)} windows; run them with "
                f"use_pallas=False (the XLA mega oracle)")
        from repro.kernels.efe import mega as mega_kernel
        if interpret is None:
            interpret = _auto_interpret()
        return mega_kernel.mega_window_pallas(
            state, est, obs_carry, params, arrival, hazard, obs_valid,
            k_env, gumbel, t0, cfg=cfg, disc=disc, util_edges=util_edges,
            util_period=util_period, dt=dt, scrape_every=scrape_every,
            restart_blackout=restart_blackout, emits_mask=emits_mask,
            interpret=interpret)
    return mega_core.mega_window(
        state, est, obs_carry, params, arrival, hazard, obs_valid,
        k_env, gumbel, t0, cfg=cfg, disc=disc, util_edges=util_edges,
        util_period=util_period, dt=dt, scrape_every=scrape_every,
        restart_blackout=restart_blackout, emits_mask=emits_mask,
        forced_down=forced_down, speed=speed, row_block=row_block,
        graph=graph, shard_axis=shard_axis)
