"""Action selection via expected free energy minimization (paper §4.3, Eq. 1).

    G(a) = Risk(a) + Ambiguity(a) + Cost(a)
    p(a) ∝ exp(−β · G(a)),  β = 5.0

For each candidate action (the topology's generated policy set) the router
rolls the belief one step through the transition model, predicts the
observation distribution per modality, and scores it:

  Risk(a)      = Σ_m KL( ô_m(a) ‖ σ(C_m) )        — divergence from preferred
                                                     observations (goal term)
  Ambiguity(a) = Σ_m Σ_s ŝ_a(s) · H[A_m(· | s)]    — expected observation
                                                     entropy (exploration term:
                                                     low in well-learned states)
  Cost(a)      = λ · (log K − H(w_a))              — regularizer against
                                                     extreme routing policies

This module is the per-tick reference.  The whole-window engine
(:mod:`repro.core.mega`, and its Pallas megakernel in
:mod:`repro.kernels.efe.mega`) computes the same terms in factored form for
fleet-scale batches of routers; the tests compare it against the per-tick
engine built on these functions, for every topology.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import generative, policies, spaces


class EfeBreakdown(NamedTuple):
    g: jnp.ndarray          # (A,) expected free energy
    risk: jnp.ndarray       # (A,)
    ambiguity: jnp.ndarray  # (A,)
    cost: jnp.ndarray       # (A,)
    action_probs: jnp.ndarray  # (A,) softmax(−β G)


def expected_free_energy(model: generative.GenerativeModel,
                         belief: jnp.ndarray,
                         cfg: generative.AifConfig,
                         cache: generative.ModelCache | None = None,
                         obs_mask: jnp.ndarray | None = None
                         ) -> EfeBreakdown:
    """G(a) for all candidate actions (Eq. 1).

    With ``cache`` the quasi-static normalized model (nb, na, amb) is read
    instead of re-derived from pseudo-counts; only the preference term, which
    tracks the per-tick adaptive ``c_log``, is computed fresh.

    ``obs_mask`` ((M,) float 0/1) restricts G to the currently *observable*
    modalities: a dark modality can neither be steered toward preferences
    (its risk term is unverifiable) nor deliver information (its expected
    observation entropy is unrealizable), so both its risk and ambiguity
    contributions are zeroed.  An all-ones mask equals ``obs_mask=None``.
    """
    topo = cfg.topology
    if cache is not None:
        nb, na, amb_s, amb_m = cache.nb, cache.na, cache.amb, cache.amb_m
    else:
        nb = generative.normalize_b(model.b_counts)
        na = generative.normalize_a(model.a_counts, topo)
        amb_m = generative.modality_ambiguity_from_normalized(na, topo)
        amb_s = jnp.sum(amb_m, axis=-2)
    s_pred = jnp.einsum("ats,s->at", nb, belief)                   # (A, S)
    s_pred = s_pred / jnp.maximum(jnp.sum(s_pred, axis=-1, keepdims=True),
                                  1e-30)
    o_pred = jnp.einsum("mbs,as->amb", na, s_pred)                 # (A, M, B)

    # Risk: KL(ô ‖ σ(C)) per modality, summed (over observable modalities).
    c = generative.c_probs(model.c_log, topo)                # (M, B)
    mask = spaces.bins_mask(topo)                            # (M, B)
    log_ratio = jnp.log(jnp.maximum(o_pred, 1e-16)) - jnp.log(
        jnp.maximum(c, 1e-16))[None]
    terms = o_pred * log_ratio
    if obs_mask is not None:
        terms = terms * obs_mask[None, :, None]
    risk = jnp.sum(jnp.where(mask[None] > 0, terms, 0.0),
                   axis=(1, 2))                              # (A,)

    # Ambiguity: expected conditional observation entropy under ŝ_a.
    if obs_mask is not None:
        amb_s = generative.masked_ambiguity(amb_m, obs_mask)
    ambiguity = s_pred @ amb_s                               # (A,)

    cost = cfg.cost_weight * policies.policy_concentration_cost(topo)

    g = risk + ambiguity + cost
    probs = jax.nn.softmax(-cfg.beta * g)
    return EfeBreakdown(g=g, risk=risk, ambiguity=ambiguity, cost=cost,
                        action_probs=probs)


def select_action(key: jax.Array,
                  model: generative.GenerativeModel,
                  belief: jnp.ndarray,
                  cfg: generative.AifConfig,
                  cache: generative.ModelCache | None = None,
                  obs_mask: jnp.ndarray | None = None):
    """Sample ``a ~ softmax(−β G)``.  Returns (action, EfeBreakdown)."""
    bd = expected_free_energy(model, belief, cfg, cache, obs_mask)
    action = jax.random.categorical(key, jnp.log(
        jnp.maximum(bd.action_probs, 1e-30)))
    return action, bd
