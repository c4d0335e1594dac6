"""Whole-window factored fleet state for the AIF megakernel engine path.

The per-tick fleet engine spends almost its entire budget on the dense
(R, A, S, S) transition pseudo-counts: the slow loop materializes a 300 MB
``b_counts`` update + renormalization every period, and every belief update
streams an (S, S) row of it.  But the counts are *structurally low rank*:

    b_counts = b0 + α_B · Σ_j  w_j · 1[act_j = a] · q_next_j ⊗ q_prev_j

where ``b0`` is the sticky prior (or, for a warm-promoted fleet, the source
fleet's already-learned dense counts) and the sum runs over replayed
transition slots ``j`` with weights that change only on slow boundaries
(``w_j = settle(Δt_j) · #times-sampled``).  This module keeps that factored
bookkeeping:

* :class:`MegaSlots` — every pushed transition of the rollout, one slot per
  tick (the rollout horizon is bounded by the replay capacity, so the
  legacy ring buffer never wraps and slot index == tick index).
* :class:`MegaCache` — quasi-static derived tensors (per-column B
  normalizers, EFE projection rows, per-slot coefficients).  The dense
  (R, A, S, S) tensor is never materialized in the hot loop: at the
  paper's S=243 it would be ~300 MB for a 64-cell fleet and every belief
  or EFE tick would stream it from HBM.
* Factored belief prior and EFE (:func:`factored_prior` /
  :func:`factored_efe`) — belief update → EFE → Gumbel argmax sampling →
  dwell gate → env window update run as one fused whole-window program
  (:func:`mega_window`), the XLA oracle twin of the Pallas megakernel.

**Streaming slow boundaries.**  The boundary step advances the cache
*incrementally* from the replayed batch (:func:`_advance_cache`), folded
as its slot-hit histogram (:func:`mega_slow_step`): the per-column
normalizer ``colsum`` gains the batch's delta, one contraction of the slot
tape weighted by the hit counts; the per-slot coefficient rows are
re-evaluated elementwise (linear in the slot-hit counts), and only the
A-derived rows (``logna``/``proj``/``projsum``/``qnproj``) are recomputed
in full — the A update renormalizes whole modality rows, so per-row
selection would save nothing there.
:func:`_refresh_cache` remains as the from-scratch fallback (init,
quarantine, warm promotion, tests): the slots' ``wcount`` is sufficient
statistics for it, and the incremental and full forms are mathematically
identical (the cache is linear in the hit counts), differing only in
floating-point association.

Semantics match the per-tick engine (:mod:`repro.core.fleet`) term for
term (same guard constants); only floating-point reassociation differs,
pinned by the rollout-parity tests at 1e-4 (actions bit-equal).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core import agent as agent_mod
from repro.core import belief as belief_mod
from repro.core import generative, learning, policies, preferences, spaces
from repro.envsim import batched

# Contractions run in float32 on every backend: a TPU's default float32
# matmul is one bfloat16 pass, which would round the beliefs, slot payloads
# and one-hot count gathers this oracle is the reference for.
_einsum = functools.partial(jnp.einsum,
                            precision=jax.lax.Precision.HIGHEST)


class MegaSlots(NamedTuple):
    """All pushed transitions of a rollout, slot ``j`` == fast tick ``j``.

    The legacy replay ring never wraps when the horizon T fits the replay
    capacity (enforced at init), so slots are written once, in tick order,
    and ``wcount`` — how many times slot ``j`` was drawn across all slow
    steps so far — is the *only* mutable learning state:
    the implicit B-count contribution of slot ``j`` is
    ``α_B · settle(Δt_j) · wcount_j · q_next_j ⊗ q_prev_j``.
    The engine's boundary step folds each replayed batch into the cache
    incrementally; ``wcount`` stays the sufficient statistic for the
    from-scratch :func:`_refresh_cache` fallback.

    ``q_prev`` / ``q_next`` may be stored in bfloat16 (``slot_dtype``) —
    every consumer accumulates in float32.
    """

    q_prev: jnp.ndarray           # (R, J, S) belief before the tick
    q_next: jnp.ndarray           # (R, J, S) posterior after the tick
    obs_bins: jnp.ndarray         # (R, J, M) int32
    obs_mask: jnp.ndarray         # (R, J, M) float32 validity at push time
    action: jnp.ndarray           # (R, J) int32 action in force at the tick
    dt_since_change: jnp.ndarray  # (R, J) float32 dwell age at the tick
    wcount: jnp.ndarray           # (R, J) float32 times sampled by slow steps


def row_major_tapes(slots: MegaSlots) -> MegaSlots:
    """State the ``q_prev``/``q_next`` tapes row-major, (R, J, S), as the
    megakernel reads them.

    XLA picks the layout of the rollout loop's tape carry from the ops that
    touch it.  Left to itself at a long tape (J = 3,600), it lays out the
    per-cell select of :func:`mega_quarantine` slot-minor, the carry
    follows, and every window then copies both whole tapes row-major for
    the kernel.  Stated on the quarantine's output, the carry stays
    row-major, as XLA already keeps it at J = 600, and no window copies a
    tape.
    """
    q_prev, q_next = with_layout_constraint((slots.q_prev, slots.q_next),
                                            Layout((0, 1, 2)))
    return slots._replace(q_prev=q_prev, q_next=q_next)


class MegaCache(NamedTuple):
    """Quasi-static derived tensors, advanced once per slow period.

    With ``u = b_prior_uniform / S`` and ``d = b_prior_sticky``:

      colsum[a, s]  = col0[a, s]
                      + Σ_j coefact[j, a] · Σ_t q_next_j[t] · q_prev_j[s]
                      (the per-column normalizer of the implicit B, where
                      ``col0`` is the scalar prior column sum — or
                      ``Σ_t b_base[a, t, s]`` for a warm-promoted fleet)
      coefw[j]      = α_B · settle(Δt_j) · wcount_j
      coefact[j, a] = coefw[j] · 1[action_j = a]
      proj          = the EFE's (P, S) projection rows: the M·NB normalized
                      observation rows followed by the M per-modality
                      ambiguity rows — o_pred and the ambiguity term are
                      both ``proj @ s_pred``.
      qnproj[j, p]  = proj[p] · q_next_j   (per-slot EFE contribution)
      sumqn[j]      = Σ_t q_next_j[t]  (≈ 1; kept exact for the colsum)
      logna         = log observation model rows for the evidence gather.
      b_base        = optional (R, A, S, S) dense transition-count baseline
                      — ``None`` on fresh fleets (the scalar sticky prior
                      suffices); a warm promotion's already-learned
                      ``b_counts``.  Static across the rollout: only the
                      slot terms grow, so it is read (streamed on EFE
                      ticks), never rewritten.

    Invalidation rule: ``colsum`` advances by the boundary batch's delta
    (the slot tape weighted by the batch's slot hits) and the coefficient
    rows (``coefw``/``coefact``) are re-evaluated elementwise from the
    bumped hit counts; the A-derived rows
    (``proj``/``projsum``/``logna``/``qnproj``) are recomputed in full each
    boundary — every modality row a replayed observation touched is
    renormalized, and the bin-sum denominator couples the rows of a
    modality, so per-row selection would save nothing.
    """

    colsum: jnp.ndarray    # (R, A, S)
    proj: jnp.ndarray      # (R, P, S) with P = M·max_bins + M
    projsum: jnp.ndarray   # (R, P)
    qnproj: jnp.ndarray    # (R, J, P)
    sumqn: jnp.ndarray     # (R, J)
    coefw: jnp.ndarray     # (R, J)
    coefact: jnp.ndarray   # (R, J, A)
    logna: jnp.ndarray     # (R, M, max_bins, S) log(max(na, 1e-16))
    b_base: jnp.ndarray | None  # (R, A, S, S) warm baseline or None


class MegaFleetState(NamedTuple):
    """Factored fleet carry of the megakernel engine path."""

    a_counts: jnp.ndarray         # (R, M, max_bins, S) — stays dense (small)
    slots: MegaSlots
    cache: MegaCache
    belief: jnp.ndarray           # (R, S)
    prev_action: jnp.ndarray      # (R,) int32
    dt_since_change: jnp.ndarray  # (R,) float32
    error_ema: jnp.ndarray        # (R,) float32
    unstable: jnp.ndarray         # (R,) bool
    t: jnp.ndarray                # (R,) int32 fast ticks elapsed


def n_proj(topo) -> int:
    """Rows of the EFE projection: M·max_bins observation rows + M
    per-modality ambiguity rows."""
    return topo.n_modalities * topo.max_bins + topo.n_modalities


def _a_cache(a_counts: jnp.ndarray, topo):
    """The observation-model-derived cache rows (recomputed in full at every
    boundary — the pure O(M·NB·S) per-cell part of the streaming update)."""
    r = a_counts.shape[0]
    m, nb, s = topo.n_modalities, topo.max_bins, topo.n_states
    mask = spaces.bins_mask(topo)[:, :, None]                     # (M, NB, 1)
    counts = a_counts * mask
    na = counts / jnp.maximum(jnp.sum(counts, axis=-2, keepdims=True), 1e-30)
    logna = jnp.log(jnp.maximum(na, 1e-16))
    amb_m = generative.modality_ambiguity_from_normalized(na, topo)
    proj = jnp.concatenate([na.reshape(r, m * nb, s), amb_m], axis=1)
    projsum = jnp.sum(proj, axis=-1)
    return proj, projsum, logna


def slot_coefficients(slots: MegaSlots, cfg: generative.AifConfig,
                      n_actions: int | None = None):
    """Per-slot factored B coefficients ``(coefw, coefact)`` from the slots'
    sufficient statistics (linear in ``wcount``)."""
    a_n = cfg.n_actions if n_actions is None else n_actions
    settle = learning.settle_weight(slots.dt_since_change, cfg)
    coefw = cfg.alpha_b * settle * slots.wcount                   # (R, J)
    coefact = coefw[..., None] * jax.nn.one_hot(
        slots.action, a_n, dtype=jnp.float32)                     # (R, J, A)
    return coefw, coefact


def _refresh_cache(a_counts: jnp.ndarray, slots: MegaSlots,
                   cfg: generative.AifConfig,
                   b_base: jnp.ndarray | None = None) -> MegaCache:
    """Recompute every derived tensor from scratch (init, quarantine, warm
    promotion and the tests' full-refresh fallback — the hot path advances
    the cache incrementally via :func:`_advance_cache`).

    ``b_base`` replaces the fresh sticky prior as the transition-count
    baseline (warm promotion: the source fleet's dense ``b_counts``).
    """
    topo = cfg.topology
    a_n = cfg.n_actions
    qp = slots.q_prev.astype(jnp.float32)
    qn = slots.q_next.astype(jnp.float32)

    coefw, coefact = slot_coefficients(slots, cfg, a_n)
    sumqn = jnp.sum(qn, axis=-1)                                  # (R, J)
    if b_base is None:
        col0 = cfg.b_prior_uniform + cfg.b_prior_sticky
    else:
        col0 = jnp.sum(b_base, axis=-2)                           # (R, A, S)
    colsum = col0 + _einsum("rja,rjs->ras",
                               coefact * sumqn[..., None], qp)
    proj, projsum, logna = _a_cache(a_counts, topo)
    qnproj = _einsum("rps,rjs->rjp", proj, qn)
    return MegaCache(colsum=colsum, proj=proj, projsum=projsum,
                     qnproj=qnproj, sumqn=sumqn, coefw=coefw,
                     coefact=coefact, logna=logna, b_base=b_base)


def _advance_cache(cache: MegaCache, a_counts: jnp.ndarray,
                   slots: MegaSlots, hits: jnp.ndarray,
                   cfg: generative.AifConfig) -> MegaCache:
    """Advance the cache by one boundary's replayed batch, given as its
    slot-hit histogram ``hits`` (R, J) (:func:`mega_slow_step`).

    ``colsum`` gains the batch's delta in the full refresh's own form with
    ``hits`` in the place of ``wcount``: one contraction over the slot tape,
    ``α_B Σ_j hits_j · settle(Δt_j) · 1[act_j = a] · sumqn_j · q_prev_j``,
    whose ``sumqn`` is the one computed here for the cache anyway.  The
    per-slot coefficient rows are re-evaluated elementwise from the bumped
    ``wcount`` (bit-equal to the full refresh: same formula, same inputs),
    and the A-derived rows are refreshed from the already-updated
    ``a_counts``.  No (R, A, S, S) tensor is formed.
    """
    a_n = cfg.n_actions
    qn = slots.q_next.astype(jnp.float32)
    coefw, coefact = slot_coefficients(slots, cfg, a_n)
    sumqn = jnp.sum(qn, axis=-1)
    with jax.named_scope("aif.slow_step.replay"):
        _, d_coef = slot_coefficients(slots._replace(wcount=hits), cfg, a_n)
        d_col = _einsum("rja,rjs->ras", d_coef * sumqn[..., None],
                        slots.q_prev, preferred_element_type=jnp.float32)
    proj, projsum, logna = _a_cache(a_counts, cfg.topology)
    qnproj = _einsum("rps,rjs->rjp", proj, qn)
    return MegaCache(colsum=cache.colsum + d_col, proj=proj,
                     projsum=projsum, qnproj=qnproj, sumqn=sumqn,
                     coefw=coefw, coefact=coefact, logna=logna,
                     b_base=cache.b_base)


def init_mega_state(cfg: generative.AifConfig, r: int, n_slots: int,
                    slot_dtype=jnp.float32,
                    from_agent_state=None) -> MegaFleetState:
    """Factored fleet state with ``n_slots`` (== rollout horizon) slots.

    Raises if the horizon exceeds the replay capacity — the factored form
    relies on the legacy ring buffer never wrapping (slot == tick).

    ``from_agent_state`` promotes a trained dense
    :class:`repro.core.agent.AgentState` (the per-tick engine's carry, or
    :func:`to_agent_state`'s output) onto the mega path mid-life: the dense
    ``b_counts`` become the cache baseline, the replay entries become the
    leading slots (tick order — requires the ring not to have wrapped), and
    the fleet clock continues.  ``init_mega_state(from_agent_state=
    to_agent_state(s))`` is an exact round-trip.  Must be called outside
    jit (the fleet clock is introspected).
    """
    if n_slots > cfg.replay_capacity:
        raise ValueError(
            f"megakernel path supports horizons up to the replay capacity "
            f"({cfg.replay_capacity}); got {n_slots} ticks — beyond that the "
            f"legacy ring buffer overwrites slots and the factored "
            f"slot==tick invariant breaks.  Raise cfg.replay_capacity, "
            f"split the run into shorter rollouts (re-promote the carry "
            f"with init_mega_state(from_agent_state=to_agent_state(...)) "
            f"between them), or chunk the dispatch with "
            f"rollout(..., launch_periods=...) over a horizon that still "
            f"fits the capacity.")
    topo = cfg.topology
    s, m, nb = topo.n_states, topo.n_modalities, topo.max_bins
    if from_agent_state is None:
        a0 = jnp.broadcast_to(
            generative.init_generative_model(cfg).a_counts, (r, m, nb, s))
        slots = MegaSlots(
            q_prev=jnp.zeros((r, n_slots, s), slot_dtype),
            q_next=jnp.zeros((r, n_slots, s), slot_dtype),
            obs_bins=jnp.zeros((r, n_slots, m), jnp.int32),
            obs_mask=jnp.ones((r, n_slots, m), jnp.float32),
            action=jnp.zeros((r, n_slots), jnp.int32),
            dt_since_change=jnp.zeros((r, n_slots), jnp.float32),
            wcount=jnp.zeros((r, n_slots), jnp.float32),
        )
        return MegaFleetState(
            a_counts=a0,
            slots=slots,
            cache=_refresh_cache(a0, slots, cfg),
            belief=jnp.full((r, s), 1.0 / s, jnp.float32),
            prev_action=jnp.full((r,), policies.BALANCED_ACTION, jnp.int32),
            dt_since_change=jnp.zeros((r,), jnp.float32),
            error_ema=jnp.zeros((r,), jnp.float32),
            unstable=jnp.zeros((r,), bool),
            t=jnp.zeros((r,), jnp.int32),
        )

    src = from_agent_state
    t_arr = np.asarray(src.t)
    if t_arr.shape[0] != r:
        raise ValueError(
            f"from_agent_state carries {t_arr.shape[0]} cells, expected {r}")
    if t_arr.size == 0 or np.any(t_arr != t_arr.flat[0]):
        raise ValueError(
            "warm promotion needs a uniform fleet clock (every cell at the "
            "same t) — mixed-phase fleets cannot share the slot==tick "
            "invariant")
    t_warm = int(t_arr.flat[0])
    if t_warm > cfg.replay_capacity:
        raise ValueError(
            f"warm promotion at t={t_warm} > replay_capacity="
            f"{cfg.replay_capacity}: the source ring has wrapped, so its "
            f"entries no longer sit at their tick index")
    if t_warm > n_slots:
        raise ValueError(
            f"warm promotion needs n_slots >= the source clock "
            f"({t_warm}); got {n_slots} — size the slots to the promoted "
            f"fleet's whole remaining horizon")

    def head(arr, fill, dtype):
        out = jnp.full((r, n_slots) + arr.shape[2:], fill, dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            out, arr[:, :n_slots].astype(dtype), 0, axis=1)

    rep = src.replay
    slots = MegaSlots(
        q_prev=head(rep.q_prev, 0.0, slot_dtype),
        q_next=head(rep.q_next, 0.0, slot_dtype),
        obs_bins=head(rep.obs_bins, 0, jnp.int32),
        obs_mask=head(rep.obs_mask, 1.0, jnp.float32),
        action=head(rep.action, 0, jnp.int32),
        dt_since_change=head(rep.dt_since_change, 0.0, jnp.float32),
        wcount=jnp.zeros((r, n_slots), jnp.float32),
    )
    a_counts = src.model.a_counts
    return MegaFleetState(
        a_counts=a_counts,
        slots=slots,
        cache=_refresh_cache(a_counts, slots, cfg,
                             b_base=src.model.b_counts),
        belief=src.belief,
        prev_action=src.prev_action,
        dt_since_change=src.dt_since_change,
        error_ema=src.error_ema,
        unstable=src.unstable,
        t=src.t,
    )


# ------------------------------------------------------------- factored math
def factored_prior(cache: MegaCache, slots: MegaSlots, belief: jnp.ndarray,
                   prev_action: jnp.ndarray,
                   cfg: generative.AifConfig) -> jnp.ndarray:
    """Normalized belief prior ``B_{a_prev} q`` without materializing B.

    With ``q̃ = q / colsum[a_prev]``:

      prior[t] ∝ base_term + Σ_j pend_j · q_next_j[t],
      pend_j = coefact[j, a_prev] · (q_prev_j · q̃)

    where ``base_term`` is ``u·Σ_s q̃[s] + d·q̃[t]`` on a fresh fleet and the
    warm baseline's (S, S) matvec ``b_base[a_prev] q̃`` otherwise — exactly
    the legacy ``row/colsum @ q`` with the count sum unrolled over slots
    (two (J, S) GEMVs per router instead of an (S, S) matvec).
    """
    s = belief.shape[-1]
    qp = slots.q_prev.astype(jnp.float32)
    qn = slots.q_next.astype(jnp.float32)
    csum = jnp.take_along_axis(
        cache.colsum, prev_action[:, None, None], axis=1)[:, 0]   # (R, S)
    qt = belief / csum
    cw = jnp.take_along_axis(
        cache.coefact, prev_action[:, None, None], axis=2)[..., 0]  # (R, J)
    pend = cw * _einsum("rjs,rs->rj", qp, qt)
    slot_term = _einsum("rj,rjt->rt", pend, qn)
    if cache.b_base is None:
        u = cfg.b_prior_uniform / s
        d = cfg.b_prior_sticky
        num = u * jnp.sum(qt, -1, keepdims=True) + d * qt + slot_term
    else:
        brow = jnp.take_along_axis(
            cache.b_base, prev_action[:, None, None, None], axis=1)[:, 0]
        num = _einsum("rts,rs->rt", brow, qt) + slot_term
    return num / jnp.maximum(jnp.sum(num, -1, keepdims=True), 1e-30)


def factored_efe(cache: MegaCache, slots: MegaSlots, q: jnp.ndarray,
                 logc: jnp.ndarray, cost: jnp.ndarray,
                 cfg: generative.AifConfig,
                 obs_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """G (R, A) from the factored model (legacy kernel-ref term-for-term).

    The predicted state ``ŝ_a ∝ B_a q`` is never materialized either: both
    the predicted observation and the ambiguity term are linear in ``ŝ_a``,
    so only its P projections through ``cache.proj`` are computed —
    ``o_pred[a] = (proj @ ŝ_num_a) / Σ_t ŝ_num_a[t]``, with the slot sum
    entering through the precomputed ``qnproj``.  A warm baseline adds its
    dense contraction (the one path that streams ``b_base``).
    """
    topo = cfg.topology
    s = q.shape[-1]
    m, nb = topo.n_modalities, topo.max_bins
    qp = slots.q_prev.astype(jnp.float32)

    qa = q[:, None, :] / cache.colsum                             # (R, A, S)
    sqa = jnp.sum(qa, axis=-1)                                    # (R, A)
    dots = _einsum("rjs,ras->rja", qp, qa)                     # (R, J, A)
    pend = cache.coefact * dots
    slot_o = _einsum("rja,rjp->rap", pend, cache.qnproj)       # (R, A, P)
    slot_den = _einsum("rja,rj->ra", pend, cache.sumqn)
    if cache.b_base is None:
        u = cfg.b_prior_uniform / s
        d = cfg.b_prior_sticky
        o_num = (u * sqa[:, :, None] * cache.projsum[:, None, :]
                 + d * _einsum("rps,ras->rap", cache.proj, qa)
                 + slot_o)
        sden = jnp.maximum((u * s + d) * sqa + slot_den, 1e-30)
    else:
        s_num = _einsum("rats,ras->rat", cache.b_base, qa)     # (R, A, S)
        o_num = _einsum("rpt,rat->rap", cache.proj, s_num) + slot_o
        sden = jnp.maximum(jnp.sum(s_num, axis=-1) + slot_den, 1e-30)
    o_pred = o_num / sden[..., None]

    o_obs = o_pred[:, :, :m * nb].reshape(q.shape[0], -1, m, nb)
    terms = jnp.where(o_obs > 1e-20,
                      o_obs * (jnp.log(jnp.maximum(o_obs, 1e-30))
                               - logc[:, None]), 0.0)
    amb_rows = o_pred[:, :, m * nb:]                              # (R, A, M)
    if obs_mask is not None:
        terms = terms * obs_mask[:, None, :, None]
        ambiguity = jnp.sum(amb_rows * obs_mask[:, None, :], axis=-1)
    else:
        ambiguity = jnp.sum(amb_rows, axis=-1)
    risk = jnp.sum(terms, axis=(2, 3))
    return risk + ambiguity + cost[None, :]


def _push_slot(slots: MegaSlots, idx, q_prev, q_next, obs_bins, obs_mask,
               action, dt_since_change) -> MegaSlots:
    """Write one transition at (traced) slot index ``idx`` on every router."""
    def put(arr, val):
        return jax.lax.dynamic_update_slice_in_dim(
            arr, val[:, None].astype(arr.dtype), idx, axis=1)

    return slots._replace(
        q_prev=put(slots.q_prev, q_prev),
        q_next=put(slots.q_next, q_next),
        obs_bins=put(slots.obs_bins, obs_bins),
        obs_mask=put(slots.obs_mask, obs_mask),
        action=put(slots.action, action),
        dt_since_change=put(slots.dt_since_change, dt_since_change),
    )


# --------------------------------------------------------------- hot window
def mega_window(state: MegaFleetState, est, obs_carry, params,
                arrival: jnp.ndarray, hazard: jnp.ndarray,
                obs_valid: jnp.ndarray | None, k_env: jax.Array,
                gumbel: jnp.ndarray, t0, *,
                cfg: generative.AifConfig, disc, util_edges,
                util_period: int, dt: float, scrape_every: int,
                restart_blackout: bool, emits_mask: bool,
                forced_down: jnp.ndarray | None = None,
                speed: jnp.ndarray | None = None,
                row_block: tuple | None = None,
                graph=None,
                shard_axis: str | None = None):
    """W fused fast ticks: belief → EFE → sample → dwell → preferences → env.

    The XLA oracle twin of the Pallas megakernel — one launch advances the
    whole fleet W ticks with the quasi-static :class:`MegaCache` held fixed
    (the engine calls :func:`mega_slow_step` between windows).  Ticks are
    Python-unrolled so selecting ticks (t % dwell == 0) compile the EFE +
    sampling path and held ticks compile only the belief update, mirroring
    the per-tick engine's dwell blocking.

    Args:
      obs_carry: (raw_obs, tier_util, tier_up, tier_queue, obs_mask) — the
        engine's lagged-telemetry carry (window t's router consumes window
        t-1's published telemetry).
      arrival/hazard/obs_valid: this window's (W, ...) schedule slices.
      k_env: (W,) env keys; gumbel: (W, R, A) pre-drawn Gumbel noise whose
        argmax reproduces ``jax.random.categorical`` of the legacy per-tick
        sampling keys bit-for-bit.
      t0: traced global tick of the window's first tick; must sit on a
        dwell boundary (the engine only launches windows there).
      row_block: ``(row_start, n_true, n_pad)`` under the sharded engine —
        forwarded to the env so restart randomness is drawn at the
        device-count-invariant global shape.
      graph/shard_axis: optional :class:`repro.core.graph.GraphData` (and,
        when sharded, the mesh axis name) — forwarded to the env's
        spillover term; the neighbor-pressure telemetry column then rides
        the ordinary obs carry through the window.

    Returns (state, env state, obs_carry, per-tick trace tuple) with the
    trace leaves stacked (W, ...) in tick order.
    """
    topo = cfg.topology
    w_ticks = gumbel.shape[0]
    dwell = max(int(cfg.action_dwell_s / cfg.fast_period_s), 1)
    raw_obs, tier_util, tier_up, tier_queue, obs_mask = obs_carry
    logc_nom, logc_uns = preferences.preference_log_tables(cfg)
    cost = cfg.cost_weight * policies.policy_concentration_cost(topo)
    edges = jnp.asarray(util_edges, jnp.float32)
    err_ix = topo.modalities.index("error")
    ys = []
    pushes = []

    for w in range(w_ticks):
        t_idx = t0 + w
        mask = obs_mask if emits_mask else None

        # --- observe (the router-spec's evidence assembly, inlined)
        obs_bins = spaces.discretize_observation(raw_obs, disc)
        util_hml = tier_util[:, ::-1]
        util_bins = jnp.sum(util_hml[..., None] >= edges,
                            axis=-1).astype(jnp.int32)
        util_valid = ((t_idx % util_period) == 0) & (t_idx > 0)

        # --- adaptive preferences + evidence
        error_ema = agent_mod.masked_error_ema(
            state.error_ema, raw_obs[:, err_ix], cfg, mask)
        unstable = error_ema > cfg.error_trigger
        per_mod = jnp.take_along_axis(
            state.cache.logna, obs_bins[..., None, None], axis=-2)[..., 0, :]
        if mask is not None:
            per_mod = per_mod * mask[..., None]
        loglik = jnp.sum(per_mod, axis=-2)
        loglik = loglik + jnp.where(
            util_valid, belief_mod.util_log_likelihood(util_bins, topo), 0.0)

        # --- belief update (factored cached prior, legacy posterior guards)
        prior = factored_prior(state.cache, state.slots, state.belief,
                               state.prev_action, cfg)
        logp = loglik + jnp.log(jnp.maximum(prior, 1e-30))
        logp = logp - jnp.max(logp, axis=-1, keepdims=True)
        q_unnorm = jnp.exp(logp)
        q_next = q_unnorm / jnp.maximum(
            jnp.sum(q_unnorm, -1, keepdims=True), 1e-30)

        # --- EFE + in-window categorical via pre-drawn Gumbel noise
        if w % dwell == 0:
            logc = jnp.where(unstable[:, None, None], logc_uns, logc_nom)
            g = factored_efe(state.cache, state.slots, q_next, logc, cost,
                             cfg, obs_mask=mask)
            probs = jax.nn.softmax(-cfg.beta * g, axis=-1)
            sampled = jnp.argmax(
                jnp.log(jnp.maximum(probs, 1e-30)) + gumbel[w],
                axis=-1).astype(jnp.int32)
        else:
            sampled = state.prev_action

        # --- stage the transition slot (slot index == global tick).  The
        # window's W pushes land as one contiguous [t0, t0+W) block write
        # after the loop: in-window slots carry coefact == 0 until the next
        # boundary re-weighs them, so the prior/EFE contractions above read
        # the window-entry buffers bit-identically while XLA keeps the slot
        # buffers free of per-tick copy-on-write.
        pushes.append((state.belief, q_next, obs_bins,
                       mask if mask is not None else jnp.ones_like(obs_mask),
                       state.prev_action, state.dt_since_change))

        # --- dwell gate + env window
        action, dtc = agent_mod.dwell_gate(
            state.t, state.prev_action, state.dt_since_change, sampled, cfg)
        state = state._replace(
            belief=q_next, prev_action=action,
            dt_since_change=dtc, error_ema=error_ema, unstable=unstable,
            t=state.t + 1)
        weights = policies.routing_weights(action, topo)
        ov = None if obs_valid is None else obs_valid[w]
        fd = None if forced_down is None else forced_down[w]
        sp = None if speed is None else speed[w]
        est, win = batched.fluid_window_step(
            params, est, weights, arrival[w], hazard[w], k_env[w], t_idx,
            dt=dt, scrape_every=scrape_every, obs_valid=ov,
            restart_blackout=restart_blackout, forced_down=fd, speed=sp,
            row_block=row_block, graph=graph, shard_axis=shard_axis)

        ys.append((action, weights, raw_obs, unstable,
                   jnp.mean(obs_mask, axis=-1), win))
        raw_obs, tier_util = win.raw_obs, win.tier_utilization
        tier_up, tier_queue = win.tier_up, win.tier_queue
        if emits_mask:
            obs_mask = win.obs_mask

    # --- land the window's slot block in one contiguous write per buffer
    with jax.named_scope("aif.window.land"):
        qp_w, qn_w, ob_w, om_w, ac_w, dt_w = (jnp.stack(xs, axis=1)
                                              for xs in zip(*pushes))
        sl = state.slots

        def put(arr, val):
            return jax.lax.dynamic_update_slice_in_dim(
                arr, val.astype(arr.dtype), t0, axis=1)

        state = state._replace(slots=sl._replace(
            q_prev=put(sl.q_prev, qp_w), q_next=put(sl.q_next, qn_w),
            obs_bins=put(sl.obs_bins, ob_w),
            obs_mask=put(sl.obs_mask, om_w), action=put(sl.action, ac_w),
            dt_since_change=put(sl.dt_since_change, dt_w)))

        trace = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ys)
    return (state, est,
            (raw_obs, tier_util, tier_up, tier_queue, obs_mask), trace)


# -------------------------------------------------------------- slow update
@jax.named_scope("aif.slow_step")
def mega_slow_step(state: MegaFleetState, k_slow: jax.Array,
                   cfg: generative.AifConfig, *,
                   incremental: bool = True) -> MegaFleetState:
    """One slow boundary: replay-sample, learn A exactly, advance the
    factored cache by the batch's delta.

    The replayed index draws are the legacy per-router
    ``randint(key, (batch,), 0, max(size, 1))`` bit-for-bit (slot == tick,
    so the legacy ``idx % capacity`` is the identity here).  Every use of
    the replayed batch is a sum over its draws, which equals a sum over the
    slots weighted by the slot-hit histogram ``hits[r, j] = #{n : idx[r, n]
    = j}`` (zero on a router with nothing pushed yet), so the batch is
    folded in that form by dense contractions over the slot tape and no
    drawn slot is gathered: the A update is ``α_A Σ_j hits_j · obs_mask_j ·
    onehot(obs_bins_j) ⊗ q_next_j`` and the B side folds the same histogram
    into the cached column sums (:func:`_advance_cache`), equal to the
    gathered batch's sums up to float32 association.  ``wcount`` gains
    ``hits`` — small integers, so exactly the legacy scatter-add — and
    stays the sufficient statistic that keeps the from-scratch
    :func:`_refresh_cache` (``incremental=False``, the legacy twin)
    mathematically identical.
    """
    topo = cfg.topology
    slots = state.slots
    j = slots.action.shape[1]
    batch = cfg.replay_batch
    size = jnp.minimum(state.t, j)                               # == t
    idx = jax.vmap(
        lambda k, n: jax.random.randint(k, (batch,), 0,
                                        jnp.maximum(n, 1)))(k_slow, size)
    live = (size > 0).astype(jnp.float32)[:, None]               # (R, 1)

    with jax.named_scope("aif.slow_step.replay"):
        drawn = idx[..., None] == jnp.arange(j, dtype=idx.dtype)
        hits = live * jnp.sum(drawn, axis=1, dtype=jnp.float32)  # (R, J)
        # exact legacy observation-model update, summed over the slots
        wgt = (spaces.one_hot_observation(slots.obs_bins, topo.max_bins)
               * (hits[..., None] * slots.obs_mask)[..., None])  # (R,J,M,NB)
        a_counts = state.a_counts + cfg.alpha_a * _einsum(
            "rjmb,rjs->rmbs", wgt, slots.q_next,
            preferred_element_type=jnp.float32)

    slots = slots._replace(wcount=slots.wcount + hits)
    if incremental:
        cache = _advance_cache(state.cache, a_counts, slots, hits, cfg)
    else:
        cache = _refresh_cache(a_counts, slots, cfg,
                               b_base=state.cache.b_base)
    return state._replace(a_counts=a_counts, slots=slots, cache=cache)


# --------------------------------------------------------------- watchdog
def mega_watchdog_bad(state: MegaFleetState) -> jnp.ndarray:
    """(R,) bool — cells whose factored carry has diverged numerically.

    The window-granularity twin of the per-tick engine's
    :func:`repro.core.fleet.fleet_watchdog_bad`: a cell is bad when its
    posterior stops being a finite distribution (NaN/Inf, negative mass, or
    a sum far from 1 — the in-loop guards keep healthy posteriors
    normalized to float32 roundoff), when its observation pseudo-counts or
    derived column sums go non-finite (either would poison every later
    belief update and the next A-learning einsum), or when the error EMA
    driving the preference switch is non-finite.
    """
    r = state.belief.shape[0]

    def rows_finite(a):
        return jnp.all(jnp.isfinite(a.reshape(r, -1)), axis=-1)

    ok = (rows_finite(state.belief)
          & jnp.all(state.belief >= 0.0, axis=-1)
          & (jnp.abs(jnp.sum(state.belief, axis=-1) - 1.0) <= 0.5)
          & rows_finite(state.a_counts)
          & rows_finite(state.cache.colsum)
          & jnp.isfinite(state.error_ema))
    return ~ok


def mega_quarantine(state: MegaFleetState, bad: jnp.ndarray,
                    cfg: generative.AifConfig) -> MegaFleetState:
    """Reinit the flagged cells to priors; healthy cells bit-unchanged.

    The bad cells' beliefs return to uniform, their pseudo-counts to the
    fresh generative prior, and their replay slots are *cleared* (not just
    de-weighted: a NaN slot would re-poison the A-update einsum through
    ``NaN * 0``).  The derived cache is recomputed from the cleaned
    (a_counts, slots) and then where-selected per cell — a blanket refresh
    would silently update healthy cells' quasi-static (stale-by-design)
    cache mid-period and break bit-identity with the unwatched program.
    (A quarantined warm-promoted cell likewise returns to the *fresh*
    prior, not its promotion baseline — the baseline is part of the
    possibly-poisoned model.)  ``t`` is left untouched: slot index ==
    global tick is a fleet-wide invariant.
    """
    r = state.belief.shape[0]
    s = cfg.topology.n_states

    def where_r(fresh, old):
        b = bad.reshape((r,) + (1,) * (old.ndim - 1))
        return jnp.where(b, jnp.asarray(fresh, old.dtype), old)

    a0 = jnp.broadcast_to(generative.init_generative_model(cfg).a_counts,
                          state.a_counts.shape)
    a_counts = where_r(a0, state.a_counts)
    sl = state.slots
    slots = row_major_tapes(MegaSlots(
        q_prev=where_r(0.0, sl.q_prev),
        q_next=where_r(0.0, sl.q_next),
        obs_bins=where_r(0, sl.obs_bins),
        obs_mask=where_r(1.0, sl.obs_mask),
        action=where_r(0, sl.action),
        dt_since_change=where_r(0.0, sl.dt_since_change),
        wcount=where_r(0.0, sl.wcount),
    ))
    if state.cache.b_base is None:
        b_base = None
    else:
        eye = jnp.eye(s, dtype=jnp.float32)
        b0 = jnp.broadcast_to(cfg.b_prior_uniform / s
                              + cfg.b_prior_sticky * eye,
                              state.cache.b_base.shape)
        b_base = where_r(b0, state.cache.b_base)
    cache_new = _refresh_cache(a_counts, slots, cfg, b_base=b_base)
    cache = jax.tree_util.tree_map(
        lambda fresh, old: where_r(fresh, old), cache_new,
        state.cache._replace(b_base=b_base))
    return MegaFleetState(
        a_counts=a_counts,
        slots=slots,
        cache=cache,
        belief=where_r(1.0 / s, state.belief),
        prev_action=where_r(policies.BALANCED_ACTION, state.prev_action),
        dt_since_change=where_r(0.0, state.dt_since_change),
        error_ema=where_r(0.0, state.error_ema),
        unstable=where_r(False, state.unstable),
        t=state.t,
    )


# ---------------------------------------------------------------- densify
def to_agent_state(state: MegaFleetState,
                   cfg: generative.AifConfig) -> agent_mod.AgentState:
    """Densify the factored carry into a legacy (R,)-batched AgentState.

    Materializes the (R, A, S, S) transition counts (baseline — the sticky
    prior or a warm promotion's ``b_base`` — plus the slots' weighted outer
    products) and the replay buffer.  Expensive by design (this is exactly
    the memory traffic the factored path exists to avoid); intended for
    checkpoint interop, warm-fleet promotion round-trips
    (:func:`init_mega_state`'s ``from_agent_state``), drill-down and
    parity tests, not the hot loop.
    """
    topo = cfg.topology
    slots = state.slots
    r, j = slots.action.shape
    s, a_n = topo.n_states, cfg.n_actions
    qp = slots.q_prev.astype(jnp.float32)
    qn = slots.q_next.astype(jnp.float32)
    if state.cache.b_base is None:
        eye = jnp.eye(s, dtype=jnp.float32)
        b0 = cfg.b_prior_uniform / s + cfg.b_prior_sticky * eye
        base_rows = [b0] * a_n
    else:
        base_rows = [state.cache.b_base[:, a] for a in range(a_n)]
    coefact = state.cache.coefact                                 # (R, J, A)
    # one action at a time keeps the peak temp at (R, J, S) not (R, A, S, S)
    b_counts = jnp.stack(
        [base_rows[a]
         + _einsum("rj,rjt,rjs->rts", coefact[:, :, a], qn, qp)
         for a in range(a_n)], axis=1)

    cap = cfg.replay_capacity
    def pad(arr, fill):
        tail = jnp.full((r, cap - j) + arr.shape[2:], fill, arr.dtype)
        return jnp.concatenate([arr.astype(tail.dtype), tail], axis=1)

    replay = learning.ReplayBuffer(
        q_prev=pad(qp, 0.0), q_next=pad(qn, 0.0),
        obs_bins=pad(slots.obs_bins, 0), obs_mask=pad(slots.obs_mask, 1.0),
        action=pad(slots.action, 0),
        dt_since_change=pad(slots.dt_since_change, 0.0),
        cursor=jnp.minimum(state.t, j) % cap,
        size=jnp.minimum(state.t, cap),
    )
    c_nom = generative.nominal_c_log(cfg)
    c_uns = generative.unstable_c_log(cfg)
    model = generative.GenerativeModel(
        a_counts=state.a_counts,
        b_counts=b_counts,
        c_log=jnp.where(state.unstable[:, None, None], c_uns, c_nom),
        d_prior=jnp.broadcast_to(jnp.full((s,), 1.0 / s, jnp.float32),
                                 (r, s)),
    )
    cache = jax.vmap(lambda m: generative.derive_cache(m, cfg.topology))(
        model)
    return agent_mod.AgentState(
        model=model, cache=cache, belief=state.belief, replay=replay,
        prev_action=state.prev_action,
        dt_since_change=state.dt_since_change,
        error_ema=state.error_ema, unstable=state.unstable, t=state.t)
