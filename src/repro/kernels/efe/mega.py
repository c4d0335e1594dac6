"""Whole-window Pallas megakernel: W fused fast ticks per launch.

One launch advances a router block through an entire slow period — belief
update (Eq. 2) -> factored EFE (Eq. 1) -> in-kernel categorical sampling
(argmax over pre-drawn Gumbel noise) -> dwell gate -> adaptive-preference
error EMA -> fluid env window — with every carried tensor resident in VMEM
for all W ticks: the (BR, J, S) transition slots, the factored
:class:`repro.core.mega.MegaCache` tensors, the posterior, and the whole
per-cell env state.  Nothing round-trips to HBM between ticks; HBM traffic
is one read of the quasi-static operands and one write of the window's
posteriors and trace per window instead of per tick.

The XLA oracle twin is :func:`repro.core.mega.mega_window` (same op order,
same guard constants); rollout-level parity is pinned at 1e-4 by
``tests/test_mega.py``.  Known intentional deviations, both inside that
tolerance:

* the env's completion-weighted P95 replaces the oracle's
  ``argsort``/``cumsum`` with a sort-free O(K²) crossing test (TPU has no
  cheap in-kernel sort; the selected atom is identical, only the cumulative
  mass summation order differs), and
* the EFE contractions over the state and slot axes run on the MXU at
  ``Precision.HIGHEST`` (float32 passes) instead of ``einsum``
  (floating-point reassociation only).  One-hot selections — the applied
  action's transition column sums and slot coefficients, the routing-weight
  row — are VPU multiply-and-sum reductions, exact in any precision mode.

PRNG contract: the kernel draws nothing.  The caller pre-splits the legacy
per-tick key chain into a per-window block — ``gumbel`` (W, R, A) for the
policy categorical (``argmax(log p + gumbel)`` is bitwise
``jax.random.categorical``) and ``uniforms`` (W, 2, R, K) for the env
restart fire/duration draws — so randomness is bit-identical to the
per-tick engine at any window size.

Transition slots: the kernel reads the (BR, J, S) slot tape and never
writes it.  Slots pushed inside the window carry zero coefficients until
the next slow boundary re-weighs them (exactly what the oracle relies on),
so the kernel emits only the window's W posteriors and the wrapper lands
the window's slot block with one contiguous write, as the oracle does.
Slots may be stored bfloat16 (``MegaSlots`` dtype); all accumulation is
float32.

TPU layout: the cell axis sits on the sublane dim of every block — router
blocks are 8 rows and R is padded to a multiple of 8 with copies of the
last router (every op is row-local; padded rows are cropped) — per-window
schedules and traces are (W, ..., R, trailing) with the small axes leading,
and per-router scalars are (BR, 1) columns.  The state axis keeps its true
width S (243 for the paper topology; Mosaic masks the partial lane tile),
so the slot tape is read in place, never padded or copied.  The resident
tape bounds the horizon: :func:`mega_vmem_bytes` prices a launch, the
launch asks the compiler for exactly that much scoped VMEM, and
:func:`mega_window_pallas` refuses one beyond :data:`VMEM_LIMIT` — on a
TPU v5e an 8-router block compiles up to a J=2230-slot tape.

The kernel compiles for a TPU v5e (``tests/test_chip_compile.py`` compiles
it for a described chip at the paper's widths) and runs there compiled
(``chip_smoke.py``); on the CPU it runs in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import policies, preferences, spaces
from repro.core import mega as mega_core
from repro.envsim import batched
from repro.kernels.efe.efe import (SUBLANES, VMEM_CAPACITY, block_vmem_bytes,
                                   pad_cells, pad_rows)

_EPS = 1e-9             # envsim.batched._EPS (restated: kernels stay leaf)
_HI = jax.lax.Precision.HIGHEST

# Scoped-VMEM ceiling requested from the compiler (headroom below the
# 128 MiB TensorCore VMEM for Mosaic's own internal scratch).
VMEM_LIMIT = VMEM_CAPACITY - 16 * 1024 * 1024

# Slots per step of the in-kernel fold over the resident tape.
SLOT_CHUNK = 128


def _batched_dot(a, b, contract_a: int, contract_b: int):
    """3-D batched contraction over the leading router axis (MXU, f32)."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((contract_a,), (contract_b,)), ((0,), (0,))),
        precision=_HI, preferred_element_type=jnp.float32)


def mega_vmem_bytes(blocks, chunk_bytes: int) -> int:
    """Scoped VMEM one launch asks for, from its ``(block shape, dtype)``
    operand/result list: every block double-buffered by the pipeline, plus
    room for four f32 temporaries the size of one tape chunk."""
    return (2 * sum(block_vmem_bytes(s, d) for s, d in blocks)
            + 4 * chunk_bytes)


def mega_window_pallas(state, est, obs_carry, params,
                       arrival: jnp.ndarray, hazard: jnp.ndarray,
                       obs_valid: jnp.ndarray | None,
                       k_env: jnp.ndarray, gumbel: jnp.ndarray,
                       t0: jnp.ndarray, *,
                       cfg, disc, util_edges, util_period: int, dt: float,
                       scrape_every: int, restart_blackout: bool,
                       emits_mask: bool, interpret: bool):
    """Pallas dispatch of one whole window; signature/result match
    :func:`repro.core.mega.mega_window`.

    ``t0`` must sit on a dwell boundary (the engine only launches windows
    there) so the selecting/held tick structure is compiled statically.
    ``interpret`` is deliberately required, as for the per-tick kernels —
    only the :mod:`..ops` wrapper auto-detects the backend.
    """
    topo = cfg.topology
    slots, cache = state.slots, state.cache
    r, j, s = slots.q_prev.shape
    m, nb, k_t = topo.n_modalities, topo.max_bins, topo.n_tiers
    mnb = m * nb
    a_n = cfg.n_actions
    p_n = mega_core.n_proj(topo)
    w_ticks = gumbel.shape[0]
    dwell = max(int(cfg.action_dwell_s / cfg.fast_period_s), 1)
    br = SUBLANES
    r_pad = pad_rows(r, br)
    # the slot tape is folded in J_c-slot chunks (code size independent of
    # the horizon), the last one possibly short
    j_chunk = min(j, SLOT_CHUNK)
    slot_dtype = slots.q_prev.dtype

    # ---- static closure constants (inlined into the kernel) ---------------
    edges_list = [np.asarray(e, np.float32) for e in disc.modality_edges()]
    uedges = np.asarray(util_edges, np.float32)
    sf_tbl = np.ascontiguousarray(
        np.asarray(spaces.state_factor_table(topo))[:, 2:2 + k_t].T)
    eps_u = 0.15                      # belief.util_log_likelihood default
    # evaluate the shared jnp-valued model constants eagerly (the wrapper is
    # usually traced under the engine's jit — these must be embeddable)
    with jax.ensure_compile_time_eval():
        logc_nom_j, logc_uns_j = preferences.preference_log_tables(cfg)
        logc_tbl = np.stack([np.asarray(logc_nom_j).reshape(mnb),
                             np.asarray(logc_uns_j).reshape(mnb)])
        cost = np.asarray(cfg.cost_weight
                          * policies.policy_concentration_cost(topo),
                          np.float32)
        ptable = np.asarray(policies.policy_table(topo), np.float32)
    err_ix = topo.modalities.index("error")
    err_decay = 0.5 ** (cfg.fast_period_s / cfg.error_ema_halflife_s)
    u_c = cfg.b_prior_uniform / s
    d_c = cfg.b_prior_sticky
    masked_obs = emits_mask or obs_valid is not None or restart_blackout

    # ---- kernel ----------------------------------------------------------
    def kernel(t0_ref, qp_ref, qn_ref, colsum_ref, proj_ref, projsum_ref,
               qnproj_ref, coefact_ref, logna_ref, belief_ref, pa_ref,
               scal_ref, obsm_ref, tutil_ref, envk_ref, envr_ref, pstack_ref,
               arr_ref, haz_ref, unif_ref, gum_ref,
               sftbl_ref, logc_ref, cost_ref, ptab_ref, *rest):
        if obs_valid is not None:
            ov_ref = rest[0]
            rest = rest[1:]
        qn_out, tr_act, tr_r, tr_rk, tr_rm, envk_out, envr_out = rest

        t0_v = t0_ref[0, 0]
        colsum = colsum_ref[...]                                 # (BR,A,S)
        ptab = ptab_ref[...]                                     # (A, K)

        servers, mu_t, svc_mean, p95f, queue_cap, p_unst = (
            pstack_ref[0], pstack_ref[1], pstack_ref[2], pstack_ref[3],
            pstack_ref[4], pstack_ref[5])
        r_base, r_load, r_knee, r_shock, r_min, r_max = (
            pstack_ref[6], pstack_ref[7], pstack_ref[8], pstack_ref[9],
            pstack_ref[10], pstack_ref[11])
        timeout_s = pstack_ref[12][:, 0:1]
        a_lat = jnp.minimum(1.0, 2.0 * dt / pstack_ref[13][:, 0:1])
        a_err = jnp.minimum(1.0, 2.0 * dt / pstack_ref[14][:, 0:1])
        a_rps = jnp.minimum(1.0, 2.0 * dt / pstack_ref[15][:, 0:1])
        cap_rate = servers * mu_t

        act_iota = jax.lax.broadcasted_iota(jnp.int32, (1, a_n), 1)
        bin_lane = jax.lax.broadcasted_iota(jnp.int32, (1, mnb), 1)

        def rowsum(x):
            return jnp.sum(x, axis=-1, keepdims=True)

        def count_ge(col, edges):
            b = jnp.zeros(col.shape, jnp.int32)
            for e in edges:
                b = b + (col >= e).astype(jnp.int32)
            return b

        def over_slots(body, init):
            """Fold ``body(slot_slice, acc)`` over the tape in J_c chunks."""
            n_full, tail = divmod(j, j_chunk)
            if n_full == 1:
                acc = body(pl.ds(0, j_chunk), init)
            else:
                acc = jax.lax.fori_loop(
                    0, n_full, lambda c, a: body(pl.ds(
                        pl.multiple_of(c * j_chunk, j_chunk), j_chunk), a),
                    init)
            if tail:
                acc = body(pl.ds(n_full * j_chunk, tail), acc)
            return acc

        def tick(w, c, select: bool):
            """One fast tick at window offset ``w`` (static or traced);
            ``select`` compiles the EFE + sampling path (dwell boundary)."""
            t_idx = t0_v + w
            raw_obs, obs_mask, belief = c["raw_obs"], c["obs_mask"], c["belief"]
            prev_action = c["prev_action"]
            mask = obs_mask if emits_mask else None

            # ---- observe: discretize published telemetry + util scrape
            obs_bins = [count_ge(raw_obs[:, i:i + 1], edges_list[i])
                        for i in range(m)]                       # M x (BR,1)
            # utilization bins in state-factor order (heaviest tier first)
            util_bins = [count_ge(c["tier_util"][:, k_t - 1 - i:k_t - i],
                                  uedges) for i in range(k_t)]
            util_valid = ((t_idx % util_period) == 0) & (t_idx > 0)

            # ---- adaptive-preference error EMA (holds when masked)
            error_ema = (err_decay * c["error_ema"]
                         + (1.0 - err_decay) * raw_obs[:, err_ix:err_ix + 1])
            if mask is not None:
                error_ema = jnp.where(mask[:, err_ix:err_ix + 1] > 0,
                                      error_ema, c["error_ema"])
            unstable = error_ema > cfg.error_trigger             # (BR, 1)

            # ---- evidence: one-hot A gather + gated utilization scrape
            loglik = jnp.zeros_like(belief)
            for m_i in range(m):
                pm = jnp.zeros_like(belief)
                for b_i in range(nb):
                    sel = (obs_bins[m_i] == b_i).astype(jnp.float32)
                    pm = pm + sel * logna_ref[:, m_i, b_i, :]
                if mask is not None:
                    pm = pm * mask[:, m_i:m_i + 1]
                loglik = loglik + pm
            util_ll = jnp.zeros_like(belief)
            for k in range(k_t):
                match = sftbl_ref[k:k + 1, :] == util_bins[k]    # (BR, S)
                util_ll = util_ll + jnp.log(jnp.where(
                    match, 1.0 - eps_u, eps_u / (topo.n_levels - 1)))
            loglik = loglik + jnp.where(util_valid, util_ll, 0.0)

            # ---- factored belief update (prior never materializes B)
            oh_pa = (prev_action == act_iota).astype(jnp.float32)  # (BR, A)
            csum = jnp.sum(oh_pa[:, :, None] * colsum, axis=1)   # (BR, S)
            qt = belief / csum

            def prior_slots(js, acc):
                cw = jnp.sum(coefact_ref[:, js, :] * oh_pa[:, None, :],
                             axis=-1, keepdims=True)             # (BR,Jc,1)
                qp = qp_ref[:, js, :].astype(jnp.float32)
                pend_p = cw * _batched_dot(qp, qt[:, None, :], 2, 2)
                return acc + jnp.sum(
                    pend_p * qn_ref[:, js, :].astype(jnp.float32), axis=1)

            slot = over_slots(prior_slots, jnp.zeros_like(belief))
            num = u_c * rowsum(qt) + d_c * qt + slot
            prior = num / jnp.maximum(rowsum(num), 1e-30)
            logp = loglik + jnp.log(jnp.maximum(prior, 1e-30))
            logp = logp - jnp.max(logp, axis=-1, keepdims=True)
            q_un = jnp.exp(logp)
            q_next = q_un / jnp.maximum(rowsum(q_un), 1e-30)

            # ---- EFE + categorical via pre-drawn Gumbel (selecting ticks)
            if select:
                logc = jnp.where(unstable, logc_ref[1:2, :],
                                 logc_ref[0:1, :])               # (BR, M·NB)
                qa = q_next[:, None, :] / colsum                 # (BR, A, S)
                sqa = jnp.sum(qa, axis=-1)                       # (BR, A)

                def efe_slots(js, acc):
                    qp = qp_ref[:, js, :].astype(jnp.float32)
                    pend = coefact_ref[:, js, :] * _batched_dot(qp, qa, 2, 2)
                    return acc + _batched_dot(pend, qnproj_ref[:, js, :],
                                              1, 1)
                # slot terms of the (P) projections and, last, of Σ_t ŝ
                o_slot = over_slots(
                    efe_slots, jnp.zeros((br, a_n, p_n + 1), jnp.float32))
                o_num = (u_c * sqa[:, :, None]
                         * projsum_ref[...][:, None, :]
                         + d_c * _batched_dot(qa, proj_ref[...], 2, 2)
                         + o_slot[:, :, :p_n])                   # (BR, A, P)
                sden = jnp.maximum((u_c * s + d_c) * sqa + o_slot[:, :, p_n],
                                   1e-30)                        # (BR, A)
                o_pred = o_num / sden[:, :, None]
                o_obs = o_pred[:, :, :mnb]
                terms = jnp.where(
                    o_obs > 1e-20,
                    o_obs * (jnp.log(jnp.maximum(o_obs, 1e-30))
                             - logc[:, None, :]), 0.0)
                amb_rows = o_pred[:, :, mnb:]                    # (BR, A, M)
                if mask is not None:
                    maskb = jnp.zeros((br, mnb), jnp.float32)
                    for m_i in range(m):
                        in_m = ((bin_lane >= m_i * nb)
                                & (bin_lane < (m_i + 1) * nb))
                        maskb = jnp.where(in_m, mask[:, m_i:m_i + 1], maskb)
                    terms = terms * maskb[:, None, :]
                    ambiguity = jnp.sum(amb_rows * mask[:, None, :],
                                        axis=-1)
                else:
                    ambiguity = jnp.sum(amb_rows, axis=-1)
                g = jnp.sum(terms, axis=-1) + ambiguity + cost_ref[...]
                probs = jax.nn.softmax(-cfg.beta * g, axis=-1)
                action = jnp.argmax(
                    jnp.log(jnp.maximum(probs, 1e-30)) + gum_ref[w],
                    axis=-1, keepdims=True).astype(jnp.int32)    # (BR, 1)
            else:
                action = prev_action

            # ---- dwell gate (selecting structure is static per window)
            dtc = jnp.where(action != prev_action, 0.0,
                            c["dtc"] + cfg.fast_period_s)
            obs_frac = jnp.mean(obs_mask, axis=-1, keepdims=True)
            qn_out[w] = q_next

            # ---- routing weights (one-hot row select) + fluid env window
            backlog, down_left = c["backlog"], c["down_left"]
            oh_act = (action == act_iota).astype(jnp.float32)
            weights = jnp.sum(oh_act[:, :, None] * ptab[None], axis=1)
            w_n = jnp.maximum(weights, 0.0)
            w_n = w_n / jnp.maximum(rowsum(w_n), 1e-12)
            up = down_left <= _EPS
            upf = up.astype(jnp.float32)
            arr_w = arr_ref[w]                                   # (BR, 1)
            lam = w_n * arr_w
            arr_mass = lam * dt
            refused = rowsum(arr_mass * (1.0 - upf))
            cap = cap_rate * dt * upf
            avail = backlog + arr_mass * upf
            served = jnp.minimum(avail, cap)
            backlog1 = avail - served
            over = jnp.maximum(backlog1 - (queue_cap + servers), 0.0)
            backlog1 = backlog1 - over
            wait = jnp.where(
                cap_rate > 0,
                0.5 * (backlog + backlog1) / jnp.maximum(cap_rate, _EPS),
                0.0)
            tier_latency = wait + svc_mean
            tier_p95 = wait + svc_mean * p95f
            timed_out = jnp.where(tier_latency > timeout_s, served, 0.0)
            completed = served - timed_out
            util = jnp.where(cap > 0,
                             served / jnp.maximum(cap_rate * dt, _EPS), 0.0)
            util_accum = c["util_accum"] + util * dt
            scrape_now = ((t_idx + 1) % scrape_every) == 0
            util_scrape = jnp.where(scrape_now,
                                    util_accum / (scrape_every * dt),
                                    c["util_scrape"])
            util_accum = jnp.where(scrape_now, 0.0, util_accum)
            hazard_w = haz_ref[w] * p_unst * (
                r_base
                + r_load * jnp.maximum(0.0, util_scrape - r_knee)
                + r_shock * jnp.maximum(0.0, lam - c["prev_tier_rps"])
                / jnp.maximum(cap_rate, _EPS))
            p_restart = 1.0 - jnp.exp(-hazard_w * dt)
            restarted = (up & (unif_ref[w, 0] < p_restart)).astype(
                jnp.float32)
            killed = backlog1 * restarted
            backlog = backlog1 * (1.0 - restarted)
            dur = r_min + unif_ref[w, 1] * (r_max - r_min)
            down_left = jnp.maximum(down_left - dt, 0.0)
            down_left = jnp.where(restarted > 0, dur, down_left)

            win_success = rowsum(completed)
            win_fail = (refused + rowsum(over) + rowsum(timed_out)
                        + rowsum(killed))

            # completion-weighted P95, sort-free: the atom whose cumulative
            # completion mass (under the stable lat-then-index order the
            # oracle's argsort induces) crosses 0.95
            tot = jnp.maximum(win_success, _EPS)
            p95_win = jnp.zeros_like(tot)
            for i in range(k_t):
                p_i = tier_p95[:, i:i + 1]
                c_i = jnp.zeros_like(tot)
                for jj in range(k_t):
                    p_j = tier_p95[:, jj:jj + 1]
                    done_j = completed[:, jj:jj + 1]
                    if jj == i:
                        c_i = c_i + done_j
                        continue
                    before = p_j < p_i
                    if jj < i:
                        before = before | (p_j == p_i)
                    c_i = c_i + jnp.where(before, done_j, 0.0)
                first = ((c_i / tot >= 0.95)
                         & ((c_i - completed[:, i:i + 1]) / tot < 0.95))
                p95_win = p95_win + jnp.where(first, p_i, 0.0)

            p95_ema = jnp.where(win_success > _EPS,
                                (1 - a_lat) * c["p95_ema"] + a_lat * p95_win,
                                c["p95_ema"])
            total_win = win_success + win_fail
            err_frac = win_fail / jnp.maximum(total_win, _EPS)
            err_ema_env = jnp.where(total_win > _EPS,
                                    (1 - a_err) * c["err_ema_env"]
                                    + a_err * err_frac, c["err_ema_env"])
            rps_ema = (1 - a_rps) * c["rps_ema"] + a_rps * arr_w
            tier_queue = jnp.maximum(backlog - servers, 0.0)
            fresh = jnp.concatenate([p95_ema, rps_ema, rowsum(tier_queue),
                                     err_ema_env], axis=-1)      # (BR, M)
            if not masked_obs:
                win_mask = jnp.ones_like(fresh)
                published = fresh
            else:
                win_mask = (ov_ref[w] if obs_valid is not None
                            else jnp.ones_like(fresh))
                if restart_blackout:
                    cell_up = jnp.max(down_left, axis=-1,
                                      keepdims=True) <= _EPS
                    win_mask = win_mask * cell_up.astype(jnp.float32)
                    util_scrape = jnp.where(cell_up, util_scrape,
                                            c["util_scrape"])
                published = jnp.where(win_mask > 0, fresh, c["held_obs"])

            tr_act[w] = action
            tr_r[w] = jnp.concatenate(
                [win_success, win_fail, unstable.astype(jnp.float32),
                 obs_frac, dtc, error_ema], axis=-1)
            tr_rk[w, 0] = weights
            tr_rk[w, 1] = util_scrape
            tr_rk[w, 2] = (down_left <= _EPS).astype(jnp.float32)
            tr_rk[w, 3] = tier_queue
            tr_rk[w, 4] = tier_latency
            tr_rk[w, 5] = tier_p95
            tr_rk[w, 6] = completed
            tr_rk[w, 7] = restarted
            tr_rm[w, 0] = published
            tr_rm[w, 1] = win_mask
            tr_rm[w, 2] = raw_obs

            acct = jnp.concatenate(
                [rowsum(arr_mass), win_success, rowsum(timed_out),
                 rowsum(over), refused, rowsum(killed)], axis=-1)
            return dict(
                belief=q_next, prev_action=action, dtc=dtc,
                error_ema=error_ema, raw_obs=published,
                obs_mask=win_mask if emits_mask else obs_mask,
                held_obs=published, tier_util=util_scrape,
                backlog=backlog, down_left=down_left, util_accum=util_accum,
                util_scrape=util_scrape, prev_tier_rps=lam,
                tier_requests=c["tier_requests"] + arr_mass,
                tier_success=c["tier_success"] + completed,
                n_restarts=c["n_restarts"] + restarted,
                p95_ema=p95_ema, rps_ema=rps_ema, err_ema_env=err_ema_env,
                acct=c["acct"] + acct)

        carry = dict(
            belief=belief_ref[...], prev_action=pa_ref[...],
            dtc=scal_ref[:, 0:1], error_ema=scal_ref[:, 1:2],
            raw_obs=obsm_ref[0], obs_mask=obsm_ref[1], held_obs=obsm_ref[2],
            tier_util=tutil_ref[...],
            backlog=envk_ref[0], down_left=envk_ref[1],
            util_accum=envk_ref[2], util_scrape=envk_ref[3],
            prev_tier_rps=envk_ref[4], tier_requests=envk_ref[5],
            tier_success=envk_ref[6], n_restarts=envk_ref[7],
            p95_ema=envr_ref[:, 0:1], rps_ema=envr_ref[:, 1:2],
            err_ema_env=envr_ref[:, 2:3], acct=envr_ref[:, 3:9])

        # dwell blocks: a selecting tick, then dwell-1 held ticks (t0 sits
        # on a dwell boundary); the tail block of a short window is unrolled
        def dwell_block(b, c):
            w0 = b * dwell
            c = tick(w0, c, select=True)
            return jax.lax.fori_loop(
                1, dwell, lambda i, cc: tick(w0 + i, cc, select=False), c)

        n_blocks, tail = divmod(w_ticks, dwell)
        carry = jax.lax.fori_loop(0, n_blocks, dwell_block, carry)
        for i in range(tail):
            carry = tick(n_blocks * dwell + i, carry, select=(i == 0))

        # ---- final env carries back to HBM (once per window, not per tick)
        for i, name in enumerate(("backlog", "down_left", "util_accum",
                                  "util_scrape", "prev_tier_rps",
                                  "tier_requests", "tier_success",
                                  "n_restarts")):
            envk_out[i] = carry[name]
        envr_out[...] = jnp.concatenate(
            [carry["p95_ema"], carry["rps_ema"], carry["err_ema_env"],
             carry["acct"]], axis=-1)

    # ---- operands (cell axis on the sublanes, padded to the block) --------
    def draws(k):
        k_fire, k_dur = jax.random.split(k)
        return jnp.stack([jax.random.uniform(k_fire, (r, k_t)),
                          jax.random.uniform(k_dur, (r, k_t))])
    uniforms = jax.vmap(draws)(k_env)                            # (W,2,R,K)

    pstack = jnp.stack(
        [params.servers, params.mu, params.service_mean_s,
         params.service_p95_factor, params.queue_cap, params.unstable,
         params.restart_base, params.restart_load, params.restart_knee,
         params.restart_shock, params.restart_min_s, params.restart_max_s]
        + [jnp.broadcast_to(v, (r, k_t)) for v in
           (params.timeout_s, params.latency_window_s,
            params.error_window_s, params.rps_window_s)])        # (16,R,K)
    envk = jnp.stack([est.backlog, est.down_left, est.util_accum,
                      est.util_scrape, est.prev_tier_rps,
                      est.tier_requests, est.tier_success,
                      est.n_restarts])                           # (8, R, K)
    envr = jnp.stack([est.p95_ema, est.rps_ema, est.err_ema,
                      est.n_requests, est.n_success, est.err_timeout,
                      est.err_overflow, est.err_refused,
                      est.err_restart], axis=-1)                 # (R, 9)
    raw_obs0, tier_util0, tier_up0, tier_queue0, obs_mask0 = obs_carry
    obsm = jnp.stack([raw_obs0, obs_mask0, est.held_obs])        # (3, R, M)

    # (operand, cell axis, block shape); the block spans the cell axis in
    # router blocks and every other axis whole
    cells = [
        (slots.q_prev, 0), (slots.q_next, 0),
        (cache.colsum, 0), (cache.proj, 0),
        (cache.projsum, 0),
        (jnp.concatenate([cache.qnproj, cache.sumqn[..., None]], axis=-1),
         0),
        (cache.coefact, 0), (cache.logna, 0),
        (state.belief, 0), (state.prev_action[:, None], 0),
        (jnp.stack([state.dt_since_change, state.error_ema], axis=-1), 0),
        (obsm, 1), (tier_util0, 0), (envk, 1), (envr, 0), (pstack, 1),
        (arrival[..., None], 1), (hazard, 1), (uniforms, 2), (gumbel, 1),
    ]
    n_cells = len(cells)
    shared = [sf_tbl, logc_tbl, cost[None], ptable]
    if obs_valid is not None:
        cells.append((jnp.asarray(obs_valid, jnp.float32), 1))

    def cell_spec(shape, axis):
        block = shape[:axis] + (br,) + shape[axis + 1:]
        zeros = (0,) * len(shape)
        return pl.BlockSpec(
            block, lambda i: zeros[:axis] + (i,) + zeros[axis + 1:])

    def full_spec(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    cell_ops = [pad_cells(x, r_pad, axis) for x, axis in cells]
    cell_specs = [cell_spec(x.shape, axis)
                  for x, (_, axis) in zip(cell_ops, cells)]
    in_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)]
                + cell_specs[:n_cells] + [full_spec(c.shape) for c in shared]
                + cell_specs[n_cells:])
    operands = ([jnp.asarray(t0, jnp.int32).reshape(1, 1)]
                + cell_ops[:n_cells] + [jnp.asarray(c) for c in shared]
                + cell_ops[n_cells:])

    out_cells = [
        ((w_ticks, r_pad, s), jnp.float32, 1),           # posteriors
        ((w_ticks, r_pad, 1), jnp.int32, 1),             # applied action
        ((w_ticks, r_pad, 6), jnp.float32, 1),           # per-cell scalars
        ((w_ticks, 8, r_pad, k_t), jnp.float32, 2),      # per-tier trace
        ((w_ticks, 3, r_pad, m), jnp.float32, 2),        # telemetry trace
        ((8, r_pad, k_t), jnp.float32, 1),               # env (R, K) carry
        ((r_pad, 9), jnp.float32, 0),                    # env (R,) carry
    ]
    out_shapes = [jax.ShapeDtypeStruct(sh, dt_) for sh, dt_, _ in out_cells]
    out_specs = [cell_spec(sh, ax) for sh, _, ax in out_cells]

    blocks = ([(sp.block_shape, x.dtype)
               for sp, x in zip(in_specs[1:], operands[1:])]
              + [(sp.block_shape, sh.dtype)
                 for sp, sh in zip(out_specs, out_shapes)])
    vmem = mega_vmem_bytes(
        blocks, block_vmem_bytes((br, j_chunk, s), jnp.float32))
    if vmem > VMEM_LIMIT:
        raise ValueError(
            f"megakernel launch needs {vmem / 2**20:.0f} MiB of VMEM for a "
            f"{j}-slot resident tape (limit {VMEM_LIMIT / 2**20:.0f} MiB): "
            f"the horizon is too long for the resident-tape kernel — run it "
            f"with use_pallas=False")

    outs = pl.pallas_call(
        kernel,
        grid=(r_pad // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*operands)
    qn_w, tr_act, tr_r, tr_rk, tr_rm, envk_o, envr_o = outs
    qn_w = qn_w[:, :r]                                           # (W, R, S)
    tr_act, tr_r = tr_act[:, :r, 0], tr_r[:, :r]
    tr_rk, tr_rm = tr_rk[:, :, :r], tr_rm[:, :, :r]
    envk_o, envr_o = envk_o[:, :r], envr_o[:r]

    # ---- land the window's slot block (slot index == global tick) --------
    def prepend(first, per_tick):
        return jnp.concatenate([first[None], per_tick[:-1]], axis=0)

    push_mask = (prepend(obs_mask0, tr_rm[:, 1]) if emits_mask
                 else jnp.ones((w_ticks,) + obs_mask0.shape, jnp.float32))
    pushes = dict(
        q_prev=prepend(state.belief, qn_w), q_next=qn_w,
        obs_bins=spaces.discretize_observation(tr_rm[:, 2], disc),
        obs_mask=push_mask,
        action=prepend(state.prev_action, tr_act),
        dt_since_change=prepend(state.dt_since_change, tr_r[..., 4]))
    new_slots = slots._replace(**{
        name: jax.lax.dynamic_update_slice_in_dim(
            getattr(slots, name),
            jnp.swapaxes(val, 0, 1).astype(getattr(slots, name).dtype),
            t0, axis=1)
        for name, val in pushes.items()})

    new_state = state._replace(
        slots=new_slots, belief=qn_w[-1], prev_action=tr_act[-1],
        dt_since_change=tr_r[-1, :, 4], error_ema=tr_r[-1, :, 5],
        unstable=tr_r[-1, :, 2] > 0.5, t=state.t + w_ticks)
    new_est = batched.FluidState(
        backlog=envk_o[0], down_left=envk_o[1], util_accum=envk_o[2],
        util_scrape=envk_o[3], prev_tier_rps=envk_o[4],
        p95_ema=envr_o[:, 0], rps_ema=envr_o[:, 1], err_ema=envr_o[:, 2],
        held_obs=tr_rm[-1, 0],
        n_requests=envr_o[:, 3], n_success=envr_o[:, 4],
        err_timeout=envr_o[:, 5], err_overflow=envr_o[:, 6],
        err_refused=envr_o[:, 7], err_restart=envr_o[:, 8],
        tier_requests=envk_o[5], tier_success=envk_o[6],
        n_restarts=envk_o[7])
    win = batched.WindowInfo(
        raw_obs=tr_rm[:, 0], obs_mask=tr_rm[:, 1],
        tier_utilization=tr_rk[:, 1], tier_up=tr_rk[:, 2],
        tier_queue=tr_rk[:, 3], tier_latency_s=tr_rk[:, 4],
        tier_p95_s=tr_rk[:, 5], tier_completed=tr_rk[:, 6],
        success=tr_r[..., 0], failures=tr_r[..., 1], restarted=tr_rk[:, 7])
    trace = (tr_act, tr_rk[:, 0], tr_rm[:, 2], tr_r[..., 2] > 0.5,
             tr_r[..., 3], win)
    new_carry = (tr_rm[-1, 0], tr_rk[-1, 1], tr_rk[-1, 2], tr_rk[-1, 3],
                 tr_rm[-1, 1] if emits_mask else obs_mask0)
    return new_state, new_est, new_carry, trace
