"""Plain reference of the AIF fleet router and its fluid environment.

Written from the paper's equations and the configuration file alone; it
imports nothing of the program.  Its routers run on a sample of the fleet's
cells, each with a dense (A, S, S) transition-count model, and it is fed
what the timed calls produced, as a language model's reference is fed the
served tokens:

* :func:`router` replays each sampled cell's router one tick at a time over
  the program's own history: the telemetry it consumed, the actions it
  applied and the posteriors it produced.  Each tick it computes the belief
  update (Eq. 2) from the program's previous posterior, the expected free
  energy and the Gumbel-max draw at every selecting tick from the program's
  new posterior, the dwell rule and the error EMA that switches
  preferences, and at every slow boundary the replayed A and B learning
  over the transitions the program pushed.  So every tick is checked by
  itself, and rounding cannot compound over the horizon.  It returns its
  posteriors, its scores at each selecting tick and the action it would
  have drawn.
* :func:`environment` advances every cell's fluid environment under the
  routing weights of the actions the program applied and returns what the
  window publishes and counts.
* :func:`summary` reduces every cell's per-tick outcome to the fleet's
  success %, P50/P95 and tier shares.

Randomness is the program's stated key tree from ``Experiment.seed``: per
tick ``k, k_env, k_agents = split(k, 3)``, one key per cell from
``split(k_agents, R)``, split again into the tick's Gumbel key and the slow
boundary's replay key; restarts draw ``uniform`` over the whole (R, K) fleet
from ``split(k_env)``.

``precision`` sets the contractions: ``"highest"`` is float32 (the
configuration's own precision); ``"high"`` is the three-pass bfloat16
product, the control one step below.  ``environment`` takes a ``dtype`` the
same way (float32, or bfloat16 for the control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_EPS = 1e-9
#: Score gap recorded where the program changed its action on a held tick
#: (the dwell rule allows a change only on a selecting tick).
DWELL_BREACH = 1e3


def contract(eq: str, x, y, precision: str):
    """``einsum(eq, x, y)`` in float32 (``"highest"``) or as three bfloat16
    passes (``"high"``): hi·hi + hi·lo + lo·hi, each exact in float32."""
    if precision == "highest":
        return jnp.einsum(eq, x, y, precision=_HIGHEST)
    if precision == "high":
        def split(a):
            hi = a.astype(jnp.bfloat16).astype(jnp.float32)
            return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
        (xh, xl), (yh, yl) = split(x), split(y)

        def f(a, b):
            return jnp.einsum(eq, a, b, precision=_HIGHEST)
        return f(xh, yh) + f(xh, yl) + f(xl, yh)
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------------ model
class Model:
    """Static tables of one configuration, built from its file."""

    def __init__(self, cfg: dict):
        ag = cfg["agent"]
        self.cfg, self.ag = cfg, ag
        self.s, self.k = int(cfg["n_states"]), int(cfg["n_tiers"])
        self.levels = int(cfg["n_levels"])
        self.n_bins = tuple(int(b) for b in cfg["n_bins"])
        self.m, self.nb = len(self.n_bins), max(self.n_bins)
        self.table = np.asarray(cfg["policy_table"], np.float32)   # (A, K)
        self.a = self.table.shape[0]
        self.mask = np.zeros((self.m, self.nb), np.float32)
        for i, n in enumerate(self.n_bins):
            self.mask[i, :n] = 1.0
        # state s = (latency, rate, u_{K-1}, ..., u_0) row-major, latency first
        n_f = 2 + self.k
        digits = np.zeros((self.s, n_f), np.int32)
        for s in range(self.s):
            x = s
            for f in reversed(range(n_f)):
                digits[s, f] = x % self.levels
                x //= self.levels
        self.util_levels = digits[:, 2:]                         # (S, K)
        width = max(len(e) for e in cfg["obs_edges"])
        self.edges = np.full((self.m, width), np.inf, np.float32)
        for i, e in enumerate(cfg["obs_edges"]):
            self.edges[i, :len(e)] = e
        self.top_bin = np.asarray([len(e) for e in cfg["obs_edges"]], np.int32)
        self.util_edges = np.asarray(cfg["util_edges"], np.float32)
        self.err_ix = cfg["modalities"].index("error")
        self.logc_nom = self._log_pref(unstable=False)
        self.logc_uns = self._log_pref(unstable=True)
        w = np.clip(self.table.astype(np.float64), 1e-12, 1.0)
        self.cost = (ag["cost_weight"]
                     * (np.log(self.k) + np.sum(w * np.log(w), -1))
                     ).astype(np.float32)
        self.dwell = max(int(ag["action_dwell_s"] / ag["fast_period_s"]), 1)
        self.period = max(int(ag["slow_period_s"] / ag["fast_period_s"]), 1)
        self.decay = 0.5 ** (ag["fast_period_s"] / ag["error_ema_halflife_s"])

    def _log_pref(self, unstable: bool) -> np.ndarray:
        """log σ(C) per modality over its valid bins (0 on padded bins)."""
        ag = self.ag
        named = {"latency": ag["c_latency"], "rps": ag["c_rps"],
                 "queue": ag["c_queue"], "error": ag["c_error_ok"]}
        out = np.zeros((self.m, self.nb), np.float32)
        for i, name in enumerate(self.cfg["modalities"]):
            n = self.n_bins[i]
            row = list(named.get(name, [0.0]))
            row = (row + row[-1:] * n)[:n]
            row = np.asarray(row, np.float64)
            if unstable and name == "latency":
                row = row * ag["latency_relax_factor"]
            if unstable and name == "error":
                u = list(ag["c_error_unstable"])
                row = np.asarray((u + u[-1:] * n)[:n], np.float64)
            row = row.astype(np.float32).astype(np.float64)
            out[i, :n] = row - np.log(np.sum(np.exp(row - row.max()))) - row.max()
        return out

    def init_counts(self):
        s = self.s
        a0 = self.ag["a_prior_count"] * np.broadcast_to(
            self.mask[:, :, None], (self.m, self.nb, s)).astype(np.float32)
        b0 = (self.ag["b_prior_uniform"] / s
              + self.ag["b_prior_sticky"] * np.eye(s, dtype=np.float32))
        b0 = np.broadcast_to(b0.astype(np.float32), (self.a, s, s))
        return jnp.asarray(a0), jnp.asarray(b0)


# ------------------------------------------------------------ randomness
@functools.partial(jax.jit, static_argnames=("n_ticks", "n_cells", "n_actions",
                                             "n_tiers"))
def keys_and_draws(seed_key, rows, *, n_ticks: int, n_cells: int,
                   n_actions: int, n_tiers: int):
    """Per tick: the sampled cells' Gumbel noise (T, n, A) and replay keys
    (T, n), and the whole fleet's restart draws (T, R, K) x 2."""
    def body(k, _):
        k, k_env, k_agents = jax.random.split(k, 3)
        cell_keys = jax.random.split(k_agents, n_cells)[rows]
        ks = jax.vmap(jax.random.split)(cell_keys)
        gum = jax.vmap(lambda kk: jax.random.gumbel(kk, (n_actions,)))(ks[:, 0])
        k_fire, k_dur = jax.random.split(k_env)
        u = jax.random.uniform(k_fire, (n_cells, n_tiers))
        du = jax.random.uniform(k_dur, (n_cells, n_tiers))
        return k, (gum, ks[:, 1], u, du)

    _, out = jax.lax.scan(body, seed_key, None, length=n_ticks)
    return out


# ------------------------------------------------------------------ router
def router(model: Model, raw_obs, util_seen, actions, q_prev, q_next,
           gumbel, slow_keys, precision: str = "highest"):
    """Replay the sampled cells' routers over the program's history.

    Args (leading axes: T ticks, n cells):
      raw_obs: (T, n, M) telemetry each tick's belief update consumed.
      util_seen: (T, n, K) per-tier utilization scrape it consumed.
      actions: (T, n) actions the program applied.
      q_prev, q_next: (T, n, S) the program's posterior before and after
        each tick.
      gumbel: (T, n, A); slow_keys: (T, n) replay keys.

    Returns dict of (n, T, S) beliefs, (n, T/dwell, A) scores at selecting
    ticks, (n, T/dwell) own draws, (n, T) score gap of the program's action
    (0 on held ticks that keep the action), (T, n) instability flags.
    """
    t_n = raw_obs.shape[0]
    if t_n % model.period:
        raise ValueError(f"horizon {t_n} is not a whole number of slow "
                         f"periods ({model.period})")
    fn = _router_fn(model, t_n, precision)
    return fn(jnp.asarray(raw_obs, jnp.float32),
              jnp.asarray(util_seen, jnp.float32),
              jnp.asarray(actions, jnp.int32),
              jnp.asarray(q_prev, jnp.float32),
              jnp.asarray(q_next, jnp.float32), jnp.asarray(gumbel),
              slow_keys)


@functools.lru_cache(maxsize=8)
def _router_fn(model: Model, t_n: int, precision: str):
    ag, s, m, nb, a_n, k = model.ag, model.s, model.m, model.nb, model.a, model.k
    period, dwell = model.period, model.dwell
    mask = jnp.asarray(model.mask)
    edges = jnp.asarray(model.edges)
    top_bin = jnp.asarray(model.top_bin)
    uedges = jnp.asarray(model.util_edges)
    ulev = jnp.asarray(model.util_levels)
    logc_nom, logc_uns = jnp.asarray(model.logc_nom), jnp.asarray(model.logc_uns)
    cost = jnp.asarray(model.cost)
    eps_u = ag["util_eps"]
    c = functools.partial(contract, precision=precision)

    def normalized_a(a):
        counts = a * mask[:, :, None]
        return counts / jnp.maximum(jnp.sum(counts, axis=1, keepdims=True),
                                    1e-30)

    def tick(st, t, raw, util, prog_a, belief, q_prog, gum, selecting: bool):
        a, b, prev, dtc, ema = st
        bins = jnp.minimum(jnp.sum(raw[:, None] >= edges, -1), top_bin)
        ubins = jnp.sum(util[::-1][:, None] >= uedges, -1)
        uvalid = ((t % ag["util_period"]) == 0) & (t > 0)
        ema = model.decay * ema + (1.0 - model.decay) * raw[model.err_ix]
        unstable = ema > ag["error_trigger"]
        na = normalized_a(a)                                      # (M, NB, S)
        picked = jnp.take_along_axis(na, bins[:, None, None], axis=1)[:, 0]
        loglik = jnp.sum(jnp.log(jnp.maximum(picked, 1e-16)), axis=0)
        p_u = jnp.where(ulev == ubins[None, :], 1.0 - eps_u,
                        eps_u / (model.levels - 1))
        loglik = loglik + jnp.where(uvalid, jnp.sum(jnp.log(p_u), -1), 0.0)
        # prior B_{a_prev} q with B normalized over s' per column s
        b_prev = b[prev]                                          # (S', S)
        col = jnp.sum(b_prev, axis=0)
        prior = c("ts,s->t", b_prev, belief / jnp.maximum(col, 1e-30))
        prior = prior / jnp.maximum(jnp.sum(prior), 1e-30)
        logp = loglik + jnp.log(jnp.maximum(prior, 1e-30))
        q = jnp.exp(logp - jnp.max(logp))
        q = q / jnp.maximum(jnp.sum(q), 1e-30)
        if selecting:
            cols = jnp.sum(b, axis=1)                             # (A, S)
            s_pred = c("ats,as->at", b,
                       q_prog[None, :] / jnp.maximum(cols, 1e-30))
            s_pred = s_pred / jnp.maximum(jnp.sum(s_pred, -1, keepdims=True),
                                          1e-30)
            o = c("mbs,as->amb", na, s_pred)                      # (A, M, NB)
            logc = jnp.where(unstable, logc_uns, logc_nom)
            risk = jnp.sum(jnp.where((o > 0) & (mask[None] > 0),
                                     o * (jnp.log(jnp.maximum(o, 1e-30))
                                          - logc[None]), 0.0), axis=(1, 2))
            h = -jnp.sum(jnp.where(mask[:, :, None] > 0,
                                   na * jnp.log(jnp.maximum(na, 1e-16)), 0.0),
                         axis=1)                                  # (M, S)
            ambiguity = c("as,s->a", s_pred, jnp.sum(h, axis=0))
            g = risk + ambiguity + cost
            probs = jax.nn.softmax(-ag["beta"] * g)
            score = jnp.log(jnp.maximum(probs, 1e-30)) + gum
            own = jnp.argmax(score).astype(jnp.int32)
            gap = score[own] - score[prog_a]
        else:
            score, own = None, prev
            gap = jnp.where(prog_a != prev, DWELL_BREACH, 0.0)
        slot = (belief, q_prog, bins, prev, dtc)
        changed = prog_a != prev
        dtc = jnp.where(changed, 0.0, dtc + ag["fast_period_s"])
        st = (a, b, prog_a, dtc, ema)
        return st, slot, (q, gap, unstable, score, own)

    def learn(a, b, rep, t_after, key):
        qp, qn, ob, act, rdt = rep
        idx = jax.random.randint(key, (ag["replay_batch"],), 0,
                                 jnp.maximum(t_after, 1))
        onehot = (ob[idx][..., None] == jnp.arange(nb)).astype(jnp.float32)
        a = a + ag["alpha_a"] * c("nmb,ns->mbs", onehot, qn[idx])
        w = jax.nn.sigmoid((rdt[idx] - ag["settle_midpoint_s"])
                           / ag["settle_scale_s"])
        act_w = jax.nn.one_hot(act[idx], a_n, dtype=jnp.float32) * w[:, None]
        outer = qn[idx][:, :, None] * qp[idx][:, None, :]        # (n, S', S)
        b = b + ag["alpha_b"] * c("na,nts->ats", act_w, outer)
        return a, b

    def cell(raw, util, acts, qp, qn, gum, skeys):
        a0, b0 = model.init_counts()
        st = (a0, b0, jnp.int32(0), jnp.float32(0.0), jnp.float32(0.0))
        rep = (jnp.zeros((t_n, s)), jnp.zeros((t_n, s)),
               jnp.zeros((t_n, m), jnp.int32), jnp.zeros((t_n,), jnp.int32),
               jnp.zeros((t_n,)))
        xs = tuple(x.reshape((t_n // period, period) + x.shape[1:])
                   for x in (raw, util, acts, qp, qn, gum))
        sk = skeys.reshape(t_n // period, period)

        def period_body(carry, inp):
            st, rep = carry
            p, (raw_p, util_p, act_p, qp_p, qn_p, gum_p), sk_p = inp
            outs = []
            for w in range(period):
                t = p * period + w
                st, slot, out = tick(st, t, raw_p[w], util_p[w], act_p[w],
                                     qp_p[w], qn_p[w], gum_p[w],
                                     selecting=(w % dwell == 0))
                rep = tuple(jax.lax.dynamic_update_index_in_dim(r, v, t, 0)
                            for r, v in zip(rep, slot))
                outs.append(out)
            a, b = learn(st[0], st[1], rep, (p + 1) * period, sk_p[-1])
            st = (a, b) + st[2:]
            q = jnp.stack([o[0] for o in outs])
            gap = jnp.stack([o[1] for o in outs])
            unst = jnp.stack([o[2] for o in outs])
            score = jnp.stack([o[3] for o in outs if o[3] is not None])
            own = jnp.stack([o[4] for w, o in enumerate(outs)
                             if w % dwell == 0])
            return (st, rep), (q, gap, unst, score, own)

        _, ys = jax.lax.scan(period_body, (st, rep),
                             (jnp.arange(t_n // period), xs, sk))
        q, gap, unst, score, own = (y.reshape((-1,) + y.shape[2:]) for y in ys)
        return q, gap, unst, score, own

    @jax.jit
    def run(raw, util, acts, qp, qn, gum, skeys):
        q, gap, unst, score, own = jax.vmap(
            cell, in_axes=(1, 1, 1, 1, 1, 1, 1))(raw, util, acts, qp, qn, gum,
                                                 skeys)
        return {"belief": q, "gap": gap, "unstable": unst.T, "score": score,
                "own": own}

    return run


# ------------------------------------------------------------- environment
def environment(model: Model, params: dict, arrival, hazard, actions,
                u, du, dt: float, scrape_every: int, dtype=jnp.float32):
    """Advance the cells' fluid environments (no graph, no masks).

    Args: ``params`` of the cells ((n, K) per-tier arrays and scalar
    constants), ``arrival`` (T, n), ``hazard`` (T, n, K), ``actions`` (T, n),
    restart draws ``u``/``du`` (T, n, K).  Returns per-tick fields (T, n, ...)
    and the final cumulative counters (n, ...).
    """
    fn = _env_fn(model, int(arrival.shape[0]), float(dt), int(scrape_every),
                 jnp.dtype(dtype).name)
    par = {k_: jnp.asarray(v) for k_, v in params.items()}
    return fn(par, jnp.asarray(arrival), jnp.asarray(hazard),
              jnp.asarray(actions, jnp.int32), jnp.asarray(u), jnp.asarray(du))


@functools.lru_cache(maxsize=8)
def _env_fn(model: Model, t_n: int, dt: float, scrape_every: int,
            dtype_name: str):
    dt_ = jnp.dtype(dtype_name)
    table = jnp.asarray(model.table)

    @jax.jit
    def run(par, arrival, hazard, actions, u, du):
        p = {k_: v.astype(dt_) for k_, v in par.items()}
        n, k = p["servers"].shape
        z = jnp.zeros((n, k), dt_)
        z1 = jnp.zeros((n,), dt_)
        st0 = dict(backlog=z, down=z, uacc=z, uscr=z, prev_lam=z, p95=z1,
                   rps=z1, err=z1, n_req=z1, n_succ=z1, e_to=z1, e_ov=z1,
                   e_ref=z1, e_rs=z1, t_req=z, t_succ=z, n_rs=z)
        eps = jnp.asarray(_EPS, dt_)

        def step(st, xs):
            t, lam_tot, hz, act, u_t, du_t = xs
            lam_tot, hz = lam_tot.astype(dt_), hz.astype(dt_)
            w = jnp.maximum(table[act].astype(dt_), 0.0)
            w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-12)
            up = st["down"] <= eps
            upf = up.astype(dt_)
            lam = w * lam_tot[:, None]
            arr = lam * dt
            refused = jnp.sum(arr * (1.0 - upf), -1)
            cap_rate = p["servers"] * p["mu"]
            cap = cap_rate * dt * upf
            b0 = st["backlog"]
            avail = b0 + arr * upf
            served = jnp.minimum(avail, cap)
            b1 = avail - served
            syscap = p["queue_cap"] + p["servers"]
            over = jnp.maximum(b1 - syscap, 0.0)
            b1 = b1 - over
            wait = jnp.where(cap_rate > 0, 0.5 * (b0 + b1)
                             / jnp.maximum(cap_rate, eps), 0.0)
            lat = wait + p["service_mean_s"]
            p95 = wait + p["service_mean_s"] * p["service_p95_factor"]
            timed_out = jnp.where(lat > p["timeout_s"], served, 0.0)
            done = served - timed_out
            util = jnp.where(cap > 0, served / jnp.maximum(cap_rate * dt, eps),
                             0.0)
            uacc = st["uacc"] + util * dt
            scrape = ((t + 1) % scrape_every) == 0
            uscr = jnp.where(scrape, uacc / (scrape_every * dt), st["uscr"])
            uacc = jnp.where(scrape, 0.0, uacc)
            haz = hz * p["unstable"] * (
                p["restart_base"]
                + p["restart_load"] * jnp.maximum(0.0, uscr - p["restart_knee"])
                + p["restart_shock"] * jnp.maximum(0.0, lam - st["prev_lam"])
                / jnp.maximum(cap_rate, eps))
            p_rs = 1.0 - jnp.exp(-haz * dt)
            rs = (up & (u_t.astype(dt_) < p_rs)).astype(dt_)
            killed = b1 * rs
            b2 = b1 * (1.0 - rs)
            dur = p["restart_min_s"] + du_t.astype(dt_) * (
                p["restart_max_s"] - p["restart_min_s"])
            down = jnp.where(rs > 0, dur, jnp.maximum(st["down"] - dt, 0.0))
            succ = jnp.sum(done, -1)
            fail = (refused + jnp.sum(over, -1) + jnp.sum(timed_out, -1)
                    + jnp.sum(killed, -1))
            a_lat = jnp.minimum(1.0, 2.0 * dt / p["latency_window_s"])
            a_err = jnp.minimum(1.0, 2.0 * dt / p["error_window_s"])
            a_rps = jnp.minimum(1.0, 2.0 * dt / p["rps_window_s"])
            # completion-weighted P95 over the K tier atoms
            order = jnp.argsort(p95, -1)
            lat_s = jnp.take_along_axis(p95, order, -1)
            m_s = jnp.take_along_axis(done, order, -1)
            share = jnp.cumsum(m_s, -1) / jnp.maximum(
                jnp.sum(m_s, -1, keepdims=True), eps)
            reach = share >= 0.95
            p95_win = jnp.where(jnp.any(reach, -1),
                                jnp.take_along_axis(
                                    lat_s, jnp.argmax(reach, -1)[:, None],
                                    -1)[:, 0], 0.0)
            p95e = jnp.where(succ > eps, (1 - a_lat) * st["p95"]
                             + a_lat * p95_win, st["p95"])
            tot = succ + fail
            erre = jnp.where(tot > eps, (1 - a_err) * st["err"]
                             + a_err * fail / jnp.maximum(tot, eps), st["err"])
            rpse = (1 - a_rps) * st["rps"] + a_rps * lam_tot
            queue = jnp.maximum(b2 - p["servers"], 0.0)
            pub = jnp.stack([p95e, rpse, jnp.sum(queue, -1), erre], -1)
            new = dict(backlog=b2, down=down, uacc=uacc, uscr=uscr,
                       prev_lam=lam, p95=p95e, rps=rpse, err=erre,
                       n_req=st["n_req"] + jnp.sum(arr, -1),
                       n_succ=st["n_succ"] + succ,
                       e_to=st["e_to"] + jnp.sum(timed_out, -1),
                       e_ov=st["e_ov"] + jnp.sum(over, -1),
                       e_ref=st["e_ref"] + refused,
                       e_rs=st["e_rs"] + jnp.sum(killed, -1),
                       t_req=st["t_req"] + arr, t_succ=st["t_succ"] + done,
                       n_rs=st["n_rs"] + rs)
            out = dict(raw_obs=pub, tier_utilization=uscr,
                       tier_up=(down <= eps).astype(dt_), tier_queue=queue,
                       tier_latency_s=lat, tier_p95_s=p95,
                       tier_completed=done, success=succ, failures=fail,
                       restarted=rs)
            return new, out

        xs = (jnp.arange(t_n), arrival, hazard, actions, u, du)
        final, per_tick = jax.lax.scan(step, st0, xs)
        to32 = functools.partial(jax.tree_util.tree_map,
                                 lambda x: x.astype(jnp.float32))
        return to32(per_tick), to32(final)

    return run


# ------------------------------------------------------------------ summary
def _weighted_quantile(lat: np.ndarray, mass: np.ndarray, q: float):
    """Per cell: the first latency atom (ascending) whose cumulative share
    of completed mass reaches ``q`` (0 where a cell completed nothing)."""
    order = np.argsort(lat, axis=1, kind="stable")
    lat_s = np.take_along_axis(lat, order, 1)
    m_s = np.take_along_axis(mass, order, 1)
    total = m_s.sum(1, keepdims=True)
    share = np.cumsum(m_s, 1) / np.where(total > 0, total, 1.0)
    idx = np.minimum(np.argmax(share >= q, axis=1), lat.shape[1] - 1)
    out = np.take_along_axis(lat_s, idx[:, None], 1)[:, 0]
    return np.where(total[:, 0] > 0, out, 0.0)


def summary(tier_p95_s, tier_latency_s, tier_completed, n_requests,
            n_success, tier_requests, tier_success, n_restarts,
            rounding=None) -> dict:
    """Fleet metrics from every cell's (T, R, K) trace and final counters, in
    float64; ``rounding`` (a function) rounds every input and intermediate,
    for the control."""
    rnd = rounding or (lambda x: np.asarray(x, np.float64))

    def cellwise(x):                      # (T, R, K) -> (R, T*K)
        x = np.asarray(x)
        return rnd(np.moveaxis(x, 1, 0).reshape(x.shape[1], -1))

    mass = cellwise(tier_completed)
    n_req, n_succ = rnd(n_requests), rnd(n_success)
    rate = rnd(n_succ / np.maximum(n_req, _EPS))
    return {
        "success_pct": float(rnd(100.0 * rnd(rate.mean()))),
        "p95_ms": float(rnd(1000.0 * rnd(_weighted_quantile(
            cellwise(tier_p95_s), mass, 0.95).mean()))),
        "p50_ms": float(rnd(1000.0 * rnd(_weighted_quantile(
            cellwise(tier_latency_s), mass, 0.50).mean()))),
        "tier_share": rnd(rnd(rnd(tier_success)
                              / np.maximum(n_succ, _EPS)[:, None]).mean(0)),
        "routed_share": rnd(rnd(rnd(tier_requests)
                                / np.maximum(n_req, _EPS)[:, None]).mean(0)),
        "restarts": float(rnd(rnd(n_restarts).sum())),
    }
