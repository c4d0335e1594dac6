"""The comparison that decides ``correct``.

What the timed calls of ``repro.api.run`` returned is compared with the
plain reference (:mod:`chipbench.reference`):

* ``belief_gap`` — largest |difference| of a posterior entry over the
  sampled cells and every tick, each tick's reference posterior computed
  from the program's previous one (belief update and slow-boundary
  learning);
* ``action_gap`` — largest amount by which the program's action at a
  selecting tick scores below the reference's best (EFE, Gumbel-max draw;
  ``reference.DWELL_BREACH`` where the program changed action on a held
  tick);
* ``env_gap`` — largest difference of what the sampled cells' environments
  published and counted, per tick, relative to max(|reference|, 1);
* ``counter_gap`` — the same for every cell's final counters (requests,
  successes, errors by cause, per-tier requests, successes and restarts):
  the reference advances the whole fleet's environments under the actions
  the program applied;
* ``summary_gap`` — largest relative difference of the fleet's success %,
  P50/P95, tier and routed shares and restarts against the reference's
  reduction of its own environments of every cell;
* ``calls_differing`` — timed calls whose fleet metrics differ from the
  warm-up call's (every call runs the same experiment; exact).

The sample of cells is drawn from the seed.  :func:`reference_low_numbers`
reads the same numbers with the reference one precision step down put in
the program's place, one of the controls ``chipbench/control.py`` reads
(``PERF.md`` gives the readings the limits were set from).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, world

ENV_FIELDS = ("raw_obs", "tier_utilization", "tier_up", "tier_queue",
              "tier_latency_s", "tier_p95_s", "tier_completed", "success",
              "failures", "restarted")
#: final per-cell counters: program's ``RunResult.fluid`` name -> reference's
FINAL_FIELDS = {"n_requests": "n_req", "n_success": "n_succ",
                "tier_requests": "t_req", "tier_success": "t_succ",
                "n_restarts": "n_rs"}
ERROR_FIELDS = {"timeout": "e_to", "overflow": "e_ov", "refused": "e_ref",
                "restart": "e_rs"}
SUMMARY_KEYS = ("success_pct", "p95_ms", "p50_ms", "tier_share",
                "routed_share", "restarts")


def sample_rows(seed: int, n_cells: int, n_sample: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n_cells, size=min(n_sample, n_cells),
                              replace=False))


def digest(res) -> tuple:
    """Fleet metrics of one call, for the exact call-to-call comparison."""
    return (res.success_pct, res.p95_ms, res.p50_ms, res.restarts,
            res.obs_frac, np.asarray(res.tier_share).tobytes(),
            np.asarray(res.routed_share).tobytes())


def gather(res, rows: np.ndarray) -> dict:
    """Host copies of what the comparison reads from one call's result: the
    sampled cells' trace and posteriors, every cell's applied actions and
    final counters, and the fleet metrics."""
    tr, env = res.trace, res.trace.env
    idx = jnp.asarray(rows)
    take = lambda x: np.asarray(jnp.take(x, idx, axis=1))   # noqa: E731
    out = {
        "rows": rows,
        "n_cells": int(res.experiment.n_cells),
        "actions": np.asarray(tr.actions),                   # (T, R)
        "raw_obs": take(tr.raw_obs),
        "env": {f: take(getattr(env, f)) for f in ENV_FIELDS},
        # the program's posteriors before and after every tick, (T, n, S)
        "q_prev": np.moveaxis(np.asarray(jnp.take(
            res.final_carry.slots.q_prev, idx, axis=0), np.float32), 1, 0),
        "belief": np.moveaxis(np.asarray(jnp.take(
            res.final_carry.slots.q_next, idx, axis=0), np.float32), 1, 0),
        "fluid": res.fluid,
        "metrics": {k: getattr(res, k) for k in SUMMARY_KEYS},
    }
    return out


def reference_run(cell: dict, model: reference.Model, seed: int,
                  rows: np.ndarray, data: dict, precision: str = "highest",
                  env_dtype=jnp.float32) -> dict:
    """The reference's routers over the sampled cells, fed the program's
    telemetry and actions, and its environments over every cell, driven by
    the actions the program applied."""
    cfg, tr = cell["config"], cell["traffic"]
    n_cells, t_n = data["n_cells"], data["actions"].shape[0]
    gum, slow_keys, u, du = reference.keys_and_draws(
        jax.random.key(seed), jnp.asarray(rows), n_ticks=t_n,
        n_cells=n_cells, n_actions=model.a, n_tiers=model.k)
    util_seen = np.concatenate(
        [np.zeros_like(data["env"]["tier_utilization"][:1]),
         data["env"]["tier_utilization"][:-1]])
    rt = reference.router(model, data["raw_obs"], util_seen,
                          data["actions"][:, rows], data["q_prev"],
                          data["belief"], gum, slow_keys, precision=precision)
    sch = world.schedules(cfg, tr, n_cells, t_n)
    par = world.fluid_params(cfg, sch["capacity_scale"])
    per_tick, final = reference.environment(
        model, par, sch["arrival_rate"], sch["hazard_scale"], data["actions"],
        u, du, dt=float(tr["window_s"]), scrape_every=int(tr["scrape_every"]),
        dtype=env_dtype)
    return {"router": jax.device_get(rt), "env": jax.device_get(per_tick),
            "final": jax.device_get(final)}


def _rel(a, b, floor) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    if a.size == 0:
        return 0.0
    d = np.abs(a - b) / np.maximum(np.abs(b), floor)
    return float(np.nanmax(np.where(np.isnan(d), np.inf, d)))


def _env_gap(env: dict, ref: dict, rows) -> float:
    """``env``: the judged side's per-tick fields (T, n, ...) of the
    sampled cells."""
    return max(_rel(env[f], ref["env"][f][:, rows], 1.0) for f in ENV_FIELDS)


def _counter_gap(final: dict, ref: dict) -> float:
    """``final``: the judged side's final counters of every cell, by the
    reference's names."""
    return max(_rel(final[v], ref["final"][v], 1.0)
               for v in list(FINAL_FIELDS.values()) + list(ERROR_FIELDS.values()))


def _summary_gap(prog: dict, ref: dict) -> float:
    return max(_rel(prog[k], ref[k], 1e-3) for k in SUMMARY_KEYS)


def _action_gap_of(score: np.ndarray, choice: np.ndarray) -> float:
    """Largest best-minus-chosen reference score over selecting ticks."""
    picked = np.take_along_axis(score, choice[..., None].astype(np.int64),
                                -1)[..., 0]
    return float(np.max(score.max(-1) - picked)) if score.size else 0.0


def _prog_final(fluid) -> dict:
    out = {ref: np.asarray(getattr(fluid, name))
           for name, ref in FINAL_FIELDS.items()}
    out.update({ref: np.asarray(fluid.error_breakdown[name])
                for name, ref in ERROR_FIELDS.items()})
    return out


def _summary_of(run: dict, rounding=None) -> dict:
    """Fleet metrics of a reference run's environments of every cell."""
    env, fin = run["env"], run["final"]
    return reference.summary(
        env["tier_p95_s"], env["tier_latency_s"], env["tier_completed"],
        fin["n_req"], fin["n_succ"], fin["t_req"], fin["t_succ"], fin["n_rs"],
        rounding=rounding)


def program_numbers(cell: dict, model, seed: int, rows, data: dict) -> dict:
    """The compared numbers of the program's call against the reference."""
    ref = reference_run(cell, model, seed, rows, data)
    rt = ref["router"]
    return {
        "belief_gap": float(np.max(np.abs(
            rt["belief"] - np.moveaxis(data["belief"], 0, 1)))),
        "action_gap": float(np.max(rt["gap"])),
        "env_gap": _env_gap(data["env"], ref, rows),
        "counter_gap": _counter_gap(_prog_final(data["fluid"]), ref),
        "summary_gap": _summary_gap(data["metrics"], _summary_of(ref)),
    }


def _bf16(x):
    return np.asarray(np.asarray(x, np.float32).astype(jnp.bfloat16),
                      np.float64)


def reference_low_numbers(cell: dict, model, seed: int, rows,
                          data: dict) -> dict:
    """The same numbers with the reference one step below the stated
    precision in the program's place: contractions as three bfloat16
    passes, the environment and the fleet reduction in bfloat16."""
    ref = reference_run(cell, model, seed, rows, data)
    low = reference_run(cell, model, seed, rows, data, precision="high",
                        env_dtype=jnp.bfloat16)
    rt, lo = ref["router"], low["router"]
    return {
        "belief_gap": float(np.max(np.abs(lo["belief"] - rt["belief"]))),
        "action_gap": _action_gap_of(rt["score"], lo["own"]),
        "env_gap": _env_gap({f: low["env"][f][:, rows] for f in ENV_FIELDS},
                            ref, rows),
        "counter_gap": _counter_gap(low["final"], ref),
        "summary_gap": _summary_gap(_summary_of(low, rounding=_bf16),
                                    _summary_of(ref)),
    }


def compare(cell: dict, seed: int, data: dict, calls_differing: int
            ) -> list[tuple[str, float, float]]:
    """(name, value, limit) for every number compared; correct when every
    value is at most its limit."""
    model = reference.Model(cell["config"])
    rows = data["rows"]
    nums = program_numbers(cell, model, seed, rows, data)
    nums["calls_differing"] = float(calls_differing)
    limits = cell["limits"]
    return [(k, nums[k], float(limits[k])) for k in limits]
