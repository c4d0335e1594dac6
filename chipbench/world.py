"""The world a cell offers the router: schedules and fluid parameters made
from the cell's configuration and traffic files, independent of the program.

:func:`schedules` is the one traffic generator: it reads a mix's parameters
(the rate shape, hazard and capacity multipliers, the horizon) and the
configuration's base rate and tier parameters, and returns the arrays the
fluid environment consumes.  :func:`guard` checks, bit for bit, that the
arrays the program built for the cell are these, so a change to the program
cannot change the traffic the benchmark offers.
"""
from __future__ import annotations

import numpy as np


def rate_multiplier(traffic: dict, n_windows: int | None = None) -> np.ndarray:
    """(T,) float64 multiplier on the base rate, per control window."""
    t_n = int(n_windows or traffic["n_windows"])
    win = float(traffic["window_s"])
    rate = traffic["rate"]
    kind = rate["kind"]
    if kind == "flat":
        return np.full(t_n, float(rate.get("level", 1.0)))
    if kind == "burst":
        # the burst cycle sampled at each window's midpoint; the off-burst
        # level keeps the mean rate at the base rate
        factor, period = float(rate["burst_factor"]), float(rate["burst_period_s"])
        duty = float(rate["burst_duty"])
        t = (np.arange(t_n, dtype=np.float64) + 0.5) * win
        phase = (t % period) / period
        off = (1.0 - duty * factor) / (1.0 - duty)
        return np.where(phase < duty, factor, off)
    raise ValueError(f"unknown rate kind {kind!r} in traffic {traffic['name']!r}")


def schedules(config: dict, traffic: dict, n_cells: int,
              n_windows: int | None = None) -> dict:
    """Arrival (T, R), hazard (T, R, K) and capacity (R, K) schedules; T is
    the mix's horizon unless ``n_windows`` is given."""
    t_n, k = int(n_windows or traffic["n_windows"]), int(config["n_tiers"])
    mult = rate_multiplier(traffic, t_n).astype(np.float32)
    rate = np.tile(mult[:, None], (1, n_cells))
    return {
        "arrival_rate": np.float32(config["rps"]) * rate,
        "hazard_scale": np.full((t_n, n_cells, k),
                                traffic.get("hazard_scale", 1.0), np.float32),
        "capacity_scale": np.full((n_cells, k),
                                  traffic.get("capacity_scale", 1.0),
                                  np.float32),
    }


def fluid_params(config: dict, capacity_scale: np.ndarray) -> dict:
    """Per-cell (R, K) tier parameters and the scalar constants, float32."""
    tiers = config["tiers"]
    r = capacity_scale.shape[0]

    def tiled(vals):
        return np.tile(np.asarray(vals, np.float32), (r, 1))

    p95f = []
    for t in tiers:
        sigma = np.sqrt(np.log(1.0 + t["service_cv"] ** 2))
        p95f.append(float(np.exp(1.645 * sigma - 0.5 * sigma ** 2)))
    inst = 1.0 if config["instability"] else 0.0
    return {
        "servers": tiled([t["servers"] for t in tiers]) * capacity_scale,
        "mu": tiled([1.0 / t["mean_service_s"] for t in tiers]),
        "service_mean_s": tiled([t["mean_service_s"] for t in tiers]),
        "service_p95_factor": tiled(p95f),
        "queue_cap": tiled([t["queue_cap"] for t in tiers]),
        "timeout_s": np.float32(config["timeout_s"]),
        "unstable": tiled([inst * float(t["unstable"]) for t in tiers]),
        "restart_base": tiled([t["restart_base_hazard"] for t in tiers]),
        "restart_load": tiled([t["restart_load_hazard"] for t in tiers]),
        "restart_knee": tiled([t["restart_util_knee"] for t in tiers]),
        "restart_shock": tiled([t["restart_shock_hazard"] for t in tiers]),
        "restart_min_s": tiled([t["restart_min_s"] for t in tiers]),
        "restart_max_s": tiled([t["restart_max_s"] for t in tiers]),
        "latency_window_s": np.float32(config["latency_window_s"]),
        "error_window_s": np.float32(config["error_window_s"]),
        "rps_window_s": np.float32(config["rps_window_s"]),
    }


def experiment_kwargs(cell: dict, seed: int, n_cells: int | None = None,
                      n_windows: int | None = None) -> dict:
    """Keyword arguments of ``repro.api.Experiment`` for this cell."""
    cfg, tr = cell["config"], cell["traffic"]
    eng = cell["engine"]
    return dict(router="aif", scenario=tr["scenario"],
                topology=cfg["topology"],
                n_cells=int(n_cells or cfg["n_cells"]),
                n_windows=int(n_windows or tr["n_windows"]),
                seed=int(seed), window_s=float(tr["window_s"]),
                mega=bool(eng["mega"]), use_pallas=bool(eng["use_pallas"]),
                mega_slot_dtype=eng["mega_slot_dtype"],
                shard=eng.get("shard"))


def guard(cell: dict, fluid, n_cells: int, n_windows: int) -> list[str]:
    """Differences between the program's world (``env_step.fluid`` of the
    experiment) and this generator's; empty when they agree bit for bit."""
    cfg, tr = cell["config"], cell["traffic"]
    sch = schedules(cfg, tr, n_cells, n_windows)
    par = fluid_params(cfg, sch["capacity_scale"])
    bad = []

    def same(name, prog, ours):
        prog = np.asarray(prog)
        if prog.shape != np.shape(ours) or prog.dtype != np.asarray(ours).dtype \
                or not np.array_equal(prog, ours):
            bad.append(name)

    same("arrival_rate", fluid.arrival_rate, sch["arrival_rate"])
    same("hazard_scale", fluid.hazard_scale, sch["hazard_scale"])
    for name, val in par.items():
        same(f"params.{name}", getattr(fluid.params, name), val)
    for name in ("obs_valid", "forced_down", "speed", "graph"):
        if getattr(fluid, name, None) is not None:
            bad.append(f"{name} (the mix states none)")
    if float(fluid.dt) != float(tr["window_s"]):
        bad.append("dt")
    if int(fluid.scrape_every) != int(tr["scrape_every"]):
        bad.append("scrape_every")
    if bool(fluid.restart_blackout):
        bad.append("restart_blackout (the mix states none)")
    return bad
