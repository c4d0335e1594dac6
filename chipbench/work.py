"""Work of the mega engine's windows, counted from problem shapes.

One window advances every cell W ticks with the factored transition cache
held fixed.  What it has to move, at the least, per cell:

* each transition slot filled before the window (slot index < t0) read
  once: q_prev and q_next (S each), its per-action coefficients (A), its
  EFE projection row (P = M·max_bins + M), its weight and its Σq_next;
* the cache read once: column sums (A·S), projection rows (P·S and P),
  log observation rows (M·max_bins·S);
* the router and environment carry read and written once;
* the window's schedule slices and noise read once, its slot block and its
  per-tick trace written once.

Its operations are those of the factored prior at every tick (two J·S
contractions over the J filled slots) and of the EFE at the selecting
ticks.  Lane padding, the unfilled tail of the tape and row padding do not
count, so a kernel that skips them reads the higher share, and no layout
can push the share past 100%.  All counts are float32 (4 bytes).
"""
from __future__ import annotations

F32 = 4


def window_work(cfg: dict, n_cells: int, n_windows: int) -> dict:
    """Bytes and FLOPs of all the rollout's windows, summed over the fleet."""
    ag = cfg["agent"]
    s, a, k = int(cfg["n_states"]), int(cfg["n_actions"]), int(cfg["n_tiers"])
    m = len(cfg["n_bins"])
    nb = max(cfg["n_bins"])
    p = m * nb + m
    w = max(int(ag["slow_period_s"] / ag["fast_period_s"]), 1)
    dwell = max(int(ag["action_dwell_s"] / ag["fast_period_s"]), 1)

    slot_bytes = F32 * (2 * s + a + p + 2)
    cache_bytes = F32 * (a * s + p * s + p + m * nb * s)
    router_carry = F32 * (s + 5)                       # belief, 5 scalars
    env_carry = F32 * (9 * k + 12 + m)                 # FluidState
    obs_carry = F32 * (2 * m + 3 * k)
    per_tick_in = F32 * (1 + k + 2 * k + a)            # arrival, hazard, draws, gumbel
    per_tick_out = F32 * ((2 * s + 2 * m + 2)          # slot push
                          + (3 * m + 8 * k + 5))       # trace
    fixed = (cache_bytes + 2 * (router_carry + env_carry + obs_carry))

    total_bytes = total_flops = 0
    t0 = 0
    while t0 < n_windows:
        ticks = min(w, n_windows - t0)
        sel = len(range(0, ticks, dwell))
        total_bytes += (t0 * slot_bytes + fixed
                        + ticks * (per_tick_in + per_tick_out))
        total_flops += (ticks * 4 * t0 * s
                        + sel * (2 * t0 * a * (s + p + 1) + 2 * a * p * s))
        t0 += ticks
    return {"bytes": total_bytes * n_cells, "flops": total_flops * n_cells,
            "slot_bytes": slot_bytes}


def roofline(work: dict, peak: dict, seconds: float) -> tuple[float, str]:
    """Least time for ``work`` on the chip over the measured ``seconds``, as
    a percentage, and which bound sets that least time."""
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["flops"] / peak["bf16_flops_per_s"]
    bound = "hbm" if t_mem >= t_ops else "flops"
    return 100.0 * max(t_mem, t_ops) / seconds, bound
