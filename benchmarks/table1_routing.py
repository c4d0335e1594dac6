"""Table 1 — 'Overall performance comparison at 50 RPS'.

AIF-Router vs the paper's uniform baseline (+ beyond-paper comparisons:
capacity-aware, round-robin, join-shortest-queue, Thompson sampling, UCB),
on either engine:

* ``--engine event`` — the paper protocol on the discrete-event simulator
  (3 × 45-minute runs with ``--full``; default 3 × 10-minute CI-speed
  variant with identical structure).  One router, one cell, host-bound.
* ``--engine batched`` (default) — the same comparison through the
  declarative :mod:`repro.api` surface on the batched fluid engine: every
  router (AIF included) runs inside one jitted ``lax.scan`` fleet, so the
  grid covers clean *and* degraded-telemetry scenarios at fleet scale —
  something the event-sim harness cannot reach.

    python -m benchmarks.table1_routing --engine batched --quick
    python -m benchmarks.table1_routing --engine batched \
        --routers aif,least_loaded --scenarios paper-burst,flaky-telemetry
    python -m benchmarks.table1_routing --engine event --full
"""
from __future__ import annotations

import argparse
import json
import time

from repro.compile_cache import enable_compile_cache


def run_event(duration_s: float, n_runs: int, out_json: str | None = None,
              strategies: tuple = ("aif", "uniform", "capacity",
                                   "round_robin", "least_loaded", "thompson",
                                   "ucb")) -> dict:
    """The original event-simulator protocol (one cell per run)."""
    from repro.baselines import (CapacityRouter, LeastLoadedRouter,
                                 RoundRobinRouter, ThompsonRouter, UcbRouter,
                                 UniformRouter)
    from repro.envsim import (AifRouter, SimConfig, evaluate_strategy,
                              table1)
    cfg = SimConfig()
    makers = {
        "aif": lambda seed: AifRouter(seed=seed),
        "uniform": lambda seed: UniformRouter(),
        "capacity": lambda seed: CapacityRouter(),
        "round_robin": lambda seed: RoundRobinRouter(),
        "least_loaded": lambda seed: LeastLoadedRouter(),
        "thompson": lambda seed: ThompsonRouter(seed=seed),
        "ucb": lambda seed: UcbRouter(),
    }
    unknown = set(strategies) - set(makers)
    if unknown:
        raise SystemExit(f"unknown event-engine strategies {sorted(unknown)};"
                         f" available: {sorted(makers)}")
    summaries = []
    out = {}
    for name in strategies:
        t0 = time.time()
        s = evaluate_strategy(makers[name], name, cfg, duration_s=duration_s,
                              n_runs=n_runs)
        summaries.append(s)
        out[name] = {
            "success_pct": [s.success_pct_mean, s.success_pct_std],
            "p50_ms": [s.p50_ms_mean, s.p50_ms_std],
            "p95_ms": [s.p95_ms_mean, s.p95_ms_std],
            "tier_share_of_success": s.tier_share_mean.tolist(),
            "routed_share": s.routed_share_mean.tolist(),
            "restarts": s.restarts_mean.tolist(),
            "wall_s": time.time() - t0,
        }
    print(table1(summaries))
    aif, uni = out.get("aif"), out.get("uniform")
    if aif and uni:
        dp50 = 100 * (aif["p50_ms"][0] / max(uni["p50_ms"][0], 1e-9) - 1)
        dsucc = aif["success_pct"][0] - uni["success_pct"][0]
        print(f"\nΔ(AIF−Base): P50 {dp50:+.1f}%  success {dsucc:+.1f}pp  "
              f"heavy-share {100*(aif['tier_share_of_success'][2]-uni['tier_share_of_success'][2]):+.1f}pp")
        print("paper:        P50 -34.7%  success -11.5pp  heavy-share +8pp")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
    return out


def run_batched(routers: tuple[str, ...], scenario_names: tuple[str, ...],
                n_cells: int, n_windows: int, seed: int = 0,
                out_json: str | None = None) -> dict:
    """The comparison grid on the batched engine via :mod:`repro.api`."""
    from repro import api
    t0 = time.time()
    comp = api.compare(api.table1_grid(
        routers=routers, scenario_names=scenario_names, n_cells=n_cells,
        n_windows=n_windows, seed=seed))
    wall = time.time() - t0
    print(comp.markdown())
    cells = len(comp.results) * n_cells * n_windows
    print(f"\n{len(comp.results)} rollouts x {n_cells} cells x "
          f"{n_windows} windows in {wall:.1f}s "
          f"({cells / wall:.0f} cell-windows/s incl. compile)")
    out = comp.to_json()
    if out_json:
        comp.dump(out_json)
        print(f"wrote {out_json}")
    return out


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", choices=("event", "batched"),
                    default="batched",
                    help="event simulator (paper protocol, one cell) or the "
                         "batched fleet engine via repro.api")
    ap.add_argument("--quick", action="store_true",
                    help="tiny R/T CI smoke grid (batched engine)")
    ap.add_argument("--routers", default=None,
                    help="comma-separated router names (default: AIF + the "
                         "five baseline families)")
    ap.add_argument("--scenarios", default="paper-burst,flaky-telemetry",
                    help="comma-separated scenario presets (batched engine; "
                         "default covers clean + degraded telemetry)")
    ap.add_argument("--cells", type=int, default=None,
                    help="fleet size R per rollout (batched engine; "
                         "default 16, or 2 with --quick)")
    ap.add_argument("--windows", type=int, default=None,
                    help="control windows T per rollout (batched engine; "
                         "default 600, or 60 with --quick)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="the paper protocol: 3 × 45-minute runs (event)")
    ap.add_argument("--duration", type=float, default=600.0,
                    help="per-run seconds (event engine)")
    ap.add_argument("--runs", type=int, default=3,
                    help="repeated runs per strategy (event engine)")
    ap.add_argument("--out", default=None, help="write results JSON")
    a = ap.parse_args(argv)

    if a.engine == "event":
        strategies = (tuple(a.routers.split(",")) if a.routers else
                      ("aif", "uniform", "capacity", "round_robin",
                       "least_loaded", "thompson", "ucb"))
        dur = 2700.0 if a.full else a.duration
        return run_event(dur, a.runs, a.out, strategies=strategies)

    from repro import api
    routers = (tuple(a.routers.split(",")) if a.routers
               else api.TABLE1_ROUTERS)
    scenario_names = tuple(a.scenarios.split(","))
    # explicit --cells/--windows always win; --quick only shrinks defaults
    d_cells, d_windows = (2, 60) if a.quick else (16, 600)
    cells = a.cells if a.cells is not None else d_cells
    windows = a.windows if a.windows is not None else d_windows
    return run_batched(routers, scenario_names, cells, windows, seed=a.seed,
                       out_json=a.out)


if __name__ == "__main__":
    main()
