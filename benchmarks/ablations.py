"""Ablations of the paper's mechanisms (§4.2 / §4.4 claims).

* adaptive preferences OFF — the paper: "without it, the router aggressively
  routes to unstable tiers, achieving low latency but with significantly
  elevated failure rates".
* utilization scrape OFF — drop the 10-second resource-metric evidence (§3).
* action dwell 1 s — re-sample the policy every second: the sigmoid
  settle-weighted B-learning never sees stabilized transitions.
* β sweep — exploration/exploitation temperature.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import AifConfig
from repro.compile_cache import enable_compile_cache
from repro.envsim import AifRouter, SimConfig, evaluate_strategy, table1


def run(duration_s: float, n_runs: int) -> None:
    cfg = SimConfig()
    variants = {
        "aif(paper)": lambda seed: AifRouter(seed=seed),
        "no-adaptive-C": lambda seed: AifRouter(
            seed=seed, adaptive_preferences=False),
        "no-util-scrape": lambda seed: AifRouter(
            seed=seed, use_util_scrape=False),
        "dwell-1s": lambda seed: AifRouter(
            seed=seed, cfg=AifConfig(action_dwell_s=1.0)),
        "beta-1": lambda seed: AifRouter(seed=seed, cfg=AifConfig(beta=1.0)),
        "beta-20": lambda seed: AifRouter(seed=seed,
                                          cfg=AifConfig(beta=20.0)),
    }
    summaries = [evaluate_strategy(mk, name, cfg, duration_s=duration_s,
                                   n_runs=n_runs)
                 for name, mk in variants.items()]
    print(table1(summaries))


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=600.0)
    ap.add_argument("--runs", type=int, default=2)
    a = ap.parse_args(argv)
    run(a.duration, a.runs)


if __name__ == "__main__":
    main()
