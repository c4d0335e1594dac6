"""Heterogeneous fleet: different cells run different topologies.

Array shapes differ across topologies (|S|, action count, tier count), so a
mixed fleet is *statically sharded*: one :class:`repro.api.Experiment` per
topology, each compiling its own jitted scan.  This demo drives two shards
side by side on the same diurnal load shape:

* 4 cells of the paper's 3-tier testbed (|S| = 243, 20 policies),
* 3 cells of the 5-tier cloud/regional/metro/far-edge/device continuum
  (|S| = 128 via binary levels, 37 generated policies).

(For pre-grouped shards sharing one call, see
``repro.core.fleet.hetero_fleet_rollout``.)

    PYTHONPATH=src python examples/hetero_fleet.py [--quick]
"""
import argparse
import time

import numpy as np

from repro import api


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short horizon for CI smoke runs")
    args = ap.parse_args()
    t = 60 if args.quick else 300

    shards = [
        api.Experiment(router="aif", topology="paper-3tier", n_cells=4,
                       n_windows=t, scenario="diurnal"),
        api.Experiment(router="aif", topology="continuum-5tier", n_cells=3,
                       n_windows=t, scenario="diurnal"),
    ]
    print(f"heterogeneous fleet, {t} control windows per shard:")
    for e in shards:
        topo = e.resolve_topology()
        print(f"  {e.topology}: {topo.describe()}, {e.n_cells} cells")

    t0 = time.time()
    results = [api.run(e) for e in shards]
    wall = time.time() - t0

    total_cells = sum(e.n_cells for e in shards)
    print(f"\nran {total_cells} cells x {t} windows in {wall:.1f}s "
          f"({total_cells * t / wall:.0f} cell-windows/s incl. compile)")
    for e, res in zip(shards, results):
        k = e.resolve_topology().n_tiers
        mean_w = np.asarray(res.trace.routing_weights).mean((0, 1))
        print(f"\n  {e.topology} (K={k}):")
        print(f"    success {res.success_pct:.1f}%  "
              f"P95 {res.p95_ms:.0f} ms  restarts {int(res.restarts)}")
        print(f"    fleet-mean routing weights (lightest->heaviest): "
              f"{np.round(mean_w, 2)}")
    print("\nEach shard learns its own topology's generative model online; "
          "the shards share no shapes, only the control cadence — exactly "
          "how a mixed edge estate (3-tier metro sites + 5-tier continuum "
          "regions) would run one router codebase.")


if __name__ == "__main__":
    main()
