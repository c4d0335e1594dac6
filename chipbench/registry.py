"""Find a cell, its configuration, its traffic mix and the per-layer metric
readers by the names ``BENCHMARK.json`` gives them.

Each lives in a file of its own, so adding one is adding a file:

* ``chipbench/cells/<cell>.json`` — configuration and traffic names, engine
  options, how many cells the check samples, and the limits of the
  comparison that decides ``correct``;
* ``chipbench/configs/<config>.json`` — the deployment: topology widths,
  fleet size, tier parameters, agent constants, policy table;
* ``chipbench/traffic/<mix>.json`` — the parameters the one schedule
  generator (:mod:`chipbench.world`) reads;
* ``chipbench/metrics/<metric>.py`` — a ``read(ctx)`` function that reduces
  the traced window to one number, or returns None when the cell has
  nothing for it to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load_json(BENCHMARK)


def config(name: str) -> dict:
    return _load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _load_json(HERE / "traffic" / f"{name}.json")


def cell(name: str) -> dict:
    """The cell's own file, with its configuration and traffic mix attached
    under ``"config"`` and ``"traffic"`` (the file holds their names)."""
    c = _load_json(HERE / "cells" / f"{name}.json")
    return {**c, "config": config(c["config"]), "traffic": traffic(c["traffic"])}


def workload(name: str) -> dict:
    """The ``BENCHMARK.json`` entry of a cell; KeyError if it has none."""
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"BENCHMARK.json has no workload {name!r}")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that a cell reports: those
    that list it under ``workloads``, or list no cells at all."""
    return [m for m in benchmark()[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks() -> dict:
    return _load_json(HERE / "peaks.json")
