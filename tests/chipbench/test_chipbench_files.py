"""Every cell, configuration, traffic mix and per-layer metric of
``BENCHMARK.json`` is a file of its own that the harness finds by name, and
adding one is adding a file."""
import json
import re
import shutil

import pytest

from chipbench import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = registry.benchmark()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    for p in BENCH["paths"]:
        assert (registry.ROOT / p).is_dir()


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_resolves(cfg):
    assert NAME.match(cfg["name"]) and _line(cfg["source"]) and _line(cfg["why"])
    data = registry.config(cfg["name"])
    assert registry.ROOT / cfg["file"] == (registry.HERE / "configs"
                                           / f"{cfg['name']}.json")
    assert data["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in data
    # widths are the configuration's own, never cut
    assert len(data["policy_table"]) == data["n_actions"]
    assert data["n_states"] == data["n_levels"] ** (2 + data["n_tiers"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(w):
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert _line(w["why"]) and w["chips"] in (1, 4)
    cell = registry.cell(w["name"])
    assert cell["config"]["name"] == w["config"]
    assert cell["traffic"]["name"] == w["traffic"]
    assert cell["chips"] == w["chips"]
    assert set(cell["limits"]) >= {"belief_gap", "action_gap", "env_gap",
                                   "counter_gap", "summary_gap",
                                   "calls_differing"}


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_names_and_units(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_its_cells_report_moves(m):
    assert callable(registry.metric_reader(m["name"]))
    assert _line(m["layer"])
    cells = [w["name"] for w in BENCH["workloads"]]
    for c in m.get("workloads", cells):
        reported = {e["name"] for e in registry.metrics_for(c, "end_to_end")}
        assert m["moves"] in reported


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    for w in BENCH["workloads"]:
        names = {e["name"] for e in registry.metrics_for(w["name"],
                                                         "end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        assert registry.metrics_for(w["name"], "per_layer")


def test_bounds_within_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= BENCH["run_seconds"] <= 51


def test_adding_a_cell_mix_config_and_metric_is_adding_files(tmp_path,
                                                             monkeypatch):
    """A copy of the benchmark gains one of each by new files and new
    ``BENCHMARK.json`` entries alone; no existing file changes."""
    root = tmp_path / "repo"
    shutil.copytree(registry.HERE, root / "chipbench")
    bench = json.loads(registry.BENCHMARK.read_text())
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file() and p.suffix in (".json", ".py")}
    here = root / "chipbench"
    cfg = json.loads((here / "configs" / "paper-3tier.json").read_text())
    cfg.update(name="paper-3tier.small", n_cells=512)
    (here / "configs" / "paper-3tier.small.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "traffic" / "paper-burst.json").read_text())
    mix.update(name="steady", scenario="steady", rate={"kind": "flat"})
    (here / "traffic" / "steady.json").write_text(json.dumps(mix))
    cell = json.loads((here / "cells" / "paper-3tier.burst.r2048.json")
                      .read_text())
    cell.update(name="paper-3tier.steady.r512", config="paper-3tier.small",
                traffic="steady")
    (here / "cells" / "paper-3tier.steady.r512.json").write_text(
        json.dumps(cell))
    (here / "metrics" / "calls_per_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.calls))\n")
    bench["workloads"].append({"name": "paper-3tier.steady.r512",
                               "config": "paper-3tier.small",
                               "traffic": "steady", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "calls_per_window", "unit": "1",
                               "better": "higher", "source": "device_trace",
                               "layer": "device (TPU v5e)",
                               "moves": "cell_windows_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(registry, "HERE", here)
    monkeypatch.setattr(registry, "BENCHMARK", root / "BENCHMARK.json")

    c = registry.cell("paper-3tier.steady.r512")
    assert c["config"]["n_cells"] == 512
    assert c["traffic"]["rate"]["kind"] == "flat"
    names = [m["name"] for m in registry.metrics_for(
        "paper-3tier.steady.r512", "per_layer")]
    assert "calls_per_window" in names
    reader = registry.metric_reader("calls_per_window")
    assert reader(type("Ctx", (), {"calls": [(0, 1), (1, 2)]})()) == 2.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"
