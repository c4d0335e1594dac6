"""``run.py`` refuses what is not a TPU it knows, and the world guard holds
the program to the benchmark's own traffic."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import registry, run, world

CELL = "paper-3tier.burst.r2048"


def test_refuses_a_cpu_backend_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(registry.HERE / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


@dataclasses.dataclass
class _Dev:
    platform: str
    device_kind: str


class _Jax:
    def __init__(self, devs):
        self._devs = devs

    def devices(self):
        return self._devs


@pytest.mark.parametrize("devs,msg", [
    ([_Dev("tpu", "TPU v9 imaginary")], "not in chipbench/peaks.json"),
    ([_Dev("gpu", "H100")], "needs a TPU"),
    ([_Dev("tpu", "TPU v5 lite")], "needs 4 chips"),
])
def test_device_check_refuses(devs, msg, capsys):
    chips = 4 if "4 chips" in msg else 1
    with pytest.raises(SystemExit) as ex:
        run.device_check(_Jax(devs), chips, rehearse=False)
    assert ex.value.code != 0
    assert msg in capsys.readouterr().err


def test_device_check_accepts_a_known_tpu():
    devs, peak = run.device_check(_Jax([_Dev("tpu", "TPU v5 lite")]), 1,
                                  rehearse=False)
    assert peak["hbm_bytes_per_s"] == 819e9


def test_unknown_workload_is_refused(capsys):
    with pytest.raises(SystemExit) as ex:
        run.main(["--workload", "no-such-cell", "--seed", "1",
                  "--seconds", "1"])
    assert ex.value.code != 0


@pytest.mark.parametrize("name", [w["name"] for w
                                  in registry.benchmark()["workloads"]])
def test_world_guard_accepts_the_programs_world(name):
    from repro.api import experiment as experiment_mod
    from repro.api import Experiment

    cell = registry.cell(name)
    e = Experiment(**world.experiment_kwargs(cell, 7, n_cells=24,
                                             n_windows=40))
    _, _, env_step = experiment_mod._build_world(
        e.resolve_topology(), e.scenario, e.n_cells, e.n_windows,
        e.window_s, e.seed, e.resolve_graph())
    assert world.guard(cell, env_step.fluid, 24, 40) == []


def test_world_guard_names_a_changed_schedule():
    from repro.api import experiment as experiment_mod
    from repro.api import Experiment

    cell = registry.cell(CELL)
    e = Experiment(**world.experiment_kwargs(cell, 7, n_cells=8,
                                             n_windows=40))
    _, _, env_step = experiment_mod._build_world(
        e.resolve_topology(), e.scenario, e.n_cells, e.n_windows,
        e.window_s, e.seed, e.resolve_graph())
    fl = env_step.fluid
    louder = fl._replace(arrival_rate=np.asarray(fl.arrival_rate) * 1.01)
    assert world.guard(cell, louder, 8, 40) == ["arrival_rate"]
    slower = fl._replace(params=fl.params._replace(
        mu=np.asarray(fl.params.mu) * 0.99))
    assert world.guard(cell, slower, 8, 40) == ["params.mu"]


def test_rate_multiplier_keeps_the_mean_at_the_base_rate():
    mix = registry.traffic("paper-burst")
    mult = world.rate_multiplier(mix)
    assert mult.shape == (600,)
    assert mult.mean() == pytest.approx(1.0, abs=1e-12)
    assert json.dumps(mix["rate"])
