"""The comparison that decides ``correct``: a sound run passes it, and each
control (``chipbench/control.py``: the program's own bfloat16-slot path,
and the reference one precision step down in the program's place) fails
it.  ``test_chipbench_faults.py`` plants faults in the timed path."""
import dataclasses

import pytest

from chipbench import check, reference, registry, world

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]
SEED = 2**31 + 3


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, rehearsal_correct):
    assert rehearsal_correct(cell)


@pytest.fixture(scope="module", params=CELLS)
def readings(request):
    """Program and control numbers of one tiny run of the cell."""
    from repro import api
    c = registry.cell(request.param)
    model = reference.Model(c["config"])
    e = api.Experiment(**world.experiment_kwargs(c, SEED, n_cells=16,
                                                 n_windows=40))
    rows = check.sample_rows(SEED, e.n_cells, c["sample_cells"])

    def numbers(exp):
        return check.program_numbers(c, model, SEED, rows,
                                     check.gather(api.run(exp), rows))
    data = check.gather(api.run(e), rows)
    return c["limits"], {
        "program": check.program_numbers(c, model, SEED, rows, data),
        "reference_low": check.reference_low_numbers(c, model, SEED, rows,
                                                     data),
        "bf16_slots": numbers(dataclasses.replace(
            e, mega_slot_dtype="bfloat16")),
    }


def test_program_passes(readings):
    lim, nums = readings
    assert all(v <= lim[k] for k, v in nums["program"].items()), nums


@pytest.mark.parametrize("control", ["reference_low", "bf16_slots"])
def test_control_fails(readings, control):
    lim, nums = readings
    assert any(v > lim[k] for k, v in nums[control].items()), nums
