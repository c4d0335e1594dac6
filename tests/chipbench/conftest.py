import os
import sys

# the benchmark's modules import as ``chipbench.*`` from the repository root
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rehearsal_correct(capsys):
    """``rehearsal_correct(cell)`` drives ``run.py --rehearse`` for a cell
    and returns whether it printed ``correct = True``.  Every call traces the
    program anew, so a fault planted in its Python is compiled in."""
    from chipbench import run

    def go(cell, seed=2**31 + 11):
        jax.clear_caches()
        try:
            run.main(["--workload", cell, "--seed", str(seed),
                      "--seconds", "0", "--rehearse"])
        finally:
            jax.clear_caches()
        err = capsys.readouterr().err
        verdict = [ln for ln in err.splitlines()
                   if ln.startswith("correct = ")]
        assert verdict, err[-2000:]
        return verdict[-1] == "correct = True"
    return go
