"""A run whose timed path is broken underneath comes out not ``correct``.

Each case drives the rest of a run (``run.py --rehearse``: no look for a
chip, a 16-cell fleet over 40 windows on the CPU) with one fault planted in
the program, and reads the ``correct = ...`` line it prints.  The faults of
``chipbench.control.PLANTS`` are also read on the chip at the cell's own
size (``PERF.md``).
"""
import jax
import numpy as np
import pytest

from chipbench import control, registry

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]


def _window_output(alter):
    def plant():
        from repro.kernels.efe import ops

        def make(orig):
            def window(*args, **kw):
                state, est, obs, ys = orig(*args, **kw)
                return state, est, obs, alter(ys)
            return window
        return control.patched(ops, "mega_window", make)
    return plant


def _flip_action(ys):
    """One cell's recorded action altered at the window's last tick."""
    action = ys[0]
    flipped = jax.numpy.where(action[-1, 0] == 0, 1, 0).astype(action.dtype)
    return (action.at[-1, 0].set(flipped),) + tuple(ys[1:])


def _scale_success(ys):
    """The environment's completed mass altered where it is produced."""
    win = ys[5]
    return ys[:5] + (win._replace(success=win.success * 1.01),) + ys[6:]


def _summary_over_half():
    """The fleet's success % taken over half of the cells."""
    from repro.envsim import batched

    def make(orig):
        def summarize(final, trace):
            res = orig(final, trace)
            rate = np.array(res.success_rate)
            half = rate.shape[0] // 2
            rate[half:] = rate[:half].mean()
            return res._replace(success_rate=rate)
        return summarize
    return control.patched(batched, "summarize", make)


FAULTS = {
    **control.PLANTS,
    "action_altered": _window_output(_flip_action),
    "env_output_altered": _window_output(_scale_success),
    "summary_over_half_the_fleet": _summary_over_half,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault, rehearsal_correct):
    with FAULTS[fault]():
        assert not rehearsal_correct(cell)
