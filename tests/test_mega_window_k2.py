"""The Pallas megakernel against its XLA oracle, one window at a time, on
the K=2 topology (edge, cloud).  ``test_mega_window_k3.py`` and
``test_mega_window_k5.py`` hold the other topologies of the grid.

Fleet sizes pad one 8-router block (R=3, 4, 5), fill it (R=8) or span two
(R=16), on clean (``paper-burst``) and masked (``flaky-telemetry``)
telemetry; :func:`mega_helpers._assert_window_matches_oracle` says what is
compared.
"""
import pytest

from mega_helpers import _assert_window_matches_oracle


@pytest.mark.parametrize("scenario", ["paper-burst", "flaky-telemetry"])
@pytest.mark.parametrize("r", [3, 4, 5, 8, 16])
def test_megakernel_window_matches_oracle(r, scenario):
    _assert_window_matches_oracle("k2", r, scenario)
