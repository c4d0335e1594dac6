"""The work count of the mega windows against a count made by hand."""
import pytest

from chipbench import work

TINY = {"n_states": 3, "n_actions": 2, "n_tiers": 1, "n_bins": [2],
        "agent": {"slow_period_s": 2.0, "fast_period_s": 1.0,
                  "action_dwell_s": 1.0}}


def test_window_work_matches_hand_count():
    # S=3, A=2, K=1, M=1, max_bins=2, so P = M*max_bins + M = 3.
    # slot: q_prev, q_next (3 each), coefact (2), qnproj (3), coefw, sumqn
    #   = 13 floats = 52 B.
    # cache: colsum 2*3 + proj 3*3 + projsum 3 + logna 1*2*3 = 24 floats.
    # carries read and written: router 3+5, env 9*1+12+1, telemetry 2*1+3*1
    #   = 8+22+5 = 35 floats, twice = 70; fixed = 24+70 = 94 floats = 376 B.
    # per tick in: arrival 1, hazard 1, draws 2, gumbel 2 = 6 floats = 24 B;
    # per tick out: slot push 2*3+2*1+2 = 10, trace 3*1+8*1+5 = 16 -> 104 B.
    # W = 2 ticks a window, both selecting (dwell 1); T = 4: t0 = 0, 2.
    #   t0=0: bytes 376 + 2*128 = 632; flops 2*(2*A*P*S) = 2*36 = 72.
    #   t0=2: bytes 2*52 + 632 = 736; flops 2 ticks*4*2*3 = 48
    #         + 2*(2*2*2*(3+3+1) + 36) = 2*(56+36) = 184 -> 232.
    # per cell 1368 B and 304 FLOP; five cells.
    w = work.window_work(TINY, n_cells=5, n_windows=4)
    assert w["slot_bytes"] == 52
    assert w["bytes"] == 5 * 1368
    assert w["flops"] == 5 * 304


def test_partial_last_window_counts_only_its_ticks():
    full = work.window_work(TINY, n_cells=1, n_windows=4)
    short = work.window_work(TINY, n_cells=1, n_windows=3)
    # the third window has one tick at t0=2: 2*52 + 376 + 128 bytes
    assert full["bytes"] - short["bytes"] == 128


@pytest.mark.parametrize("mem_s,ops_s,bound", [(1.0, 0.5, "hbm"),
                                               (0.25, 1.0, "flops")])
def test_roofline_names_the_binding_bound(mem_s, ops_s, bound):
    peak = {"hbm_bytes_per_s": 800e9, "bf16_flops_per_s": 200e12}
    w = {"bytes": mem_s * 800e9, "flops": ops_s * 200e12}
    pct, b = work.roofline(w, peak, seconds=2.0)
    assert b == bound
    assert pct == pytest.approx(100.0 * max(mem_s, ops_s) / 2.0)
