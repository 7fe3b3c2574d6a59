"""The knee sweep's arithmetic: the backlog from a window's due and finish
stamps, and the knee over several draws of the arrivals: below the first
rate at which the median draw grew."""
import pytest

from perfbench import sweep


def window(due, finished, step_t):
    return {"due": dict(enumerate(due)),
            "finished": {k: v for k, v in enumerate(finished)
                         if v is not None},
            "step_t": step_t}


def test_backlog_counts_requests_due_and_not_finished():
    # due at 0, 1, 2, 3; the first two finish at 1.5 and 2.5, the rest never
    w = window([0.0, 1.0, 2.0, 3.0], [1.5, 2.5, None, None],
               [0.5, 1.0, 2.0, 3.0, 4.0])
    # steps at 0.5 (1 due, 0 done) and 1.0 (2 due, 0 done): mean 1.5
    assert sweep.mean_backlog(w, 1.5, 1.0) == pytest.approx(1.5)
    # steps at 2.0 (3 due, 1 done) and 3.0 (4 due, 2 done): mean 2
    assert sweep.mean_backlog(w, 3.5, 2.0) == pytest.approx(2.0)
    # no step in the span
    assert sweep.mean_backlog(w, 10.0, 1.0) == 0.0


@pytest.mark.parametrize("grew, want", [
    ({9: [False] * 3, 12: [True, False, False], 14: [False, True, False],
      15: [True, True, False], 16: [False] * 3}, 14),
    ({9: [False], 12: [False], 14: [False]}, 14),
    ({9: [False], 12: [True], 14: [False]}, 9),
    ({9: [True, True, False], 12: [False] * 3}, None),
    ({9: [True, False], 12: [False, False]}, 12),
])
def test_knee_is_below_the_first_rate_most_draws_grew(grew, want):
    lines = [{"rate_per_s": r, "backlog_grew": g}
             for r, gs in grew.items() for g in gs]
    assert sweep.knee(lines) == want
