"""The rate and the slices beside it: a stall moves the plain rate, which
is what a run reports, and shows in the slices as one slow slice."""

import numpy as np

from bmlib import estimate


def _log(stall_at=None, stall_s=0.5, rate=50e6, every=0.06, seconds=30.0):
    """Deliveries every ``every`` seconds at a steady rate; optionally one
    delivery ``stall_s`` late (the work waits, the events do not)."""
    t = np.arange(0.0, seconds, every)
    e = (t * rate).astype(np.int64)
    if stall_at is not None:
        t = np.where(t >= stall_at, t + stall_s, t)
    return list(t), list(e)


def test_slices_span_at_least_a_second_and_drop_the_tail():
    t, e = _log(seconds=3.5)
    sl = estimate.slices(t, e, 1.0)
    assert len(sl) == 3
    assert all(dt >= 1.0 for dt, _ in sl)


def test_one_stall_moves_the_reported_rate_and_shows_as_one_slice():
    steady = estimate.rate_summary(*_log(), 1.0)
    stalled = estimate.rate_summary(*_log(stall_at=12.3), 1.0)
    assert abs(steady["median"] / 50e6 - 1) < 1e-3
    assert abs(stalled["median"] / steady["median"] - 1) < 1e-3
    # 0.5 s of 30.5: the reported rate pays for the stall in full
    assert abs(stalled["plain_rate"] / steady["plain_rate"] - 30 / 30.5) < 2e-3
    # the stall is there to see: one slow slice
    assert stalled["min"] < 0.75 * stalled["median"]
    assert stalled["q1"] > 0.99 * stalled["median"]


def test_percentile_is_nearest_rank():
    xs = sorted(range(1, 101))
    assert estimate.percentile(xs, 50) == 50
    assert estimate.percentile(xs, 95) == 95
    assert estimate.percentile([], 95) is None
