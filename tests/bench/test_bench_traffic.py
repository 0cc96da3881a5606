"""The open-loop generator: due times come from the mix and the seed alone."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

STEADY = {"backlog": 0, "phases": [{"rate_per_s": 400.0}]}


def test_same_seed_same_schedule_and_pool():
    a = traffic.schedule(STEADY, 5.0, 7)
    b = traffic.schedule(STEADY, 5.0, 7)
    np.testing.assert_array_equal(a.due, b.due)
    cfg = {"points_per_message": 32, "dim": 9, "pool_messages": 3,
           "data": {"blobs": 4, "scale": 10.0, "noise": 1.0}}
    for x, y in zip(traffic.make_pool(cfg, 7), traffic.make_pool(cfg, 7)):
        np.testing.assert_array_equal(x, y)


def test_seeds_reorder_the_same_gaps():
    """Every seed offers the same messages and the same set of gaps, in
    another order: the seed moves bursts, never the amount of work."""
    a = traffic.schedule(STEADY, 5.0, 1)
    b = traffic.schedule(STEADY, 5.0, 2 ** 33 + 1)
    assert len(a.due) == len(b.due) == 2000
    assert not np.array_equal(a.due, b.due)
    ga = traffic.poisson_gaps(np.random.default_rng(1), 400.0, 2000)
    gb = traffic.poisson_gaps(np.random.default_rng(2), 400.0, 2000)
    assert not np.array_equal(ga, gb)
    np.testing.assert_array_equal(np.sort(ga), np.sort(gb))
    assert ga.mean() == pytest.approx(1 / 400.0, rel=1e-3)


def test_due_times_are_open_loop():
    """The schedule is fixed before the window opens: it is a function of
    the mix, the window and the seed, and nothing the consumer does."""
    s = traffic.schedule(STEADY, 5.0, 3)
    assert np.all(np.diff(s.due) > 0)
    assert s.due[0] == 0.0 and s.due[-1] < 5.0
    assert s.backlog == 0


def test_large_and_negative_seeds_are_distinct():
    seeds = (5, 2 ** 40 + 5, -5)
    dues = [traffic.schedule(STEADY, 1.0, s).due for s in seeds]
    assert not np.array_equal(dues[0], dues[1])
    assert not np.array_equal(dues[0], dues[2])


def test_bursty_phases_cycle_through_the_window():
    mix = {"phases": [{"rate_per_s": 1000.0, "seconds": 0.5},
                      {"rate_per_s": 0.0, "seconds": 0.5}]}
    s = traffic.schedule(mix, 3.0, 9)
    assert len(s.due) == 1500
    on = np.floor(s.due / 0.5) % 2 == 0
    assert on.all()


def test_backlog_alone_and_empty_mix():
    s = traffic.schedule({"backlog": 100}, 2.0, 1)
    assert s.backlog == 100 and len(s.due) == 0
    with pytest.raises(ValueError):
        traffic.schedule({"backlog": 0, "phases": []}, 2.0, 1)
