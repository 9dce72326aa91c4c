"""The traffic schedules are fixed by the seed, and every seed sends the
same set of arrivals (open loop) and clip starts (closed loop)."""

import numpy as np
import pytest

from bench import loadgen, spec

OPEN = spec.loop("open").Schedule
CLOSED = spec.loop("closed").Schedule
LIVE = spec._json(spec.traffic_path("live"))
BACKLOG = spec._json(spec.traffic_path("backlog"))
SEEDS = [0, 7, 2 ** 31 + 11, 9_876_543_210]


@pytest.mark.parametrize("seed", SEEDS)
def test_open_schedule_is_the_seeds(seed):
    a = OPEN(seed, LIVE)
    b = OPEN(seed, LIVE)
    assert np.array_equal(a.phase, b.phase)
    due = [a.due(g) for g in range(3 * a.sensors)]
    assert due == sorted(due)
    assert [a.offset(i, k) for i in (0, 5) for k in (0, 900)] == \
        [b.offset(i, k) for i in (0, 5) for k in (0, 900)]
    other = OPEN(seed + 1, LIVE)
    assert np.allclose(np.sort(a.phase), np.sort(other.phase))
    assert not np.array_equal(a.phase, other.phase)


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_schedule_is_the_seeds(seed):
    a = CLOSED(seed, BACKLOG)
    b = CLOSED(seed, BACKLOG)
    assert np.array_equal(a.start, b.start)
    assert [a.next_clip() for _ in range(5)] == \
        [b.next_clip() for _ in range(5)]
    other = CLOSED(seed + 1, BACKLOG)
    assert np.array_equal(np.sort(a.start), np.sort(other.start))
    assert a.start.max() < a.clip


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_pool_is_the_seeds(seed):
    assert np.array_equal(loadgen.pool(seed, LIVE)[:64],
                          loadgen.pool(seed, LIVE)[:64])
