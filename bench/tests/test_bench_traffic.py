"""The traffic generator repeats exactly per seed and gives every seed
the same work in another order."""
import numpy as np
import pytest

from bench.loadgen import Traffic, make_pool

OPEN = {"kind": "open", "cameras": 8, "fps": 30, "jitter_ms": 1.0}
CLOSED = {"kind": "closed", "streams": 4, "queued": 8}
SEEDS = [0, 7, 2**31 + 11, 2**40 + 3, -5]


@pytest.mark.parametrize("seed", SEEDS)
def test_schedule_repeats_per_seed(seed):
    a = Traffic(OPEN, seed, 32).schedule(10.0)
    b = Traffic(OPEN, seed, 32).schedule(10.0)
    assert a == b
    assert all(0 <= d < 10.0 for d, _, _ in a)
    assert [d for d, _, _ in a] == sorted(d for d, _, _ in a)


def test_seeds_share_the_arrivals():
    """Every seed offers the same number of frames at the same slots of
    the frame period, each within the jitter."""
    period = 1 / 30
    slots = np.arange(8) * period / 8
    counts = set()
    for seed in SEEDS:
        sched = Traffic(OPEN, seed, 32).schedule(10.0)
        counts.add(len(sched))
        for due, s, k in sched:
            off = (due - k * period)
            assert np.min(np.abs(off - slots)) <= 1e-3 + 1e-9 or due == 0.0
    assert len(counts) <= 2     # a first frame may fall before 0 and clamp


@pytest.mark.parametrize("seed", SEEDS)
def test_content_repeats_per_seed(seed):
    a, b = Traffic(CLOSED, seed, 32), Traffic(CLOSED, seed, 32)
    seq = [[a.content(s, k) for k in range(64)] for s in range(4)]
    assert seq == [[b.content(s, k) for k in range(64)] for s in range(4)]
    assert all(0 <= i < 32 for row in seq for i in row)
    assert all(len(set(row[:32])) == 32 for row in seq)  # odd stride
    with pytest.raises(ValueError):
        a.schedule(1.0)


def test_pool_repeats_per_seed():
    assert np.array_equal(make_pool(2**33, 3, 8, 16),
                          make_pool(2**33, 3, 8, 16))
    assert not np.array_equal(make_pool(1, 3, 8, 16), make_pool(2, 3, 8, 16))
