"""The benchmark's generator copy is pinned to digests recorded here, not to
the program's generator, so a later change to the program cannot move the
traffic the benchmark measures."""
import hashlib

import numpy as np
import pytest

from bench import fleet

PINNED = [
    # (apps, days, seed): (shape, events, times+counts, population)
    ((600, 2.0, 7), ((600, 2880), 334722,
                     "943bd55c2851b0d9fa52505ebf07858a81e9cf31cd3235c660aaad26242e1378",
                     "61bcb30cf423c597e990a221b58347abdc637c5efd69cf090dfb2d6a3498b480")),
    ((120, 14.0, 2 ** 31 + 5), ((120, 20160), 289630,
                                "57d0829ea54e618ae31f8dd4d799f2e4dcb59d2e43ae6d183221bff2e715fbad",
                                "79e7f1f1e4047d7b159cf57ab927dfc57d02f0d41c76468cff1320029142d445")),
]


def _digest(n, days, seed):
    times, counts = fleet.fleet(n, days, seed)
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(times).tobytes())
    h.update(counts.astype("<i4").tobytes())
    pop = fleet.population(n, days, seed)
    g = hashlib.sha256()
    for key in sorted(pop):
        g.update(key.encode())
        g.update(np.ascontiguousarray(pop[key]).tobytes())
    return times.shape, int(counts.sum()), h.hexdigest(), g.hexdigest()


@pytest.mark.parametrize("args,expected", PINNED)
def test_fleet_matches_recorded_digest(args, expected):
    assert _digest(*args) == expected


def test_fleet_is_sorted_padded_and_capped():
    times, counts = fleet.fleet(400, 3.0, 11)
    assert times.dtype == np.float64 and counts.dtype == np.int32
    for row, c in zip(times, counts):
        assert np.all(np.isfinite(row[:c])) and np.all(np.isinf(row[c:]))
        assert np.all(np.diff(row[:c]) >= 1.0 - 1e-9)
        assert np.all((row[:c] >= 0) & (row[:c] < 3.0 * 1440))
    # every app keeps its own rate up to the cap: the busiest reach it
    assert counts.max() >= 0.9 * 3 * 1440
    assert np.mean(counts > 1440) > 0.1


def test_fleet_is_made_from_the_seed():
    a, ca = fleet.fleet(300, 2.0, 3)
    b, cb = fleet.fleet(300, 2.0, 3)
    c, _ = fleet.fleet(300, 2.0, 4)
    assert np.array_equal(a, b) and np.array_equal(ca, cb)
    assert not np.array_equal(a[:, :8], c[:, :8])
