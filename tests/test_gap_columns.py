"""The Pallas path's host gap columns (``simulator._gap_columns``).

The builder works in row blocks spread over a thread pool and reads the
chunk's rows in place from the padded fleet. Its outputs must equal, bit
for bit, the whole-array arithmetic it replaced, kept below as the
oracle, for any block split and any pool size, and no thread may raise a
``RuntimeWarning`` on the +inf padding.
"""
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import policy_math, simulator

N_BINS = 60


def _oracle(sub, bin_minutes, n_bins):
    """The whole-array gap columns the row-block builder replaced."""
    clock = np.asarray(sub, np.float64)
    valid = np.isfinite(clock)
    first = np.zeros_like(valid)
    first[:, 0] = valid[:, 0]
    gap = np.zeros_like(clock)
    gap[:, 1:] = np.where(valid[:, 1:], clock[:, 1:] - np.where(
        valid[:, 1:], clock[:, :-1], 0.0), 0.0)
    code = policy_math.encode_gaps(gap, valid, first, bin_minutes, n_bins)
    it_lo = gap.astype(np.float32)
    it_hi = it_lo.copy()
    down = it_lo > gap
    it_lo[down] = np.nextafter(it_lo[down], np.float32(-np.inf))
    up = it_hi < gap
    it_hi[up] = np.nextafter(it_hi[up], np.float32(np.inf))
    n_ev = valid.sum(axis=1)
    last = np.where(n_ev > 0, clock[np.arange(len(clock)),
                                    np.maximum(n_ev - 1, 0)], -np.inf)
    return (it_lo, it_hi, code), last


def _buckets_before(times, counts):
    """``_buckets`` as it was: whole rows copied, then sliced."""
    lo = 0
    for edge in simulator.BUCKET_EDGES:
        sel = np.where((counts > lo) & (counts <= edge))[0]
        if len(sel):
            width = int(counts[sel].max())
            yield sel, times[sel][:, :width]
        lo = edge


def _fleet(bin_minutes, n_rows=23, width=20, seed=0):
    """Sorted float64 rows padded with +inf: the first rows hold 0, 1 and
    ``width`` events, the rest random counts. Gaps are drawn from bin
    edges (k x ``bin_minutes``, the out-of-bounds edge ``n_bins x
    bin_minutes`` and just below it) and from a continuous range, on
    clocks that float32 cannot hold."""
    rng = np.random.default_rng(seed)
    edges = bin_minutes * np.array([1, 2, 7, N_BINS - 1, N_BINS, N_BINS + 1])
    counts = np.concatenate([[0, 1, width],
                             rng.integers(0, width + 1, n_rows - 3)])
    times = np.full((n_rows, width + 5), np.inf)   # wider than any row
    for i, c in enumerate(counts):
        if c == 0:
            continue
        on_edge = rng.random(c - 1) < 0.4
        gaps = np.where(on_edge, rng.choice(edges, c - 1),
                        rng.uniform(0.0, 1.2 * N_BINS * bin_minutes, c - 1))
        t0 = 20_000.0 * rng.random() + 1.0 / 3.0
        times[i, :c] = t0 + np.concatenate([[0.0], np.cumsum(gaps)])
    return times, counts


class _CountingPool(ThreadPoolExecutor):
    """A pool that counts the builds handed to it."""

    def __init__(self, workers):
        super().__init__(workers)
        self.maps = 0

    def map(self, *args, **kw):
        self.maps += 1
        return super().map(*args, **kw)


@pytest.fixture(params=[1, 8], ids=["pool1", "pool8"])
def pool(request, monkeypatch):
    p = _CountingPool(request.param)
    monkeypatch.setattr(simulator, "_gap_pool", lambda: p)
    yield p
    p.shutdown()


def _assert_equal(got, want):
    (lo, hi, code), last = got
    (lo_w, hi_w, code_w), last_w = want
    assert lo.dtype == np.float32 and hi.dtype == np.float32
    assert code.dtype == np.int32 and last.dtype == np.float64
    assert np.array_equal(lo, lo_w)
    assert np.array_equal(hi, hi_w)
    assert np.array_equal(code, code_w)
    assert np.array_equal(last, last_w)


def test_the_fleet_covers_every_edge_case():
    times, counts = _fleet(2.5)
    gaps = np.concatenate([np.diff(times[i, :c])
                           for i, c in enumerate(counts)])
    cast = gaps.astype(np.float32)
    # float32 casts that are exact, that round up and that round down
    assert (cast == gaps).any()
    assert (cast > gaps).any() and (cast < gaps).any()
    for k in (1, N_BINS - 1, N_BINS):      # bin edges, the out-of-bounds one
        assert (gaps == k * 2.5).any()
    (_, _, code), _ = _oracle(times, 2.5, N_BINS)
    assert (code == N_BINS).any() and (code == 0).any()
    clock = times[np.isfinite(times)]
    assert (clock.astype(np.float32) != clock).any()
    assert {0, 1, times.shape[1] - 5} <= set(counts.tolist())


@pytest.mark.parametrize("bin_minutes", [1.0, 2.5])
@pytest.mark.parametrize("n_rows", [1, 2, 10, 23])
def test_row_blocks_equal_the_whole_array_oracle(pool, monkeypatch,
                                                 bin_minutes, n_rows):
    # 3 rows a block: 1 and 2 rows are one block, built on the calling
    # thread; 10 and 23 rows are 4 and 8 blocks, the last one short
    monkeypatch.setattr(simulator, "_GAP_BLOCK", 60)
    times, counts = _fleet(bin_minutes)
    rows = np.random.default_rng(n_rows).permutation(len(times))[:n_rows]
    width = int(counts[rows].max())
    want = _oracle(times[rows][:, :width], bin_minutes, N_BINS)
    # rows read in place from the padded fleet, and a chunk given whole
    _assert_equal(simulator._gap_columns(times, bin_minutes, N_BINS,
                                         rows, width), want)
    _assert_equal(simulator._gap_columns(
        np.ascontiguousarray(times[rows][:, :width]), bin_minutes, N_BINS),
        want)
    assert pool.maps == (2 if n_rows > 6 else 0)


def test_pool_size_does_not_change_the_bits(monkeypatch):
    monkeypatch.setattr(simulator, "_GAP_BLOCK", 40)
    times, counts = _fleet(1.0, n_rows=64, width=30, seed=3)
    rows = np.flatnonzero(counts)
    width = int(counts.max())
    outs = []
    for workers in (1, 8):
        with ThreadPoolExecutor(workers) as p:
            monkeypatch.setattr(simulator, "_gap_pool", lambda: p)
            outs.append(simulator._gap_columns(times, 1.0, N_BINS, rows,
                                               width))
    _assert_equal(outs[0], outs[1])
    _assert_equal(outs[0], _oracle(times[rows][:, :width], 1.0, N_BINS))


def test_the_pool_raises_no_runtime_warning():
    # The default block size and more than three blocks' rows of up to 64
    # events, +inf padded: the process-wide pool builds them.
    width = 64
    n = 3 * (simulator._GAP_BLOCK // width) + 1
    rng = np.random.default_rng(11)
    counts = rng.integers(1, width + 1, n)
    times = np.sort(rng.uniform(0.0, 20_160.0, (n, width)), axis=1)
    times[np.arange(width) >= counts[:, None]] = np.inf
    rows = rng.permutation(n)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        got = simulator._gap_columns(times, 1.0, 240, rows, width)
    _assert_equal(got, _oracle(times[rows], 1.0, 240))


@pytest.mark.parametrize("seed", [0, 1])
def test_buckets_gather_the_same_rows(seed):
    rng = np.random.default_rng(seed)
    counts = np.concatenate([[0, 64, 65, 512, 513, 700],
                             rng.integers(0, 700, 40)])
    times = np.full((len(counts), 900), np.inf)
    for i, c in enumerate(counts):
        times[i, :c] = np.sort(rng.uniform(0.0, 1440.0, c))
    got = list(simulator._buckets(times, counts))
    want = list(_buckets_before(times, counts))
    assert len(got) == len(want) == 3
    for (sel, sub), (sel_w, sub_w) in zip(got, want):
        assert np.array_equal(sel, sel_w)
        assert sub.shape == sub_w.shape and np.array_equal(sub, sub_w)
    chunks = list(simulator._chunked_buckets(times, counts, 7))
    want = [(sel[lo:lo + 7], sub[lo:lo + 7]) for sel, sub in want
            for lo in range(0, len(sel), 7)]
    assert len(chunks) == len(want)
    for (sel, sub), (sel_w, sub_w) in zip(chunks, want):
        assert np.array_equal(sel, sel_w)
        assert sub.shape == sub_w.shape and np.array_equal(sub, sub_w)
