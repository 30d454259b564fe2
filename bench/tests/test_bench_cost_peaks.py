"""The roofline's byte count and the peaks table."""
import numpy as np
import pytest

from bench import cost, fleet
from bench.peaks import PEAKS, peaks


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v99 imaginary")


def test_v5e_peaks_have_a_source():
    p = peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert all("source" in v for v in PEAKS.values())


def test_least_bytes_counts_events_and_results_once():
    assert cost.sweep_least_bytes(1000, 10, 3) == 1000 * 4 + 3 * 10 * 5 * 4


@pytest.mark.parametrize("days", [0.25, 1.0])
def test_least_bytes_depend_only_on_fleet_and_grid_shapes(days):
    """Two fleets with the same events and active apps cost the same,
    however the events are spread; padding (the trace's width) is not
    work."""
    times, counts = fleet.fleet(300, days, 5)
    n_ev, n_act = int(counts.sum()), int((counts > 0).sum())
    wide = np.pad(times, ((0, 0), (0, 64)), constant_values=np.inf)
    assert wide.shape[1] > times.shape[1]
    shuffled = np.random.default_rng(0).permutation(counts)
    got = cost.sweep_least_bytes(int(shuffled.sum()),
                                 int((shuffled > 0).sum()), 32)
    assert got == cost.sweep_least_bytes(n_ev, n_act, 32)
    assert got == n_ev * cost.EVENT_BYTES + 32 * n_act * 5 * 4


def test_search_ops_scale_with_bins_logarithmically():
    assert cost.sweep_search_ops(10, 1, 60) == 10 * 2 * 6
    assert cost.sweep_search_ops(10, 32, 240) == 10 * 32 * 2 * 8
