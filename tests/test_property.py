"""Hypothesis property tests on system invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.histogram import AppHistogram, HistogramConfig
from repro.core.policy import (FixedKeepAlivePolicy, HybridConfig,
                               HybridHistogramPolicy, PolicyWindows, is_warm,
                               loaded_idle_time)
from repro.core.workload import AppSpec, Trace
from repro.core.simulator import simulate_scalar

its = st.floats(min_value=0.0, max_value=5000.0, allow_nan=False)


@settings(max_examples=50, deadline=None)
@given(st.lists(its, min_size=1, max_size=300))
def test_histogram_counts_conserved(values):
    cfg = HistogramConfig(range_minutes=240.0)
    h = AppHistogram(cfg)
    for v in values:
        h.record(v)
    assert h.total + h.oob == len(values)
    assert h.counts.sum() == h.total
    assert h.cv >= 0.0


@settings(max_examples=50, deadline=None)
@given(st.lists(its, min_size=1, max_size=200))
def test_histogram_windows_bounds(values):
    cfg = HistogramConfig()
    h = AppHistogram(cfg)
    for v in values:
        h.record(v)
    pw, ka = h.windows()
    assert pw >= 0.0
    assert ka >= 0.0
    # windows never exceed the (margin-inflated) histogram range
    assert pw + ka <= cfg.range_minutes * (1.0 + cfg.margin) + 1e-6


@settings(max_examples=50, deadline=None)
@given(st.floats(0.0, 500.0), st.floats(0.0, 500.0), st.floats(0.0, 1000.0))
def test_warmth_waste_consistency(prewarm, keep, it):
    w = PolicyWindows(prewarm, keep)
    waste = loaded_idle_time(it, w)
    assert 0.0 <= waste <= max(keep, 0.0) + 1e-9
    if is_warm(it, w):
        # a warm hit means the image was resident at arrival; for prewarmed
        # windows the resident span ends exactly at the arrival
        assert waste <= it + 1e-9


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(1.0, 2000.0), min_size=2, max_size=60),
       st.floats(1.0, 240.0))
def test_fixed_policy_cold_count_formula(iats, keep):
    """Scalar sim == direct formula for the fixed policy."""
    times = np.cumsum(np.asarray(iats))
    spec = AppSpec(app_id="app-000000", pattern="poisson", rate_per_day=1.0,
                   period_minutes=1.0, exec_time_s=0.0, memory_mb=1.0,
                   n_functions=1, triggers=("http",))
    trace = Trace(specs=[spec], times=[times],
                  duration_minutes=float(times[-1] + 1))
    res = simulate_scalar(trace, FixedKeepAlivePolicy(keep),
                          include_trailing=False)
    expected_cold = 1 + int(np.sum(np.diff(times) > keep))
    assert res.cold[0] == expected_cold
    expected_waste = float(np.minimum(np.diff(times), keep).sum())
    assert np.isclose(res.wasted_minutes[0], expected_waste, rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.floats(1.0, 400.0), min_size=5, max_size=80))
def test_hybrid_never_negative_windows(iats):
    p = HybridHistogramPolicy(HybridConfig(use_arima=False))
    w = p.on_invocation("a", None)
    for it in iats:
        w = p.on_invocation("a", it)
        assert w.prewarm >= 0.0
        assert w.keep_alive >= 0.0


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 64), st.integers(2, 120))
def test_batched_policy_kernel_invariants(napps, nbins):
    """Scan-kernel final state over random gap columns: every closed gap
    counted once (in bounds or OOB), the cumulative counts' last bin is the
    in-bounds total, windows non-negative and within range x (1 + margin)."""
    import jax.numpy as jnp
    from repro.core.simulator import _build_pallas_cfg, _gap_columns
    from repro.kernels.histogram import hybrid_sweep_scan_pallas
    from test_sweep_scan_kernel import _init
    rng = np.random.default_rng(napps * 1000 + nbins)
    width = 12
    cfg = HybridConfig(histogram=HistogramConfig(range_minutes=float(nbins)),
                       use_arima=False)
    n_ev = rng.integers(0, width + 1, napps)
    gaps = rng.uniform(0.0, 1.5 * nbins, (napps, width))
    times = np.cumsum(gaps, axis=1)
    times[np.arange(width)[None, :] >= n_ev[:, None]] = np.inf
    cols, _ = _gap_columns(times, 1.0, nbins)
    ci, cf = _build_pallas_cfg([cfg])
    (cum, oob, _, _, pw, ub, cold, _) = (
        np.asarray(x[0]) for x in hybrid_sweep_scan_pallas(
            *(jnp.asarray(c.T) for c in cols), _init(1, nbins, napps, cf),
            ci, cf, tile_apps=1024, interpret=True))
    code = cols[2]
    in_b = ((code >= 0) & (code < nbins)).sum(axis=1)
    closed = np.maximum(n_ev - 1, 0)
    np.testing.assert_array_equal(cum[-1], in_b)
    np.testing.assert_array_equal(cum[-1] + oob[0], closed)
    assert np.all(np.diff(cum, axis=0) >= 0)
    np.testing.assert_array_equal(cold[0] > 0, n_ev > 0)
    assert np.all(cold[0] <= n_ev)
    ka = ub[0].astype(np.float64) - pw[0]
    assert np.all(pw[0] >= 0)
    assert np.all(ka >= 0)
    assert np.all(ub[0] <= float(nbins) * (1 + cfg.histogram.margin) + 1e-4)
