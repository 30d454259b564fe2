"""Simulator + workload generator tests, including engine equivalence and
reproduction of the paper's headline policy comparisons (trend-level)."""
import numpy as np
import pytest

from repro.core import (EngineOptions, FixedKeepAlivePolicy, FixedSpec,
                        HybridConfig, HybridHistogramPolicy, HybridSpec,
                        NoUnloadSpec, generate_trace, run, simulate_scalar)
from repro.core.workload import sample_apps
from repro.core.workload_spec import WorkloadSpec


def uniform_trace(n_apps, days, seed, max_events):
    """Legacy-equivalent scaling trace (the old ``Trace.synthesize``)."""
    return WorkloadSpec.uniform(n_apps, days=days, seed=seed,
                                max_events=max_events,
                                min_events=1).materialize()


@pytest.fixture(scope="module")
def trace():
    return generate_trace(n_apps=300, days=5.0, seed=7)


@pytest.fixture(scope="module")
def int_trace():
    t = generate_trace(n_apps=60, days=3.0, seed=3)
    for i in range(t.n_apps):
        t.times[i] = np.unique(np.floor(t.times[i]))
    return t


def test_fixed_batch_matches_scalar(int_trace):
    fb = run(int_trace, FixedSpec(10.0), engine="fused")
    fs = simulate_scalar(int_trace, FixedKeepAlivePolicy(10.0))
    np.testing.assert_array_equal(fb.cold, fs.cold)
    np.testing.assert_allclose(fb.wasted_minutes, fs.wasted_minutes,
                               rtol=1e-4, atol=0.5)


def test_hybrid_batch_matches_scalar(int_trace):
    cfg = HybridConfig(use_arima=False)
    hb = run(int_trace, HybridSpec.from_config(cfg))
    hs = simulate_scalar(int_trace, HybridHistogramPolicy(cfg))
    np.testing.assert_array_equal(hb.cold, hs.cold)
    np.testing.assert_allclose(hb.wasted_minutes, hs.wasted_minutes,
                               rtol=1e-4, atol=0.5)


def test_first_invocation_always_cold(trace):
    res = run(trace, NoUnloadSpec())
    assert np.all(res.cold >= 1)


def test_no_unloading_is_lower_bound(trace):
    nou = run(trace, NoUnloadSpec())
    f10 = run(trace, FixedSpec(10.0))
    assert np.all(nou.cold <= f10.cold)
    # no-unloading: exactly one cold start per app
    assert np.all(nou.cold == 1)


def test_longer_keepalive_fewer_colds_more_waste(trace):
    f10 = run(trace, FixedSpec(10.0))
    f120 = run(trace, FixedSpec(120.0))
    assert f120.cold.sum() < f10.cold.sum()
    assert f120.total_wasted > f10.total_wasted
    assert f120.cold_pct_percentile(75) < f10.cold_pct_percentile(75)


def test_hybrid_pareto_dominates_fixed(trace):
    """The paper's headline (Fig. 15): hybrid gives fewer cold starts than
    the 10-minute fixed policy while using LESS memory."""
    f10 = run(trace, FixedSpec(10.0))
    hyb = run(trace, HybridSpec(use_arima=False))
    assert hyb.cold_pct_percentile(75) < f10.cold_pct_percentile(75) / 1.5
    assert hyb.total_wasted < 1.15 * f10.total_wasted


def test_cutoffs_reduce_waste(trace):
    """Fig. 16: [5,99] cutoffs cut memory vs [0,100] without hurting colds."""
    from repro.core.histogram import HistogramConfig
    h_cut = run(trace, HybridSpec(head_percentile=5, tail_percentile=99,
                                  use_arima=False))
    h_all = run(trace, HybridSpec(head_percentile=0, tail_percentile=100,
                                  use_arima=False))
    assert h_cut.total_wasted <= h_all.total_wasted


def test_arima_reduces_always_cold():
    """Fig. 18: ARIMA halves the fraction of 100%-cold-start apps among
    infrequently invoked ones."""
    # apps with ITs beyond the 4h histogram range: periodic ~6h
    from repro.core.workload import AppSpec, Trace
    n = 30
    times = []
    specs = []
    rng = np.random.default_rng(0)
    for i in range(n):
        period = 360.0 + rng.uniform(-5, 5)   # ~6h, OOB for 4h histogram
        t = np.arange(rng.uniform(0, 60), 7 * 1440.0, period)
        times.append(t)
        specs.append(AppSpec(app_id=f"app-{i:06d}", pattern="periodic",
                             rate_per_day=1440.0 / period,
                             period_minutes=period, exec_time_s=1.0,
                             memory_mb=100.0, n_functions=1, triggers=("timer",)))
    trace = Trace(specs=specs, times=times, duration_minutes=7 * 1440.0)
    no_arima = run(trace, HybridSpec(use_arima=False))
    with_arima = run(trace, HybridSpec(use_arima=True))
    assert with_arima.cold.sum() < 0.6 * no_arima.cold.sum()


# --- fused engine: float64 parity, chunking, scale path ----------------------

def test_fixed_batch_float64_boundary_parity():
    """ITs sitting exactly on the keep-alive boundary of a two-week trace:
    float32 time arithmetic flips warm/cold verdicts vs the float64 oracle
    (t ~ 2e4 minutes loses the sub-millisecond IAT bits)."""
    from repro.core.workload import AppSpec, Trace
    c = 1.0 / 3.0
    times = np.arange(0.0, 20160.0, 10.0) + c
    spec = AppSpec(app_id="app-000000", pattern="periodic", rate_per_day=144.0,
                   period_minutes=10.0, exec_time_s=1.0, memory_mb=100.0,
                   n_functions=1, triggers=("timer",))
    trace = Trace(specs=[spec], times=[times], duration_minutes=20160.0)
    fb = run(trace, FixedSpec(10.0), engine="fused")
    fs = simulate_scalar(trace, FixedKeepAlivePolicy(10.0))
    np.testing.assert_array_equal(fb.cold, fs.cold)
    np.testing.assert_allclose(fb.wasted_minutes, fs.wasted_minutes, rtol=1e-9)


def test_hybrid_fused_exact_parity_two_week_trace():
    """Cross-engine: fused batched engine == float64 scalar oracle, exact
    cold counts and ~machine-precision waste, on a full two-week float trace
    (the pre-PR float32 engine diverges here)."""
    t = generate_trace(n_apps=40, days=14.0, seed=11)
    cfg = HybridConfig(use_arima=False)
    hs = simulate_scalar(t, HybridHistogramPolicy(cfg))
    hb = run(t, HybridSpec.from_config(cfg))
    np.testing.assert_array_equal(hb.cold, hs.cold)
    np.testing.assert_allclose(hb.wasted_minutes, hs.wasted_minutes,
                               rtol=1e-9, atol=1e-6)


def test_hybrid_chunked_matches_unchunked(int_trace):
    cfg = HybridConfig(use_arima=False)
    whole = run(int_trace, HybridSpec.from_config(cfg))
    chunked = run(int_trace, HybridSpec.from_config(cfg),
                  options=EngineOptions(app_chunk=7))
    np.testing.assert_array_equal(chunked.cold, whole.cold)
    np.testing.assert_allclose(chunked.wasted_minutes, whole.wasted_minutes)


def test_hybrid_pallas_path_matches_scalar():
    """The fused Pallas kernel path (interpret mode here, TPU in prod) must
    agree with the scalar oracle on a small integer-time trace."""
    from repro.core.workload import Trace
    base = uniform_trace(n_apps=48, days=0.5, seed=4, max_events=24)
    padded, counts = base.to_padded()
    # integer minutes (exact in float32), in a fresh trace — to_padded's
    # cached arrays are shared and must not be mutated
    t = Trace(specs=None, times=None, duration_minutes=base.duration_minutes,
              _padded=(np.floor(padded), counts))
    cfg = HybridConfig(use_arima=False)
    hs = simulate_scalar(t, HybridHistogramPolicy(cfg))
    hp = run(t, HybridSpec.from_config(cfg), engine="pallas",
             options=EngineOptions(app_chunk=16))
    np.testing.assert_array_equal(hp.cold, hs.cold)
    np.testing.assert_allclose(hp.wasted_minutes, hs.wasted_minutes,
                               rtol=1e-4, atol=0.5)


def test_uniform_scaling_path():
    t = uniform_trace(5000, days=2.0, seed=9, max_events=48)
    assert t.n_apps == 5000
    padded, counts = t.to_padded()
    assert padded.shape == (5000, 48)
    assert counts.min() >= 1 and counts.max() <= 48
    # rows sorted, padding is +inf, events within the trace window
    for i in (0, 17, 4999):
        ev = t.events(i)
        assert len(ev) == counts[i]
        assert np.all(np.diff(ev) >= 0)
        assert np.all((ev >= 0) & (ev <= t.duration_minutes))
        assert np.all(np.isinf(padded[i, counts[i]:]))
    assert t.app_id(3) == "app-000003"
    # the padded-only trace runs through both engines
    res = run(t, HybridSpec(use_arima=False),
              options=EngineOptions(app_chunk=2048))
    assert res.invocations.sum() == counts.sum()
    assert np.all(res.cold >= 1)


def test_synthesize_shim_removed():
    """``Trace.synthesize`` is gone after its PR 5 deprecation cycle: any
    access — including ``hasattr`` probes — raises an AttributeError that
    spells out the ``WorkloadSpec.uniform`` replacement (same contract as
    the removed ``simulate*`` entry points)."""
    from repro.core.workload import Trace
    with pytest.raises(AttributeError, match="WorkloadSpec.uniform"):
        Trace.synthesize
    with pytest.raises(AttributeError, match="was removed"):
        Trace.synthesize(n_apps=10)
    assert not hasattr(Trace, "synthesize")
    t = uniform_trace(4, days=0.5, seed=0, max_events=4)
    assert not hasattr(t, "synthesize")


def test_uniform_rejects_invalid_args():
    with pytest.raises(ValueError, match="n_apps"):
        WorkloadSpec.uniform(-1).materialize()
    with pytest.raises(ValueError, match="max_events"):
        WorkloadSpec.uniform(4, max_events=0).materialize()
    with pytest.raises(ValueError, match="min_events"):
        WorkloadSpec.uniform(4, min_events=3).materialize()


def test_simulate_rejects_invalid_app_chunk(int_trace):
    cfg = HybridConfig(use_arima=False)
    with pytest.raises(ValueError, match="app_chunk"):
        run(int_trace, HybridSpec.from_config(cfg),
            options=EngineOptions(app_chunk=-3))


def test_uniform_ragged_last_block():
    """App counts that are NOT a multiple of the generation block must
    produce a fully populated trace (generation is block-aligned, with a
    counter RNG per block)."""
    t = uniform_trace(1000, days=1.0, seed=2, max_events=24)
    padded, counts = t.to_padded()
    assert padded.shape[0] == 1000 and padded.shape[1] <= 24
    assert counts.min() >= 1
    # the ragged tail is as well-formed as the rest
    width = padded.shape[1]
    tail = padded[768:]
    assert np.all(np.isfinite(tail[np.arange(width)[None, :] <
                                   counts[768:, None]]))
    for i in (767, 768, 999):
        ev = t.events(i)
        assert len(ev) == counts[i]
        assert np.all(np.diff(ev) >= 0)
        assert np.all(np.isinf(padded[i, counts[i]:]))
    # regeneration is deterministic block by block
    np.testing.assert_array_equal(
        uniform_trace(1000, days=1.0, seed=2, max_events=24).to_padded()[0],
        padded)


def test_hybrid_ragged_chunk_parity():
    """A bucket whose size is not a multiple of app_chunk (ragged last
    chunk) must change nothing — including through the Pallas path, whose
    kernel tiles and pads independently of the chunking."""
    t = uniform_trace(23, days=0.5, seed=6, max_events=12)
    cfg = HybridConfig(use_arima=False)
    whole = run(t, HybridSpec.from_config(cfg))
    ragged = run(t, HybridSpec.from_config(cfg),
                 options=EngineOptions(app_chunk=5))   # 5,5,5,5,3
    np.testing.assert_array_equal(ragged.cold, whole.cold)
    np.testing.assert_array_equal(ragged.wasted_minutes, whole.wasted_minutes)
    pallas_ragged = run(t, HybridSpec.from_config(cfg), engine="pallas",
                        options=EngineOptions(app_chunk=5))
    np.testing.assert_array_equal(pallas_ragged.cold, whole.cold)
    np.testing.assert_allclose(pallas_ragged.wasted_minutes,
                               whole.wasted_minutes, rtol=1e-5, atol=1e-3)


def test_hybrid_parity_power_of_two_bins():
    """Regression: the percentile binary search must cover the full [0,
    n_bins] answer space — with a power-of-two bin count an iteration-short
    search returns the wrong head bin and flips windows vs the oracle."""
    from repro.core.histogram import HistogramConfig
    t = uniform_trace(64, days=1.0, seed=33, max_events=32)
    cfg = HybridConfig(histogram=HistogramConfig(range_minutes=128.0),
                       use_arima=False)
    hs = simulate_scalar(t, HybridHistogramPolicy(cfg))
    hb = run(t, HybridSpec.from_config(cfg))
    np.testing.assert_array_equal(hb.cold, hs.cold)
    np.testing.assert_allclose(hb.wasted_minutes, hs.wasted_minutes,
                               rtol=1e-6, atol=1e-6)


def test_find_first_ge_power_of_two_bins():
    import jax.numpy as jnp
    from repro.core.histogram import find_first_ge
    for n_bins in (2, 4, 8, 64, 128, 240, 256):
        cum = jnp.asarray(np.full((1, n_bins), 5), jnp.int32)
        thr = jnp.asarray([1], jnp.int32)
        assert int(find_first_ge(cum, thr)[0]) == 0, n_bins
        empty = jnp.zeros((1, n_bins), jnp.int32)
        assert int(find_first_ge(empty, thr)[0]) == n_bins, n_bins
        ladder = jnp.asarray(np.arange(1, n_bins + 1)[None, :], jnp.int32)
        for want in (0, n_bins // 2, n_bins - 1):
            got = int(find_first_ge(ladder, jnp.asarray([want + 1]))[0])
            assert got == want, (n_bins, want, got)


def test_uniform_parity_small():
    t = uniform_trace(64, days=1.0, seed=21, max_events=32)
    cfg = HybridConfig(use_arima=False)
    hs = simulate_scalar(t, HybridHistogramPolicy(cfg))
    hb = run(t, HybridSpec.from_config(cfg))
    np.testing.assert_array_equal(hb.cold, hs.cold)
    np.testing.assert_allclose(hb.wasted_minutes, hs.wasted_minutes,
                               rtol=1e-6, atol=1e-6)


def test_zero_event_apps_consistent_across_engines():
    """Regression (the legacy synthesize clamped Poisson counts to >= 1, so
    no engine ever saw a count-0 row): the spec engine's default allows
    zero-event apps, and every engine must agree on them — zero cold
    starts, zero invocations, zero waste, the policy's initial windows, and
    no contribution to always_cold_fraction."""
    # near-zero rates: most apps get no events at all
    t = WorkloadSpec.uniform(96, days=0.02, seed=11, max_events=8).materialize()
    _, counts = t.to_padded()
    zeros = np.where(counts == 0)[0]
    assert len(zeros) > 10, "fixture must actually contain zero-event apps"

    # a list-backed trace with an explicitly empty row exercises the same
    # contract on the eager representation
    lt = __import__("repro.core.workload", fromlist=["Trace"]).Trace(
        specs=None, times=[np.asarray([1.0, 7.0]), np.asarray([])],
        duration_minutes=60.0)

    for trace, zsel in ((t, zeros), (lt, np.asarray([1]))):
        spec = HybridSpec(range_minutes=48.0, use_arima=False)
        results = {eng: run(trace, spec, engine=eng)
                   for eng in ("scalar", "fused", "pallas")}
        base = results["scalar"]
        assert np.all(base.invocations[zsel] == 0)
        assert np.all(base.cold[zsel] == 0)
        assert np.all(base.wasted_minutes[zsel] == 0.0)
        # never-invoked apps report the policy's initial (standard) windows
        assert np.all(base.final_prewarm[zsel] == 0.0)
        assert np.all(base.final_keep_alive[zsel] == 48.0)
        for eng, res in results.items():
            np.testing.assert_array_equal(res.cold, base.cold, err_msg=eng)
            np.testing.assert_array_equal(res.invocations, base.invocations,
                                          err_msg=eng)
            np.testing.assert_array_equal(res.final_prewarm,
                                          base.final_prewarm, err_msg=eng)
            np.testing.assert_array_equal(res.final_keep_alive,
                                          base.final_keep_alive, err_msg=eng)
            np.testing.assert_allclose(res.wasted_minutes,
                                       base.wasted_minutes, rtol=1e-5,
                                       atol=1e-3, err_msg=eng)
        fx = run(trace, FixedSpec(10.0))
        assert np.all(fx.cold[zsel] == 0)
        assert np.all(fx.final_keep_alive[zsel] == 10.0)
        # count-0 rows must not inflate the always-cold fraction
        invoked = base.invocations > 0
        want = (np.mean(base.cold[invoked] >= base.invocations[invoked])
                if invoked.any() else 0.0)
        assert base.always_cold_fraction == pytest.approx(want)


def test_always_cold_fraction_ignores_zero_invocation_apps():
    from repro.core.simulator import SimResult
    res = SimResult(cold=np.array([1, 0, 0, 2]),
                    invocations=np.array([1, 0, 0, 4]),
                    wasted_minutes=np.zeros(4))
    # only the two invoked apps count; one of them is always-cold
    assert res.always_cold_fraction == pytest.approx(0.5)
    empty = SimResult(cold=np.zeros(3, np.int64),
                      invocations=np.zeros(3, np.int64),
                      wasted_minutes=np.zeros(3))
    assert empty.always_cold_fraction == 0.0


# --- workload generator vs paper anchors -------------------------------------

def test_rate_distribution_anchors():
    specs = sample_apps(4000, seed=11)
    rates = np.array([s.rate_per_day for s in specs])
    assert np.mean(rates <= 24) == pytest.approx(0.45, abs=0.06)
    assert np.mean(rates <= 1440) == pytest.approx(0.81, abs=0.05)
    assert rates.max() / rates.min() > 1e6    # many orders of magnitude


def test_exec_time_distribution():
    specs = sample_apps(4000, seed=12)
    execs = np.array([s.exec_time_s for s in specs])
    assert np.median(execs) < 1.0                      # 50% below 1s
    assert np.mean(execs <= 60.0) > 0.9                # ~96% under 60s


def test_memory_distribution():
    specs = sample_apps(4000, seed=13)
    mem = np.array([s.memory_mb for s in specs])
    assert 90 < np.median(mem) < 250                   # ~170MB median
    assert np.percentile(mem, 90) < 600                # 90% under ~400MB


def test_cv_classes(trace):
    cvs = []
    for i in range(trace.n_apps):
        ia = trace.iats(i)
        if len(ia) >= 5:
            cvs.append(np.std(ia) / max(np.mean(ia), 1e-9))
    cvs = np.array(cvs)
    assert np.mean(cvs < 0.1) > 0.08     # periodic class exists
    assert np.mean(cvs > 1.0) > 0.2      # bursty class exists
