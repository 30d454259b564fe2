"""Conformance suite for the batched forecasting subsystem.

Pins the three contracts ISSUE-10 ships on:

  * the batched grid fit (``fit_arima_grid``) against the
    triangle-constrained scipy CSS oracle (``tests/arima_oracle.py``) —
    AIC within 4.0 of the Nelder-Mead optimum on seeded series;
  * the hybrid engines' ARIMA post-pass (now routed through
    ``repro.forecast.replay``) bit-identical to the scalar per-event
    oracle through ``run()`` — including a ``cv_threshold``-forced trace
    where *every* app takes the ARIMA path — and the cluster engine's
    per-gap window sequences vector == scalar;
  * the SPES predictor family (``SpesSpec``) across every engine:
    scalar oracle, fused, pallas and reference are bit-identical on
    cold counts, final windows AND waste (the float64-compute /
    single-f32-rounding state update makes waste exact, not just
    close), cluster vector == scalar, ``sweep()`` rows == single
    ``run()``s — plus the frontier scenario: long-period timers (period
    beyond the 240-minute histogram range) where SpesSpec strictly
    Pareto-dominates the hybrid on the cold-start/waste frontier.
"""
import numpy as np
import pytest

from repro.core.experiment import (EngineOptions, FixedSpec, HybridSpec,
                                   SpesSpec, run, sweep)
from repro.core.policy import HybridConfig, HybridHistogramPolicy, SpesPolicy
from repro.core.simulator import simulate_scalar
from repro.core.workload import Trace
from repro.core.workload_spec import azure_like, timer_heavy
from repro.forecast import MAX_OBS, ORDER_GRID, fit_arima_grid
from repro.serving.apptable import AppTable
from repro.serving.cluster_vector import ClusterSpec, run_cluster, sweep_cluster

from golden_traces import CFG48, coarse_twoweek


# --------------------------------------------------------------------------
# Batched grid fit vs the scipy CSS oracle
# --------------------------------------------------------------------------


def _oracle_bank():
    rng = np.random.default_rng(17)
    ar1 = [50.0]
    for _ in range(40):
        ar1.append(50.0 + 0.75 * (ar1[-1] - 50.0) + rng.normal(0, 2.0))
    trend = np.arange(30) * 4.0 + 20.0 + rng.normal(0, 0.5, 30)
    periodic = 300.0 + 30.0 * np.sin(np.arange(48) * 0.9) \
        + rng.normal(0, 3.0, 48)
    return {"ar1": np.asarray(ar1), "trend": trend, "periodic": periodic}


def test_batched_fit_tracks_scipy_oracle():
    """Per-order AIC within 4.0 of the constrained Nelder-Mead optimum
    for every order with <= 3 free coefficients; the two 4-coefficient
    orders (2,0,2)/(2,1,2) get a looser 12.0 (their CSS surface has
    boundary optima on the invertibility triangle that fixed-iteration
    LM does not always reach). What the product depends on — the AIC of
    the *selected* (argmin) order — stays within the tight bound."""
    pytest.importorskip("scipy")
    from arima_oracle import fit_css_oracle

    for name, y in _oracle_bank().items():
        row = np.zeros((1, MAX_OBS), np.float32)
        row[0, :len(y)] = y
        fit = fit_arima_grid(row, [len(y)])
        checked = 0
        best_batched = best_oracle = np.inf
        for i, order in enumerate(ORDER_GRID):
            if not bool(fit.valid[0, i]):
                continue
            oracle = fit_css_oracle(y, order)
            if oracle is None:
                continue
            p, _, q = order
            tol = 4.0 if p + q <= 3 else 12.0
            assert float(fit.aic[0, i]) <= oracle[0] + tol, \
                f"{name} order {order}: batched AIC " \
                f"{float(fit.aic[0, i]):.3f} vs oracle {oracle[0]:.3f}"
            best_batched = min(best_batched, float(fit.aic[0, i]))
            best_oracle = min(best_oracle, oracle[0])
            checked += 1
        assert checked >= 10, f"{name}: too few valid fits ({checked})"
        assert best_batched <= best_oracle + 4.0, \
            f"{name}: selected-order AIC {best_batched:.3f} vs oracle " \
            f"best {best_oracle:.3f}"


# --------------------------------------------------------------------------
# Hybrid ARIMA post-pass: engines vs the scalar oracle
# --------------------------------------------------------------------------


def _assert_run_equal(got, oracle, err, waste_exact=True):
    np.testing.assert_array_equal(got.invocations, oracle.invocations,
                                  err_msg=err)
    np.testing.assert_array_equal(got.cold, oracle.cold, err_msg=err)
    np.testing.assert_array_equal(got.final_prewarm, oracle.final_prewarm,
                                  err_msg=err)
    np.testing.assert_array_equal(got.final_keep_alive,
                                  oracle.final_keep_alive, err_msg=err)
    if waste_exact:
        np.testing.assert_array_equal(got.wasted_minutes,
                                      oracle.wasted_minutes, err_msg=err)
    else:
        np.testing.assert_allclose(got.wasted_minutes, oracle.wasted_minutes,
                                   rtol=1e-5, atol=1e-3, err_msg=err)


@pytest.mark.parametrize("seed", [3, 11, 29])
def test_hybrid_arima_replay_matches_scalar_oracle(seed):
    """cv_threshold=1.9 sits just under the bursty traces' CV, forcing a
    healthy mix of histogram- and ARIMA-governed apps; the batched replay
    must reproduce the scalar per-event oracle app-for-app."""
    trace = coarse_twoweek(n_apps=12, seed=seed)
    cfg = HybridConfig(histogram=CFG48.histogram, use_arima=True,
                       cv_threshold=1.9)
    oracle = simulate_scalar(trace, HybridHistogramPolicy(cfg))
    got = run(trace, HybridSpec.from_config(cfg), engine="fused")
    _assert_run_equal(got, oracle, f"hybrid+arima fused seed={seed}")
    chunked = run(trace, HybridSpec.from_config(cfg), engine="fused",
                  options=EngineOptions(app_chunk=5))
    _assert_run_equal(chunked, oracle,
                      f"hybrid+arima fused chunked seed={seed}")


@pytest.mark.parametrize("engine,opts", [
    pytest.param("fused", {}, id="fused"),
    pytest.param("pallas", {}, id="pallas"),
    # each app its own chunk: the post-pass reads OOB flags across chunks
    pytest.param("pallas", {"app_chunk": 1}, id="pallas-app_chunk1")])
def test_forecaster_consulted_mid_trace_only_is_replayed(engine, opts):
    """An app that is OOB-heavy — and so consults the forecaster — early in
    its trace but not at its end must still be replayed: its final state
    alone does not show that the forecaster governed some of its gaps."""
    gaps = [300.0] * 6 + [5.0] * 20      # past the 60-min range, then inside
    early = np.concatenate([[0.0], np.cumsum(gaps)])
    steady = np.arange(0.0, 182.0, 7.0)   # an in-range companion app
    trace = Trace(specs=None, times=[early, steady],
                  duration_minutes=float(early[-1]) + 60.0)
    spec = HybridSpec(range_minutes=60.0, use_arima=True)
    oracle = simulate_scalar(trace, spec.build())
    # the forecaster's windows matter here: without them the app differs
    no_forecast = simulate_scalar(
        trace, HybridSpec(range_minutes=60.0, use_arima=False).build())
    assert oracle.cold[0] != no_forecast.cold[0]
    got = run(trace, spec, engine=engine, options=EngineOptions(**opts))
    _assert_run_equal(got, oracle, f"mid-trace forecaster, {engine} {opts}",
                      waste_exact=(engine == "fused"))


def test_cluster_hybrid_arima_vector_matches_scalar():
    """The cluster engine's per-app ARIMA window loop was replaced by one
    batched ``hybrid_window_sequences`` call; vector == scalar pins it."""
    table = AppTable.from_spec(timer_heavy(90, days=0.5, seed=7))
    spec = HybridSpec(use_arima=True, cv_threshold=1.9)
    cl = ClusterSpec(n_workers=5, hbm_budget_bytes=float("inf"))
    vec = run_cluster(table, spec, cl, engine="vector")
    sca = run_cluster(table, spec, cl, engine="scalar")
    np.testing.assert_array_equal(vec.cold_pct_per_app, sca.cold_pct_per_app)
    np.testing.assert_array_equal(vec.latencies_s, sca.latencies_s)
    np.testing.assert_allclose(vec.wasted_gb_minutes, sca.wasted_gb_minutes,
                               rtol=1e-9)


# --------------------------------------------------------------------------
# SPES predictor family: cross-engine conformance
# --------------------------------------------------------------------------

SPES_SPECS = [SpesSpec(), SpesSpec(alpha=0.2, band_margin=0.05,
                                   band_sigma=4.0)]


@pytest.fixture(scope="module", params=["azure", "timers"])
def spes_case(request):
    if request.param == "azure":
        trace = azure_like(80, days=0.5, seed=3).materialize()
    else:
        trace = timer_heavy(80, days=0.5, seed=11).materialize()
    oracles = {spec: simulate_scalar(trace, SpesPolicy(spec.to_config()))
               for spec in SPES_SPECS}
    return request.param, trace, oracles


@pytest.mark.parametrize("engine,opts", [
    ("fused", {}), ("fused", {"app_chunk": 7}),
    ("pallas", {}),
])
def test_spes_engines_match_scalar_oracle(spes_case, engine, opts):
    """Cold counts, final windows AND waste bit-identical for every
    engine: the SPES state update computes in float64 and rounds once to
    float32, so XLA fusion choices cannot perturb the decision state."""
    name, trace, oracles = spes_case
    for spec, oracle in oracles.items():
        got = run(trace, spec, engine=engine,
                  options=EngineOptions(**opts))
        _assert_run_equal(got, oracle,
                          f"{spec.name}/{engine}/{opts} on {name}")


def test_spes_sweep_rows_match_single_runs(spes_case):
    name, trace, oracles = spes_case
    grid = sweep(traces=[trace], specs=list(SPES_SPECS))
    for s, spec in enumerate(SPES_SPECS):
        row = grid.row(0, s)
        _assert_run_equal(row, oracles[spec],
                          f"sweep row {s} ({spec.name}) on {name}")


def test_spes_cluster_vector_matches_scalar():
    table = AppTable.from_spec(azure_like(100, days=0.25, seed=11))
    cl = ClusterSpec(n_workers=5, hbm_budget_bytes=float("inf"))
    grid = sweep_cluster(table, [SpesSpec(), FixedSpec(keep_alive=10.0)],
                         [cl])
    for s, spec in enumerate([SpesSpec(), FixedSpec(keep_alive=10.0)]):
        vec = grid.row(0, s, 0)
        sca = run_cluster(table, spec, cl, engine="scalar")
        err = f"cluster {spec.name}"
        np.testing.assert_array_equal(vec.cold_pct_per_app,
                                      sca.cold_pct_per_app, err_msg=err)
        np.testing.assert_array_equal(vec.latencies_s, sca.latencies_s,
                                      err_msg=err)
        np.testing.assert_allclose(vec.wasted_gb_minutes,
                                   sca.wasted_gb_minutes, rtol=1e-9,
                                   err_msg=err)


# --------------------------------------------------------------------------
# The frontier scenario: SpesSpec Pareto-dominates the hybrid
# --------------------------------------------------------------------------


def _long_period_timers(n_apps=100, days=7, seed=42):
    """Timers with periods past the histogram's 240-minute range: every
    IT lands out of bounds, so the hybrid can only offer its (wide) ARIMA
    or standard-keep-alive windows while the SPES band tracks the period
    directly."""
    rng = np.random.default_rng(seed)
    duration = days * 24 * 60.0
    periods = rng.uniform(280.0, 420.0, n_apps)
    times = []
    for i in range(n_apps):
        phase = rng.uniform(0.0, periods[i])
        t = np.arange(phase, duration, periods[i])
        t = t + rng.normal(0.0, 0.5, t.shape)
        times.append(np.sort(np.clip(t, 0.0, duration - 1e-6)))
    return Trace(specs=None, times=times, duration_minutes=duration)


def test_spes_pareto_dominates_hybrid_on_long_period_timers():
    trace = _long_period_timers()
    hybrid = run(trace, HybridSpec(use_arima=True), engine="fused")
    h_cold = int(hybrid.cold.sum())
    h_waste = float(hybrid.wasted_minutes.sum())
    for spec in (SpesSpec(), SpesSpec(band_margin=0.05, band_sigma=4.0)):
        r = run(trace, spec, engine="fused")
        cold, waste = int(r.cold.sum()), float(r.wasted_minutes.sum())
        assert cold < h_cold and waste < h_waste, \
            f"{spec.name}: ({cold}, {waste:.0f}) does not dominate " \
            f"hybrid ({h_cold}, {h_waste:.0f})"
