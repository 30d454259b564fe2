"""Differential conformance harness across the hybrid-policy engines.

Engines under test (all routed through ``repro.core.policy_math`` and the
``repro.core.experiment.run`` front door):

  * ``engine="scalar"``     — float64 event-driven oracle
  * ``engine="fused"``      — float64 factored lax.scan sweep engine
  * ``engine="pallas"``     — float32 sweep TPU kernel (interpret on CPU),
                              SMEM config block via scalar prefetch

Assertions: exact cold-count, invocation, and final-window parity for every
engine; waste is bit-exact for the float64 engine (same accumulation order
as the oracle) and machine-precision-close for the float32 engine (its
per-gap terms accumulate in float32).

The traces (see ``golden_traces``) include a two-week trace with
sub-millisecond inter-arrivals — absolute timestamps beyond float32 — which
the float32 engine survives because the Pallas kernel is fed gaps
differenced on the host in float64, plus OOB-heavy and sub-``min_samples``
apps that exercise every decision-gate branch. This suite is also run by
CI under ``JAX_ENABLE_X64=0`` to emulate TPU's float64-free numerics.
"""
import numpy as np
import pytest

from repro.core.experiment import EngineOptions, HybridSpec, run
from repro.core.policy import HybridConfig, HybridHistogramPolicy
from repro.core.simulator import simulate_scalar
from repro.core.workload import Trace

from golden_traces import (CFG48, bursty_subms_multiweek, coarse_twoweek,
                           synthesized_small, GOLDEN_TRACES)


def _run(t, cfg, engine, **opts):
    return run(t, HybridSpec.from_config(cfg), engine=engine,
               options=EngineOptions(**opts))


# name -> (runner, waste is bit-exact vs the float64 oracle)
ENGINES = {
    "jnp_f64": (lambda t, cfg: _run(t, cfg, "fused"), True),
    "jnp_f64_chunked": (lambda t, cfg: _run(t, cfg, "fused", app_chunk=7),
                        True),
    "pallas_f32": (lambda t, cfg: _run(t, cfg, "pallas", app_chunk=16),
                   False),
}

TRACES = {
    "bursty_subms_multiweek": bursty_subms_multiweek,
    "coarse_twoweek": coarse_twoweek,
    "synthesized_small": synthesized_small,
}


@pytest.fixture(scope="module", params=sorted(TRACES))
def case(request):
    name = request.param
    trace = TRACES[name]()
    cfg = GOLDEN_TRACES[name][1]
    oracle = simulate_scalar(trace, HybridHistogramPolicy(cfg))
    return name, trace, cfg, oracle


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_engine_conformance(case, engine):
    name, trace, cfg, oracle = case
    runner, waste_exact = ENGINES[engine]
    got = runner(trace, cfg)
    err = f"{engine} vs scalar oracle on {name}"
    np.testing.assert_array_equal(got.invocations, oracle.invocations,
                                  err_msg=err)
    np.testing.assert_array_equal(got.cold, oracle.cold, err_msg=err)
    # the float32 decision layer is dtype-invariant: windows match exactly
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


def test_float32_engines_agree_exactly():
    """The float32 engine's results do not depend on how the driver chunks
    the apps: one chunk per bucket (the default here, as in every chip
    cell), chunks of 16 and ragged chunks of 7 give identical results
    bit-for-bit, waste included (each app's waste accumulates in its own
    lane, in event order)."""
    trace = coarse_twoweek()
    a = _run(trace, CFG48, "pallas")
    for chunk in (16, 7):
        b = _run(trace, CFG48, "pallas", app_chunk=chunk)
        err = f"app_chunk={chunk} vs one chunk per bucket"
        np.testing.assert_array_equal(a.cold, b.cold, err_msg=err)
        np.testing.assert_array_equal(a.final_prewarm, b.final_prewarm,
                                      err_msg=err)
        np.testing.assert_array_equal(a.final_keep_alive, b.final_keep_alive,
                                      err_msg=err)
        np.testing.assert_array_equal(a.wasted_minutes, b.wasted_minutes,
                                      err_msg=err)


def test_time_translation_invariance_batched():
    """The property the host gap columns rely on (verdicts read only
    differences of the clock): shifting every timestamp by a constant
    changes no verdict, window, or waste."""
    base = coarse_twoweek(n_apps=16, seed=3)
    shift = 4096.0 + 1.0 / 64.0   # on the trace grid, keeps times exact
    shifted = type(base)(
        specs=None, times=[t + shift for t in base.times],
        duration_minutes=base.duration_minutes + shift)
    for tr_a, tr_b in ((base, shifted),):
        a = _run(tr_a, CFG48, "fused", include_trailing=False)
        b = _run(tr_b, CFG48, "fused", include_trailing=False)
        np.testing.assert_array_equal(a.cold, b.cold)
        np.testing.assert_array_equal(a.wasted_minutes, b.wasted_minutes)
        np.testing.assert_array_equal(a.final_prewarm, b.final_prewarm)
        np.testing.assert_array_equal(a.final_keep_alive, b.final_keep_alive)


def test_arima_postpass_override_consistency():
    """With ARIMA enabled, OOB-heavy apps are re-simulated through the
    scalar policy; the batched result (cold, waste, windows) must equal the
    scalar oracle's for every app."""
    trace = coarse_twoweek(n_apps=16, seed=13)
    cfg = HybridConfig(histogram=CFG48.histogram, use_arima=True)
    oracle = simulate_scalar(trace, HybridHistogramPolicy(cfg))
    got = _run(trace, cfg, "fused")
    np.testing.assert_array_equal(got.cold, oracle.cold)
    np.testing.assert_array_equal(got.final_prewarm, oracle.final_prewarm)
    np.testing.assert_array_equal(got.final_keep_alive,
                                  oracle.final_keep_alive)
    np.testing.assert_allclose(got.wasted_minutes, oracle.wasted_minutes,
                               rtol=1e-9)


def test_subms_trace_actually_needs_rebasing():
    """Sanity check on the showcase trace: its absolute timestamps do NOT
    round-trip through float32 (the sub-ms structure is lost), while the
    per-app rebased timestamps do — so a float32 engine must never see the
    absolute clock (the Pallas kernel gets float64-differenced gaps)."""
    trace = bursty_subms_multiweek()
    broken = exact = 0
    for t in trace.times:
        t = np.asarray(t)
        if not np.array_equal(t.astype(np.float32).astype(np.float64), t):
            broken += 1
        reb = t - t[0]
        if np.array_equal(reb.astype(np.float32).astype(np.float64), reb):
            exact += 1
    assert broken > 0, "trace no longer exercises float32-unrepresentable times"
    assert exact == trace.n_apps


def _float32_timers():
    """Timers whose clock is stored in float32, like a materialized
    ``WorkloadSpec``: periods at and inside the 240-minute range, phases
    with fine float32 bits, so gaps sit an ulp off whole minutes."""
    rng = np.random.default_rng(5)
    times = []
    for period in (240.0, 180.0, 162.0, 45.0):
        for phase in rng.uniform(0.0, 300.0, 6):
            t = np.float32(phase + period * np.arange(40))
            times.append(t.astype(np.float64))
    return Trace(specs=None, times=times,
                 duration_minutes=float(max(t[-1] for t in times)) + 60.0)


def test_pallas_bins_gaps_on_the_float64_clock():
    """Rebased float32 times round some of these gaps across a bin edge
    (or the range edge); the Pallas kernel classifies every gap from the
    float64 clock and compares a float32 bracket of it, so its cold counts
    and windows are the oracle's exactly."""
    trace = _float32_timers()
    crossed = 0
    for t in trace.times:
        exact = np.floor(np.diff(t))
        rebased = (t - t[0]).astype(np.float32).astype(np.float64)
        crossed += int(np.sum(np.floor(np.diff(rebased)) != exact))
    assert crossed > 0, "no gap crosses a bin edge under float32 rebasing"
    spec = HybridSpec(use_arima=False)
    oracle = simulate_scalar(trace, spec.build())
    got = run(trace, spec, engine="pallas",
              options=EngineOptions(app_chunk=8))
    np.testing.assert_array_equal(got.cold, oracle.cold)
    np.testing.assert_array_equal(got.final_prewarm, oracle.final_prewarm)
    np.testing.assert_array_equal(got.final_keep_alive,
                                  oracle.final_keep_alive)
    np.testing.assert_allclose(got.wasted_minutes, oracle.wasted_minutes,
                               rtol=1e-5, atol=1e-3)


def test_cv_tie_decided_alike_by_every_engine():
    """48 gaps in 48 distinct one-minute bins give a bin-count CV of
    exactly 2.0 over 240 bins — the default threshold. A gate that divides
    rounds that tie differently on different backends; the product gate
    passes it everywhere, so the next gap (100.5 minutes, past the
    histogram's keep-alive) is cold in every engine."""
    t = np.concatenate([[0.0], np.cumsum(np.arange(48) + 0.5)])
    t = np.concatenate([t, t[-1] + [100.5, 101.0]])
    trace = Trace(specs=None, times=[t, t + 7.25],
                  duration_minutes=float(t[-1]) + 30.0)
    spec = HybridSpec(use_arima=False)
    oracle = simulate_scalar(trace, spec.build())
    # the first event and the gap after the tie (histogram windows)
    assert oracle.cold.tolist() == [2, 2]
    for engine, opts in (("fused", {}), ("pallas", {"app_chunk": 8})):
        got = run(trace, spec, engine=engine, options=EngineOptions(**opts))
        np.testing.assert_array_equal(got.cold, oracle.cold, err_msg=engine)
        np.testing.assert_array_equal(got.final_prewarm,
                                      oracle.final_prewarm, err_msg=engine)
        np.testing.assert_array_equal(got.final_keep_alive,
                                      oracle.final_keep_alive, err_msg=engine)
