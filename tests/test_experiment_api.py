"""The experiment front door: PolicySpec pytrees, run()/sweep() parity on
both axes (policy grid AND trace axis), and the removed-shim contract.

The load-bearing guarantee: every row of a ``sweep()`` is bit-identical
(cold counts, invocations, final windows; waste too, engine-for-engine) to
the corresponding single-config ``run()`` on EVERY engine, including the
golden traces — stacking configurations into a traced config axis must
change nothing but wall-clock. The same holds along the trace axis:
``sweep(traces=[...], specs=[...])`` cells equal the single-trace calls.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax.tree_util as tree_util

from repro.core.experiment import (ENGINES, EngineOptions, FixedSpec,
                                   HybridSpec, NoUnloadSpec, as_spec,
                                   as_trace, run, sweep)
from repro.core.histogram import HistogramConfig
from repro.core.policy import (FixedKeepAlivePolicy, HybridConfig,
                               HybridHistogramPolicy, NoUnloadingPolicy)
from repro.core.simulator import simulate_scalar
from repro.core.workload import Trace
from repro.core.workload_spec import WorkloadSpec, azure_like, bursty

from golden_traces import CFG48, GOLDEN_TRACES, coarse_twoweek

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden")

# A deliberately mixed grid: two families, two histogram bands, and
# window/gate variants that exercise the factored sweep layers.
GRID = [
    FixedSpec(10.0),
    NoUnloadSpec(),
    HybridSpec.from_config(CFG48),
    HybridSpec(range_minutes=48.0, cv_threshold=0.5, use_arima=False),
    HybridSpec(range_minutes=48.0, head_percentile=0.0,
               tail_percentile=100.0, use_arima=False),
    HybridSpec(range_minutes=64.0, use_arima=False),
    FixedSpec(48.0),
]

OPTS = EngineOptions(app_chunk=11)   # ragged chunks on purpose

# (engine, options): every engine at OPTS, and the Pallas engine at the
# default options too (each bucket one chunk, as in every chip cell).
ENGINE_CASES = [pytest.param(e, OPTS, id=e) for e in sorted(ENGINES)] + [
    pytest.param("pallas", EngineOptions(), id="pallas-one-chunk")]


@pytest.fixture(scope="module")
def trace():
    return coarse_twoweek()


@pytest.mark.parametrize("engine,opts", ENGINE_CASES)
def test_sweep_rows_equal_single_config_runs(trace, engine, opts):
    """sweep() row s == run(spec_s) bit-for-bit, per engine — including
    float32 waste, which accumulates in the same order either way."""
    res = sweep(trace, GRID, engine=engine, options=opts)
    assert len(res) == len(GRID)
    for s, spec in enumerate(GRID):
        one = run(trace, spec, engine=engine, options=opts)
        err = f"engine={engine} row={s} ({spec.name})"
        np.testing.assert_array_equal(res.cold[s], one.cold, err_msg=err)
        np.testing.assert_array_equal(res.invocations, one.invocations,
                                      err_msg=err)
        np.testing.assert_array_equal(res.wasted_minutes[s],
                                      one.wasted_minutes, err_msg=err)
        np.testing.assert_array_equal(res.final_prewarm[s],
                                      one.final_prewarm, err_msg=err)
        np.testing.assert_array_equal(res.final_keep_alive[s],
                                      one.final_keep_alive, err_msg=err)


@pytest.mark.parametrize("engine,opts", [
    pytest.param("fused", OPTS, id="fused"),
    pytest.param("pallas", OPTS, id="pallas"),
    pytest.param("pallas", EngineOptions(), id="pallas-one-chunk")])
def test_sweep_matches_scalar_oracle(trace, engine, opts):
    """Every sweep row reproduces the float64 scalar oracle exactly on the
    decision-layer outputs (cold counts, windows)."""
    res = sweep(trace, GRID, engine=engine, options=opts)
    for s, spec in enumerate(GRID):
        oracle = simulate_scalar(trace, spec.build())
        err = f"engine={engine} row={s} ({spec.name})"
        np.testing.assert_array_equal(res.cold[s], oracle.cold, err_msg=err)
        np.testing.assert_array_equal(res.final_prewarm[s],
                                      oracle.final_prewarm, err_msg=err)
        np.testing.assert_array_equal(res.final_keep_alive[s],
                                      oracle.final_keep_alive, err_msg=err)
        np.testing.assert_allclose(res.wasted_minutes[s],
                                   oracle.wasted_minutes, rtol=1e-5,
                                   atol=1e-3, err_msg=err)


@pytest.mark.parametrize("name", sorted(GOLDEN_TRACES))
def test_sweep_matches_golden_fixtures(name):
    """sweep() over the pinned golden traces reproduces the checked-in
    float64 oracle records row-for-row."""
    make_trace, cfg = GOLDEN_TRACES[name]
    with open(os.path.join(GOLDEN_DIR, f"{name}.json")) as f:
        want = json.load(f)
    t = make_trace()
    # the golden config twice (both rows must match the fixture) plus a
    # decoy variant in between — row order must be preserved
    spec = HybridSpec.from_config(cfg)
    decoy = dataclasses.replace(spec, cv_threshold=spec.cv_threshold + 1.0)
    res = sweep(t, [spec, decoy, spec], engine="fused")
    for s in (0, 2):
        np.testing.assert_array_equal(res.cold[s], np.asarray(want["cold"]))
        np.testing.assert_array_equal(res.final_prewarm[s],
                                      np.asarray(want["final_prewarm"]))
        np.testing.assert_array_equal(res.final_keep_alive[s],
                                      np.asarray(want["final_keep_alive"]))
        np.testing.assert_allclose(res.wasted_minutes[s],
                                   np.asarray(want["wasted_minutes"]),
                                   rtol=0, atol=0)


def test_arima_sweep_rows_match_runs():
    """use_arima specs trigger the per-config scalar post-pass; rows must
    still equal single-config runs and the oracle. Small trace: the ARIMA
    refits per invocation, and this runs the scalar path six times."""
    trace = coarse_twoweek(n_apps=4, seed=13)
    specs = [HybridSpec.from_config(CFG48),
             dataclasses.replace(HybridSpec.from_config(CFG48),
                                 use_arima=True)]
    res = sweep(trace, specs, engine="fused")
    for s, spec in enumerate(specs):
        one = run(trace, spec, engine="fused")
        oracle = simulate_scalar(trace, spec.build())
        np.testing.assert_array_equal(res.cold[s], one.cold)
        np.testing.assert_array_equal(res.cold[s], oracle.cold)
        np.testing.assert_array_equal(res.final_keep_alive[s],
                                      oracle.final_keep_alive)


def test_sweep_points_and_iteration(trace):
    res = sweep(trace, [FixedSpec(10.0), HybridSpec.from_config(CFG48)])
    pts = res.points()
    assert [p.name for p in pts] == ["fixed-10m", "hybrid-48m"]
    rows = list(res)
    assert len(rows) == 2
    assert pts[0].wasted_memory == rows[0].total_wasted


def test_sweep_rejects_bad_inputs(trace):
    with pytest.raises(ValueError, match="at least one"):
        sweep(trace, [])
    with pytest.raises(ValueError, match="unknown engine"):
        sweep(trace, [FixedSpec(10.0)], engine="warp")
    for gone in ("reference",):      # engines removed from ENGINES
        with pytest.raises(ValueError, match="unknown engine"):
            run(trace, HybridSpec.from_config(CFG48), engine=gone)
    with pytest.raises(TypeError, match="PolicySpec"):
        as_spec(object())
    with pytest.raises(TypeError, match="Trace or WorkloadSpec"):
        as_trace(object())
    with pytest.raises(TypeError, match="exactly one"):
        sweep(trace, [FixedSpec(10.0)], traces=[trace])
    with pytest.raises(TypeError, match="exactly one"):
        sweep(specs=[FixedSpec(10.0)])
    with pytest.raises(ValueError, match="at least one trace"):
        sweep(traces=[], specs=[FixedSpec(10.0)])


# --- the trace axis: sweep(traces=[...], specs=[...]) ------------------------


@pytest.mark.parametrize("engine,opts", ENGINE_CASES)
def test_trace_axis_cells_equal_single_trace_runs(trace, engine, opts):
    """Every (t, s) cell of a trace x policy grid is bit-identical to the
    corresponding single-trace run() — the acceptance bar for the axis."""
    spec_b = bursty(24, days=3.0, seed=5, max_events=24, min_events=1)
    traces = [trace, spec_b]
    grid = sweep(traces=traces, specs=GRID, engine=engine, options=opts)
    assert grid.shape == (2, len(GRID))
    assert len(list(iter(grid))) == 2
    materialized = [trace, spec_b.materialize()]
    for t, tr in enumerate(materialized):
        for s, spec in enumerate(GRID):
            one = run(tr, spec, engine=engine, options=opts)
            err = f"engine={engine} t={t} s={s} ({spec.name})"
            cell = grid.row(t, s)
            np.testing.assert_array_equal(cell.cold, one.cold, err_msg=err)
            np.testing.assert_array_equal(cell.invocations, one.invocations,
                                          err_msg=err)
            np.testing.assert_array_equal(cell.wasted_minutes,
                                          one.wasted_minutes, err_msg=err)
            np.testing.assert_array_equal(cell.final_prewarm,
                                          one.final_prewarm, err_msg=err)
            np.testing.assert_array_equal(cell.final_keep_alive,
                                          one.final_keep_alive, err_msg=err)


def test_workload_specs_accepted_everywhere(trace):
    """run()/sweep() take WorkloadSpec wherever they take Trace, and the
    spec materializes deterministically to the same trace each time."""
    wspec = azure_like(40, days=2.0, seed=3, max_events=16)
    via_spec = run(wspec, FixedSpec(10.0))
    via_trace = run(wspec.materialize(), FixedSpec(10.0))
    np.testing.assert_array_equal(via_spec.cold, via_trace.cold)
    np.testing.assert_array_equal(via_spec.wasted_minutes,
                                  via_trace.wasted_minutes)
    grid = sweep(traces=[wspec, trace], specs=[FixedSpec(10.0)])
    assert grid.trace_name(0) == wspec.name
    assert grid.trace_name(1) == "trace-1"
    np.testing.assert_array_equal(grid.row(0, 0).cold, via_spec.cold)


# --- removed deprecation shims ----------------------------------------------


def test_removed_shims_raise_with_pointer():
    import repro.core
    import repro.core.simulator as sim
    for name in ("simulate", "simulate_fixed_batch", "simulate_hybrid_batch",
                 "simulate_hybrid_batch_reference"):
        for mod in (sim, repro.core):
            with pytest.raises(AttributeError,
                               match="repro.core.experiment"):
                getattr(mod, name)
    with pytest.raises(AttributeError, match="no attribute"):
        sim.definitely_not_a_thing


# --- PolicySpec pytree + build() properties ----------------------------------


def test_specs_roundtrip_and_build_match_legacy():
    spec = HybridSpec(range_minutes=60.0, cv_threshold=1.5, use_arima=True,
                      label="x")
    leaves, treedef = tree_util.tree_flatten(spec)
    assert tree_util.tree_unflatten(treedef, leaves) == spec
    cfg = spec.to_config()
    assert HybridSpec.from_config(cfg, label="x") == spec
    assert spec.build().cfg == cfg

    fx = FixedSpec(25.0)
    leaves, treedef = tree_util.tree_flatten(fx)
    assert tree_util.tree_unflatten(treedef, leaves) == fx
    assert fx.build().keep_alive == 25.0
    assert isinstance(NoUnloadSpec().build(), NoUnloadingPolicy)

    # as_spec round-trips the legacy objects
    assert as_spec(FixedKeepAlivePolicy(30.0)) == FixedSpec(30.0)
    assert as_spec(NoUnloadingPolicy()) == NoUnloadSpec()
    assert as_spec(cfg) == HybridSpec.from_config(cfg)
    assert as_spec(HybridHistogramPolicy(cfg)) == HybridSpec.from_config(cfg)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                 # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    hybrid_specs = st.builds(
        HybridSpec,
        bin_minutes=st.sampled_from([0.5, 1.0, 2.0]),
        range_minutes=st.sampled_from([24.0, 48.0, 240.0, 480.0]),
        head_percentile=st.sampled_from([0.0, 5.0, 10.0]),
        tail_percentile=st.sampled_from([95.0, 99.0, 100.0]),
        margin=st.floats(0.0, 0.5),
        cv_threshold=st.floats(0.0, 8.0),
        min_samples=st.integers(1, 20),
        oob_fraction_threshold=st.floats(0.05, 0.95),
        use_arima=st.booleans())

    @settings(max_examples=50, deadline=None)
    @given(spec=hybrid_specs)
    def test_hybrid_spec_pytree_roundtrip(spec):
        leaves, treedef = tree_util.tree_flatten(spec)
        assert all(np.isscalar(x) for x in leaves)
        assert tree_util.tree_unflatten(treedef, leaves) == spec

    @settings(max_examples=50, deadline=None)
    @given(spec=hybrid_specs)
    def test_hybrid_spec_build_matches_legacy_constructor(spec):
        cfg = spec.build().cfg
        want = HybridConfig(
            histogram=HistogramConfig(
                bin_minutes=spec.bin_minutes,
                range_minutes=spec.range_minutes,
                head_percentile=spec.head_percentile,
                tail_percentile=spec.tail_percentile,
                margin=spec.margin),
            cv_threshold=spec.cv_threshold, min_samples=spec.min_samples,
            oob_fraction_threshold=spec.oob_fraction_threshold,
            arima_min_samples=spec.arima_min_samples,
            arima_margin=spec.arima_margin, use_arima=spec.use_arima)
        assert cfg == want
        assert HybridSpec.from_config(cfg) == spec

    @settings(max_examples=25, deadline=None)
    @given(keep=st.floats(0.5, 480.0))
    def test_fixed_spec_roundtrip_and_build(keep):
        spec = FixedSpec(keep)
        leaves, treedef = tree_util.tree_flatten(spec)
        assert tree_util.tree_unflatten(treedef, leaves) == spec
        assert spec.build().keep_alive == keep
        assert as_spec(spec.build()) == spec
