"""Parity suite: the Pallas sweep scan kernel vs the scalar reference.

The kernel (``repro.kernels.histogram.hybrid_sweep_scan_pallas``), fed the
event columns the sweep driver builds (``simulator._gap_columns``: every
gap differenced and binned on the host in float64, its idle time sent as a
float32 bracket), must reproduce, per event, exactly what the control-plane
scalar path (AppHistogram + HybridHistogramPolicy decision tree) computes:
histogram contents, OOB counters, and the (prewarm, keep-alive) windows.

One kernel call covers a whole stream: lane i holds the stream's first
i + 1 events, so its final state is the state after event i. Every case
runs in both tile layouts of ``histogram.scan_blocks``: a lane-only tile
(a chunk of fewer than 1,024 apps) and the packed 1,024-app tile of every
chip chunk (the lanes padded to 1,024 with event-free rows). Property tests
run when hypothesis is installed (see requirements-dev.txt); the seeded
stream tests below always run.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.histogram import AppHistogram, HistogramConfig
from repro.core.policy import HybridConfig, HybridHistogramPolicy
from repro.core.simulator import _build_pallas_cfg, _gap_columns
from repro.kernels.histogram import hybrid_sweep_scan_pallas, scan_blocks

from test_sweep_scan_kernel import _init

CFG = HistogramConfig(range_minutes=48.0)   # 48 bins: fast in interpret mode
HYB = HybridConfig(histogram=CFG, use_arima=False)
MAX_EVENTS = 64         # the lanes of the lane-only layout: the longest stream
TILE_APPS = 1024        # the driver's app tile
LAYOUTS = {"lanes": MAX_EVENTS, "packed": TILE_APPS}   # layout -> lanes
CFG_I32, CFG_F32 = _build_pallas_cfg([HYB])

_scan = jax.jit(functools.partial(hybrid_sweep_scan_pallas,
                                  tile_apps=TILE_APPS, interpret=True))


def _kernel_stream(its, layout):
    """Drive the scan kernel over every prefix of one stream at once.

    Returns (prewarm, keep, total, oob, counts) after every event.
    """
    n_ev, n = len(its), LAYOUTS[layout]
    assert n_ev <= MAX_EVENTS
    ta, _ = scan_blocks(n, n_ev, CFG.n_bins, TILE_APPS)
    assert (ta % 1024 == 0) == (layout == "packed")
    clock = np.cumsum(np.asarray(its, np.float64))
    times = np.full((n, n_ev), np.inf)
    lane, event = np.tril_indices(n_ev)
    times[lane, event] = clock[event]
    gaps, _ = _gap_columns(times, CFG.bin_minutes, CFG.n_bins)
    it_lo, it_hi, code = (jnp.asarray(g.T) for g in gaps)
    (cum, oob, _, _, prewarm, unload_at, cold, waste) = (
        np.asarray(x[0]) for x in _scan(
            it_lo, it_hi, code, _init(1, CFG.n_bins, n, CFG_F32), CFG_I32,
            CFG_F32))
    prewarm, unload_at = prewarm[0], unload_at[0]
    oob, cold, waste = oob[0], cold[0], waste[0]
    # event-free lanes keep the initial state
    np.testing.assert_array_equal(cum[:, n_ev:], 0)
    for x in (oob, prewarm, cold, waste):
        np.testing.assert_array_equal(x[n_ev:], 0)
    np.testing.assert_array_equal(unload_at[n_ev:], HYB.standard_keep_alive)
    counts = np.diff(np.concatenate(
        [np.zeros((1, n), np.int64), cum.astype(np.int64)]), axis=0)
    # the state holds residency bounds; keep-alive is their exact float64
    # difference (same reconstruction the drivers use)
    return [(float(prewarm[i]), float(unload_at[i]) - float(prewarm[i]),
             int(cum[-1, i]), int(oob[i]), counts[:, i])
            for i in range(n_ev)]


def _scalar_stream(its):
    """Same stream through the scalar control-plane reference."""
    policy = HybridHistogramPolicy(HYB)
    hist = AppHistogram(CFG)
    out = []
    for k, it in enumerate(its):
        w = policy.on_invocation("a", None if k == 0 else float(its[k]))
        if k > 0:
            hist.record(float(its[k]))
        out.append((w.prewarm, w.keep_alive, hist.total, hist.oob,
                    hist.counts.copy()))
    return out


def _check_stream(its, layout):
    """its[0] is the first arrival (not recorded); its[1:] are idle times.

    Times are kept on a dyadic grid so the float64 clock differences back
    to exactly the idle times the scalar reference is given.
    """
    got = _kernel_stream(its, layout)
    want = _scalar_stream(its)
    for k, ((gp, gk, gt, go, gc), (wp, wk, wt, wo, wc)) in enumerate(
            zip(got, want)):
        assert gt == wt, f"event {k}: total {gt} != {wt}"
        assert go == wo, f"event {k}: oob {go} != {wo}"
        np.testing.assert_array_equal(gc, wc, err_msg=f"event {k}")
        # single-source float32 decision layer: windows match bit-for-bit
        assert gp == wp, f"event {k}: prewarm {gp} != {wp}"
        assert gk == wk, f"event {k}: keep {gk} != {wk}"
    return got


def _quantize(vals):
    # 1/64-minute grid: exact float32 arithmetic for cumulative times < 2^17
    return [max(round(v * 64.0) / 64.0, 0.0) for v in vals]


layouts = pytest.mark.parametrize("layout", sorted(LAYOUTS))


# --- seeded streams (always run) --------------------------------------------

@layouts
def test_fused_kernel_parity_in_bounds_stream(layout):
    rng = np.random.default_rng(0)
    its = _quantize(rng.uniform(0.5, 40.0, 60))
    _check_stream(its, layout)


@layouts
def test_fused_kernel_parity_oob_heavy_stream(layout):
    """Most idle times beyond the histogram range: the representativeness
    check must veto the histogram windows on both paths."""
    rng = np.random.default_rng(1)
    its = _quantize(rng.uniform(CFG.range_minutes + 1.0,
                                3.0 * CFG.range_minutes, 40))
    its[5] = 3.0   # a couple in-bounds so total > 0
    its[11] = 7.0
    _check_stream(its, layout)


@layouts
def test_fused_kernel_parity_sub_min_samples(layout):
    its = _quantize([4.0, 4.0, 4.0])   # fewer than min_samples ITs
    got = _check_stream(its, layout)
    # standard keep-alive must be in force after so few samples
    assert got[-1][0] == 0.0
    assert got[-1][1] == HYB.standard_keep_alive


@layouts
def test_fused_kernel_parity_bimodal_prewarm_stream(layout):
    """Concentrated bimodal ITs push CV over threshold: histogram windows
    (prewarm > 0) activate and must match the scalar decision."""
    its = _quantize([10.0 if i % 2 else 30.0 for i in range(50)])
    got = _check_stream(its, layout)
    assert got[-1][0] > 0.0   # pre-warming active


@layouts
def test_fused_kernel_parity_mixed_random_streams(layout):
    for seed in range(3, 7):
        rng = np.random.default_rng(seed)
        its = _quantize(np.abs(rng.normal(0.0, CFG.range_minutes, 30)))
        _check_stream(its, layout)


# --- hypothesis property tests (absent hypothesis, only these skip; the
# seeded streams above still run) ---------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:      # pragma: no cover - depends on dev environment
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    its_strategy = st.lists(
        st.floats(min_value=0.0, max_value=3.0 * CFG.range_minutes,
                  allow_nan=False),
        min_size=1, max_size=40)

    @layouts
    @settings(max_examples=25, deadline=None)
    @given(values=its_strategy)
    def test_fused_kernel_parity_property(layout, values):
        _check_stream(_quantize(values), layout)

    @layouts
    @settings(max_examples=10, deadline=None)
    @given(values=st.lists(st.integers(0, 3 * int(CFG.range_minutes)),
                           min_size=1, max_size=40))
    def test_fused_kernel_parity_property_integer(layout, values):
        _check_stream([float(v) for v in values], layout)
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev.txt)")
    def test_fused_kernel_parity_property():
        pass
