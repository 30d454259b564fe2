"""The Pallas sweep scan kernel (``kernels.histogram.hybrid_sweep_scan_pallas``).

The kernel walks a chunk's event columns itself, one block of time steps
per grid step, with each (config, app tile)'s state held across the time
axis. Its final state must equal, bit for bit, a ``lax.scan`` of the
single-source step (``policy_math.hybrid_gap_step_math`` with
``gather=False, bin_axis=0``) over the same columns, kept below as the
oracle: same steps, same order, same float32 sums. Interpret mode, small
shapes; a tiny VMEM plan forces several time blocks and a padded last one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import policy_math
from repro.core.experiment import EngineOptions, HybridSpec, sweep
from repro.core.histogram import HistogramConfig
from repro.core.policy import HybridConfig
from repro.core.simulator import _build_pallas_cfg, _gap_columns
from repro.core.workload import Trace
from repro.kernels import histogram

BIN_MINUTES = 1.0


def _configs(S, n_bins):
    """S configs of one band (same bins), every knob of the step moved."""
    knobs = [(5.0, 99.0, 0.10, 2.0, 5), (0.0, 100.0, 0.20, 0.5, 3),
             (15.0, 90.0, 0.05, 4.0, 8)]
    return [HybridConfig(
        histogram=HistogramConfig(bin_minutes=BIN_MINUTES,
                                  range_minutes=n_bins * BIN_MINUTES,
                                  head_percentile=head, tail_percentile=tail,
                                  margin=margin),
        cv_threshold=cv, min_samples=min_samples, use_arima=False)
        for head, tail, margin, cv, min_samples in knobs[:S]]


def _times(n_apps, width, n_bins, seed):
    """Sorted float64 rows padded with +inf: an app without events, one
    with a single (first) event, one with ``width``, the rest random
    counts; gaps in the first bins, on bin edges, out of bounds and
    anywhere in between."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, width + 1, n_apps)
    counts[:3] = (0, 1, width)
    edges = BIN_MINUTES * np.array([1.0, 2.0, n_bins - 1.0, n_bins,
                                    3.0 * n_bins])
    gaps = np.where(rng.random((n_apps, width)) < 0.5,
                    rng.choice(edges, (n_apps, width)),
                    rng.uniform(0.0, 1.5 * n_bins * BIN_MINUTES,
                                (n_apps, width)))
    times = 1.0e4 + np.cumsum(gaps, axis=1)
    times[np.arange(width)[None, :] >= counts[:, None]] = np.inf
    return times


def _init(S, n_bins, n, cfg_f32):
    vec = lambda dt: jnp.zeros((S, 1, n), dt)
    return (jnp.zeros((S, n_bins, n), jnp.int32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.float32), vec(jnp.float32),
            jnp.broadcast_to(jnp.asarray(cfg_f32)[:, 6:7, None], (S, 1, n)),
            vec(jnp.int32), vec(jnp.float32))


@jax.jit
def _oracle_one(it_lo, it_hi, code, state, ci, cf):
    """``lax.scan`` of the single-source step for one config: columns
    [width, n], ``cum`` [n_bins, n] and [1, n] vectors, knobs traced as
    the kernel reads them from SMEM."""
    cfg = policy_math.HybridStepConfig(
        n_bins=ci[0], head_numer=ci[1], tail_numer=ci[2], min_samples=ci[3],
        margin_lo=cf[0], margin_hi=cf[1], bin_minutes=cf[2], bin_f32=cf[2],
        range_f32=cf[3], cv_factor=cf[4], oob_threshold=cf[5],
        standard_keep=cf[6])

    def step(carry, column):
        lo, hi, c = (x[None] for x in column)
        return policy_math.hybrid_gap_step_math(
            lo, hi, policy_math.decode_gaps(c, cfg.n_bins), *carry, cfg=cfg,
            gather=False, bin_axis=0), None

    final, _ = jax.lax.scan(step, state, (it_lo, it_hi, code))
    return final


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("S,n_bins,n_apps,width,tile,vmem", [
    pytest.param(1, 60, 37, 1, 512, None, id="one-config-width-1"),
    pytest.param(3, 60, 37, 7, 512, None, id="three-configs-width-7"),
    pytest.param(1, 240, 37, 1, 512, None, id="240-bins-width-1"),
    pytest.param(3, 240, 37, 7, 16, None, id="240-bins-ragged-tiles"),
    # no VMEM to plan with: blocks of 8 steps, the last one padded
    pytest.param(1, 60, 37, 21, 512, 0, id="time-blocks"),
    pytest.param(3, 240, 37, 21, 16, 0, id="240-bins-time-blocks-tiles"),
    pytest.param(3, 60, 1100, 9, 1024, None, id="ragged-wide-tiles"),
])
def test_scan_kernel_equals_a_scan_of_the_step(monkeypatch, S, n_bins,
                                               n_apps, width, tile, vmem):
    if vmem is not None:
        monkeypatch.setattr(histogram, "_SCAN_VMEM_BYTES", vmem)
    _, tb = histogram.scan_blocks(n_apps, width, n_bins, tile)
    assert (tb == 8 and width % tb) if vmem is not None else tb == width
    ci, cf = _build_pallas_cfg(_configs(S, n_bins))
    gaps, _ = _gap_columns(_times(n_apps, width, n_bins, seed=n_apps + width),
                           BIN_MINUTES, n_bins)
    it_lo, it_hi, code = (jnp.asarray(x.T) for x in gaps)
    codes = np.asarray(code)
    assert (codes == policy_math.GAP_PAD).all(axis=0).any()   # no events
    assert (codes == policy_math.GAP_FIRST).any()
    if width > 1:
        assert (codes == n_bins).any()                         # OOB gaps

    got = histogram.hybrid_sweep_scan_pallas(
        it_lo, it_hi, code, _init(S, n_bins, n_apps, cf), ci, cf,
        tile_apps=tile, interpret=True)
    init = _init(S, n_bins, n_apps, cf)
    for s in range(S):
        want = _oracle_one(it_lo, it_hi, code,
                           tuple(x[s] for x in init), ci[s], cf[s])
        for k, (g, w) in enumerate(zip(got, want)):
            assert g[s].shape == w.shape
            np.testing.assert_array_equal(_bits(g[s]), _bits(w),
                                          err_msg=f"config {s}, state {k}")


def test_scan_blocks_fit_the_plan():
    """A chunk that fits is one block; a longer one splits into equal
    blocks of a multiple of 8 steps, at most 7 of them padding, within
    the VMEM the kernel plans for. A tile whose histograms would span
    more than the vregs the kernel allows is halved."""
    assert histogram.scan_blocks(37, 64, 60, 512) == (37, 64)
    assert histogram.scan_blocks(1000, 64, 240, 1024) == (512, 64)
    for n_bins, tile, ta_want in ((60, 512, 512), (240, 512, 512),
                                  (60, 1024, 1024), (240, 1024, 512),
                                  (60, 2048, 2048), (240, 2048, 512),
                                  (60, 4096, 2048)):
        ta, tb = histogram.scan_blocks(4096, 20160, n_bins, tile)
        blocks = -(-20160 // tb)
        assert ta == ta_want and tb % 8 == 0
        assert blocks * tb - 20160 < 8 * blocks
        assert histogram._tile_vregs(n_bins, ta) <= \
            histogram._MAX_TILE_VREGS
        assert 5 * histogram._state_bytes(n_bins, ta) + \
            2 * 3 * histogram._vmem_bytes(tb, ta) <= \
            histogram._SCAN_VMEM_BYTES


def test_sweep_on_the_scan_kernel_matches_the_fused_engine(monkeypatch):
    """``sweep()`` through the kernel (interpret mode), in time blocks of 8
    steps and ragged app tiles, against the float64 fused jnp engine."""
    monkeypatch.setattr(histogram, "_SCAN_VMEM_BYTES", 0)
    counts = np.array([0, 1, 2, 5, 3, 9, 30, 27, 70])
    times = np.full((len(counts), counts.max()), np.inf)
    rng = np.random.default_rng(3)
    for i, c in enumerate(counts):
        times[i, :c] = np.sort(rng.uniform(0.0, 1440.0, c))
    trace = Trace(specs=None, times=None, duration_minutes=1440.0,
                  _padded=(times, counts))
    specs = [HybridSpec(range_minutes=48.0, use_arima=False),
             HybridSpec(range_minutes=48.0, cv_threshold=0.5,
                        use_arima=False),
             HybridSpec(range_minutes=64.0, use_arima=False)]
    got = sweep(trace, specs, engine="pallas",
                options=EngineOptions(app_chunk=3, tile_apps=2))
    want = sweep(trace, specs, engine="fused")
    np.testing.assert_array_equal(got.cold, want.cold)
    np.testing.assert_array_equal(got.invocations, want.invocations)
    np.testing.assert_array_equal(got.final_prewarm, want.final_prewarm)
    np.testing.assert_array_equal(got.final_keep_alive,
                                  want.final_keep_alive)
    np.testing.assert_allclose(got.wasted_minutes, want.wasted_minutes,
                               rtol=1e-5, atol=1e-3)
