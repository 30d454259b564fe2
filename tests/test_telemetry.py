"""Spans and counters of one ``sweep()`` call (``repro.telemetry``).

Under ``jax.profiler`` each call writes a ``sweep`` span on the calling
thread's host line, with every phase of the hybrid driver as a leaf span
inside it; with or without a profiler the driver counts its scans and the
steps they take, real and padded. Neither changes a result.
"""
import glob
import os

import numpy as np
import pytest

import jax

from repro import telemetry
from repro.core.experiment import EngineOptions, HybridSpec, sweep
from repro.core.histogram import HistogramConfig
from repro.core.policy import HybridConfig
from repro.core.simulator import (_build_pallas_cfg, _gap_columns,
                                  _hybrid_sweep_scan_pallas, _scan_extent)
from repro.core.workload import Trace

DURATION = 1440.0
# Apps' event counts: one app without events, four in the <=64 bucket
# (width 5) and two in the <=512 bucket (width 70).
COUNTS = np.array([0, 2, 5, 3, 1, 66, 70])
# Two bands: 48 bins (two configs) and 64 bins (one config).
SPECS = [HybridSpec(range_minutes=48.0, use_arima=False),
         HybridSpec(range_minutes=48.0, cv_threshold=0.5, use_arima=False),
         HybridSpec(range_minutes=64.0, use_arima=False)]
# Three apps a chunk: the <=64 bucket's apps go in chunks of 3 and 1, the
# <=512 bucket's two in one; a kernel tile of 2 apps pads the chunk of 3.
OPTS = EngineOptions(app_chunk=3, tile_apps=2)
LEAVES = {"sweep.pad", "sweep.bucket", "sweep.gap_columns", "sweep.put",
          "sweep.dispatch", "sweep.pull", "sweep.scatter", "sweep.fill"}


def _trace():
    rng = np.random.default_rng(7)
    times = np.full((len(COUNTS), COUNTS.max()), np.inf)
    for i, c in enumerate(COUNTS):
        times[i, :c] = np.sort(rng.uniform(0.0, DURATION, c))
    return Trace(specs=None, times=None, duration_minutes=DURATION,
                 _padded=(times, COUNTS))


def _fields(res):
    return (res.cold, res.invocations, res.wasted_minutes,
            res.final_prewarm, res.final_keep_alive)


def _profiled(tmp_path, fn):
    """``fn()`` under the profiler; its result and the host lines' events
    as {line: [(name, start_ns, end_ns), ...]}."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            lines[k] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    return out, lines


@pytest.mark.parametrize("engine", ["pallas", "fused"])
def test_sweep_spans_nest_on_one_thread_and_change_no_result(tmp_path,
                                                             engine):
    trace = _trace()
    off = sweep(trace, SPECS, engine=engine, options=OPTS)
    on, lines = _profiled(tmp_path, lambda: sweep(trace, SPECS,
                                                  engine=engine,
                                                  options=OPTS))
    for a, b in zip(_fields(off), _fields(on)):
        np.testing.assert_array_equal(a, b)

    held = [ev for ev in lines.values()
            if any(e[0] == "sweep" for e in ev)]
    assert len(held) == 1, "the sweep span is on one thread's line"
    events = held[0]
    calls = [e for e in events if e[0] == "sweep"]
    assert len(calls) == 1
    _, lo, hi = calls[0]
    leaves = [e for e in events if e[0].startswith("sweep.")]
    expected = LEAVES - ({"sweep.gap_columns"} if engine == "fused"
                         else set())
    assert {e[0] for e in leaves} == expected
    for name, s, e in leaves:
        assert lo <= s <= e <= hi, f"{name} outside its sweep span"
    # one bucket span a chunk and one for the exhausted generator
    assert sum(e[0] == "sweep.bucket" for e in leaves) == 4
    # every chunk's scan (3 chunks x 2 bands) is dispatched, pulled and
    # scattered once
    for name in ("sweep.dispatch", "sweep.pull", "sweep.scatter"):
        assert sum(e[0] == name for e in leaves) == 6


@pytest.mark.parametrize("engine,app_steps", [
    # rows x width per band: chunk of 3 apps padded to 4 by the 2-app
    # tile, x 5 steps; chunk of 1 app x 5; chunk of 2 x 70
    ("pallas", 3 * (4 * 5 + 1 * 5 + 2 * 70)),
    # the jnp scan steps every row it is given, and no padding
    ("fused", 3 * (3 * 5 + 1 * 5 + 2 * 70)),
])
def test_sweep_counters_equal_a_hand_count(engine, app_steps):
    before = telemetry.counters()
    sweep(_trace(), SPECS, engine=engine, options=OPTS)
    after = telemetry.counters()
    got = {k: after[k] - before.get(k, 0) for k in after
           if k.startswith("sweep.")}
    assert got == {
        "sweep.event_steps": 3 * int(COUNTS.sum()),  # configs x events
        "sweep.app_steps": app_steps,
    }


def test_app_steps_count_the_time_block_padding(monkeypatch):
    """With no VMEM to plan with, the kernel walks the 70-event bucket in
    9 blocks of 8 steps: 72 steps, 2 of them padding."""
    from repro.kernels import histogram
    monkeypatch.setattr(histogram, "_SCAN_VMEM_BYTES", 0)
    assert histogram.scan_blocks(2, 70, 48, 2) == (2, 8)
    before = telemetry.counters()
    sweep(_trace(), SPECS, engine="pallas", options=OPTS)
    after = telemetry.counters()
    # the <=64 bucket's 5 steps fit one block; the <=512 bucket's 70 do not
    assert after["sweep.app_steps"] - before.get("sweep.app_steps", 0) == \
        3 * (4 * 5 + 1 * 5 + 2 * 72)


def test_counters_are_a_copy():
    telemetry.count("test.copy", 2)
    seen = telemetry.counters()
    seen["test.copy"] = 0
    assert telemetry.counters()["test.copy"] >= 2


@pytest.mark.parametrize("n,ndev,tile,rows", [
    (2507, 1, 512, 2560),      # padded to the 512-app tile
    (2030, 1, 512, 2048),
    (37, 1, 512, 37),          # below one tile: the chunk is the tile
    (1024, 1, 512, 1024),
    (3, 1, 0, 3),              # the jnp scan: no tile
    (10, 4, 0, 12),            # the mesh pads rows to its devices
    (10, 4, 2, 16),            # 3 rows a device, padded to 4 by the tile
    (1000, 4, 128, 1024),      # 250 a device, padded to 256
    (2560, 1, 1024, 3072),     # packed tiles of whole vregs
])
def test_scan_rows_follow_the_mesh_and_kernel_padding(n, ndev, tile, rows):
    assert _scan_extent(n, ndev, 6, 60, tile) == (rows, 6)


def _kernel_state_rows(jaxpr):
    """The app rows of the state the first ``pallas_call`` found in
    ``jaxpr`` carries (its first output, ``cum``)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            # [S, n_bins, rows], or [S, n_bins, rows / 128, 128] packed
            return int(np.prod(eqn.outvars[0].aval.shape[2:]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            rows = _kernel_state_rows(sub)
            if rows is not None:
                return rows
    return None


@pytest.mark.parametrize("n_bins", [60, 240])
@pytest.mark.parametrize("n,tile", [(3, 2), (5, 4), (4, 4), (3, 8),
                                    (1100, 1024)])
def test_scan_rows_equal_the_rows_the_pallas_scan_carries(n, tile, n_bins):
    ci, cf = _build_pallas_cfg([HybridConfig(
        histogram=HistogramConfig(range_minutes=float(n_bins)),
        use_arima=False)])
    times = np.sort(np.random.default_rng(n).uniform(0.0, DURATION, (n, 6)))
    gaps, _ = _gap_columns(times, 1.0, n_bins)
    jaxpr = jax.make_jaxpr(_hybrid_sweep_scan_pallas,
                           static_argnums=(3, 4, 5))(
        gaps, ci, cf, n_bins, True, tile)
    assert _kernel_state_rows(jaxpr.jaxpr) == _scan_extent(
        n, 1, 6, n_bins, tile)[0]
