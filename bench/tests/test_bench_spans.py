"""The readers of the program's spans and counters (``repro.telemetry``):
``host_prep_s``, ``device_wait_s``, ``host_post_s`` and
``padded_step_share``, each ``.sweep``."""
import os
import sys

import numpy as np
import pytest

from bench import harness, trace
from bench.metrics import _spans

MS = 1_000_000
LOG = dict(log=lambda *a, **k: None)


def _metric(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "bench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_"))


def _synthetic():
    """Two timed calls of 100 ms, each with its sweep and leaf spans, and
    one leaf span after the window."""
    host = []
    for k in range(2):
        t = k * 100 * MS
        host += [[trace.CALL, t, 100 * MS],
                 ["sweep", t + 1 * MS, 98 * MS],
                 ["sweep.pad", t + 1 * MS, 1 * MS],
                 ["sweep.bucket", t + 2 * MS, 30 * MS],
                 ["sweep.gap_columns", t + 32 * MS, 20 * MS],
                 ["sweep.put", t + 52 * MS, 4 * MS],
                 ["sweep.dispatch", t + 56 * MS, 1 * MS],
                 ["PjitFunction(_hybrid_sweep_scan_pallas)", t + 56 * MS,
                  1 * MS],
                 ["sweep.pull", t + 57 * MS, 30 * MS],
                 ["sweep.scatter", t + 87 * MS, 8 * MS],
                 ["sweep.fill", t + 95 * MS, 2 * MS]]
    host.append(["sweep.pull", 300 * MS, 50 * MS])          # outside
    return {"devices": {}, "host": host}


@pytest.mark.parametrize("name,seconds", [
    ("host_prep_s.sweep", 0.055),       # (1 + 30 + 20 + 4) ms a call
    ("device_wait_s.sweep", 0.030),
    ("host_post_s.sweep", 0.010),       # (8 + 2) ms; no ARIMA post-pass
])
def test_span_readers_sum_their_spans_per_call(name, seconds):
    assert _metric(name).reduce(_synthetic(), LOG) == pytest.approx(seconds)


@pytest.mark.parametrize("name", ["host_prep_s.sweep", "device_wait_s.sweep",
                                  "host_post_s.sweep"])
def test_span_readers_are_null_not_zero_without_a_sweep_span(name):
    tr = {"devices": {}, "host": [[trace.CALL, 0, MS],
                                  ["PjitFunction(f)", 0, MS]]}
    assert _metric(name).reduce(tr, LOG) is None
    assert _metric(name).reduce({"devices": {}, "host": []}, LOG) is None


def test_spans_per_call_and_their_coverage():
    per = _spans.per_call(_synthetic())
    assert set(per) == {"sweep.pad", "sweep.bucket", "sweep.gap_columns",
                        "sweep.put", "sweep.dispatch", "sweep.pull",
                        "sweep.scatter", "sweep.fill"}
    assert per["sweep.pull"] == pytest.approx(0.030)
    # 96 of each call's 100 ms are in a leaf span
    assert _spans.coverage(_synthetic()) == pytest.approx(0.96)


def test_padded_step_share_reads_the_counters(monkeypatch):
    from repro import telemetry
    monkeypatch.setattr(telemetry, "_COUNTS", {
        "sweep.event_steps": 6 * 35_105_845,
        "sweep.app_steps": 6 * 71_792_640})
    share = _metric("padded_step_share.sweep").reduce({}, LOG)
    assert round(share, 2) == 51.10
    monkeypatch.setattr(telemetry, "_COUNTS", {})
    assert _metric("padded_step_share.sweep").reduce({}, LOG) is None


def test_padded_step_share_is_null_for_a_program_without_counters(
        monkeypatch):
    import repro
    monkeypatch.delattr(repro, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert _metric("padded_step_share.sweep").reduce({}, LOG) is None


def test_a_recorded_sweep_is_read_on_the_calling_threads_line():
    """A small sweep profiled as the harness profiles a window: its spans
    are on the line the reduction keeps, and cover nearly all of each
    call."""
    import jax
    from repro.core.experiment import HybridSpec, sweep
    from repro.core.workload import Trace
    from bench import fleet
    times, counts = fleet.fleet(40, 1.0, 5)
    tr = Trace(specs=None, times=None, duration_minutes=1440.0,
               _padded=(times, counts))
    specs = [HybridSpec(range_minutes=60.0, use_arima=False)]
    sweep(tr, specs, engine="fused")
    cap = trace.Capture()
    for _ in range(2):
        with jax.profiler.TraceAnnotation(trace.CALL):
            sweep(tr, specs, engine="fused")
    got = cap.stop()
    for name in ("host_prep_s.sweep", "device_wait_s.sweep",
                 "host_post_s.sweep"):
        value = _metric(name).reduce(got, LOG)
        assert value is not None and value > 0
    assert sum(e[0] == "sweep" for e in got["host"]) == 2
    assert 0.5 < _spans.coverage(got) <= 1.0
    assert np.isfinite(_spans.coverage(got))
