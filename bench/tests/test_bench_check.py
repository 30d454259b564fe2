"""What decides ``correct``: the plain references agree with the program,
and their controls and each fault a cell can have come out false.

These runs skip the harness's look for a chip and drive the rest of a run
on the CPU at a small fleet. There the sweep's timed path is the
program's float64 fused engine (on the chip it is the Pallas kernel, held
to the same check).
"""
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.drivers import sweep as sweep_driver
from bench.reference.hybrid import reference

SEED = 2 ** 31 + 17


@pytest.fixture(scope="module")
def resolved():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    r = harness.resolve(bench, "grid32-azure10k-14d")
    r["config"] = dict(r["config"], fleet=dict(r["config"]["fleet"],
                                               n_apps=200, days=1))
    r["traffic"] = dict(r["traffic"], check_apps=48)
    return r


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")


def _run(resolved, seconds=0.2):
    jax.clear_caches()   # a patched engine must be traced again
    return harness.run_cell(resolved, SEED, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            log=lambda *a, **k: None)


def test_reference_matches_the_programs_float64_engine():
    from repro.core.experiment import HybridSpec, sweep
    from repro.core.workload import Trace
    from bench import fleet
    times, counts = fleet.fleet(120, 1.0, SEED)
    trace = Trace(specs=None, times=None, duration_minutes=1440.0,
                  _padded=(times, counts))
    cfgs = sweep_driver.call_grid(
        sweep_driver.grid(harness.load_json(
            harness.ROOT, "bench", "traffic", "hybrid-grid32.json")),
        0.01, SEED, 3)
    res = sweep(trace, [HybridSpec(
        bin_minutes=c["bin_minutes"], range_minutes=c["range_minutes"],
        head_percentile=c["head_percentile"],
        tail_percentile=c["tail_percentile"], margin=c["margin"],
        cv_threshold=c["cv_threshold"], min_samples=c["min_samples"],
        oob_fraction_threshold=c["oob_threshold"], use_arima=False)
        for c in cfgs], engine="fused")
    ref = reference(times, counts, 1440.0, cfgs)
    assert np.array_equal(res.cold, ref["cold"])
    assert np.array_equal(res.invocations, ref["invocations"])
    assert np.array_equal(res.final_prewarm, ref["prewarm"])
    assert np.array_equal(res.final_keep_alive, ref["keep_alive"])
    assert np.array_equal(res.wasted_minutes, ref["waste"])


def test_a_sound_run_is_correct(resolved):
    out = _run(resolved)
    assert out["correct"] is True
    assert out["checks"]["mismatch_rows"] == {"value": 0, "limit": 0}
    assert out["checks"]["waste_err"]["value"] <= 1.0
    assert out["metrics"]["sweep_events_per_s"]["value"] > 0
    assert list(out["checks"]) == ["mismatch_rows", "waste_err"]
    assert list(out)[-1] == "checks"


def test_the_float32_control_is_not_correct(resolved):
    work = resolved["driver"].Work(resolved["config"], resolved["traffic"],
                                   SEED, None, log=lambda *a, **k: None)
    for i in range(2):
        work.call(i)
    sound = work.check()
    control = work.control(2)
    assert harness.is_correct(sound) and not harness.is_correct(control)
    assert sound["mismatch_rows"]["value"] == 0
    assert control["mismatch_rows"]["value"] > 0


def _state_unchanged(monkeypatch):
    from repro.core import policy_math
    monkeypatch.setattr(policy_math, "fused_hybrid_sweep_step_math",
                        lambda t_now, *carry, **kw: carry)


def _half_the_batch(monkeypatch):
    from repro.core import simulator
    orig = simulator._chunked_buckets

    def half(times, counts, app_chunk):
        for sel, sub in orig(times, counts, app_chunk):
            k = max(len(sel) // 2, 1)
            yield sel[:k], sub[:k]
    monkeypatch.setattr(simulator, "_chunked_buckets", half)


def _answer_altered(monkeypatch):
    from repro.core import simulator
    orig = simulator._hybrid_sweep_scan

    def altered(*args, **kw):
        cold, *rest = orig(*args, **kw)
        return (cold.at[0].add(1), *rest)
    monkeypatch.setattr(simulator, "_hybrid_sweep_scan", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _answer_altered])
def test_a_broken_timed_path_is_not_correct(resolved, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(resolved)
    assert out["correct"] is False
    assert out["checks"]["mismatch_rows"]["value"] > 0
