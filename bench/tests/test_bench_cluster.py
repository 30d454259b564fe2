"""The cluster cell's check: the plain invoker reference agrees with the
program, its control and each fault the cell can have come out false, and
the cell's readers reduce a trace to what it holds.

These run on the CPU at a small fleet (300 apps over 0.1 day on 4
invokers, with a budget that evicts), skipping the harness's look for a
chip.
"""
import os
import time

import jax
import numpy as np
import pytest

from bench import harness, trace
from bench.reference import cluster as ref
from bench.reference.hybrid import reference

SEED = 2 ** 31 + 23
CELL = "cluster64-azure20k-12h"
MS = 1_000_000
LOG = dict(log=lambda *a, **k: None)


def _resolved(balancing="hash"):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    r = harness.resolve(bench, CELL)
    r["config"] = dict(
        r["config"], fleet=dict(r["config"]["fleet"], n_apps=300, days=0.1),
        cluster=dict(r["config"]["cluster"], invokers=4,
                     hbm_budget_bytes=3e9, balancing=balancing))
    r["traffic"] = dict(r["traffic"], check_invokers=4)
    return r


@pytest.fixture(scope="module")
def resolved():
    return _resolved()


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")


def _run(resolved, seconds=0.2):
    jax.clear_caches()
    return harness.run_cell(resolved, SEED, seconds, False,
                            time.perf_counter(), require_tpu=False,
                            log=lambda *a, **k: None)


@pytest.mark.parametrize("balancing", ["hash", "affinity"])
def test_reference_equals_the_program_on_every_invoker(balancing):
    r = _resolved(balancing)
    work = r["driver"].Work(r["config"], r["traffic"], SEED, None,
                            log=lambda *a, **k: None)
    work.call(0)
    got = work.check()
    assert got["mismatch_rows"]["value"] == 0
    assert got["resident_err"]["value"] <= 1.0
    facts = work.check_facts()
    assert facts["checked_invokers"] == 4
    assert facts["checked_apps"] == 300
    assert facts["checked_evictions"] == work.kept[0][1].evictions > 0


def test_reference_windows_equal_the_hybrid_reference():
    from bench import fleet
    times, counts = fleet.fleet(300, 0.1, SEED)
    cfg = harness.load_json(harness.ROOT, "bench", "traffic",
                            "cluster-hybrid-default.json")["policy"]
    load, unload = ref.windows(times, counts, cfg)
    last = np.maximum(counts - 1, 0)
    apps = np.arange(len(counts))
    want = reference(times, counts, 0.1 * 1440.0, [cfg])
    has = counts > 0
    np.testing.assert_array_equal(load[apps, last][has],
                                  want["prewarm"][0][has])
    np.testing.assert_array_equal((unload[apps, last] - load[apps, last])
                                  [has], want["keep_alive"][0][has])


def test_a_sound_run_is_correct(resolved):
    out = _run(resolved)
    assert out["correct"] is True
    assert out["checks"]["mismatch_rows"] == {"value": 0, "limit": 0}
    assert out["checks"]["resident_err"]["value"] <= 1.0
    assert out["metrics"]["sweep_events_per_s"]["value"] > 0
    assert list(out["checks"]) == ["mismatch_rows", "resident_err"]


def test_the_float32_control_is_not_correct(resolved):
    work = resolved["driver"].Work(resolved["config"], resolved["traffic"],
                                   SEED, None, log=lambda *a, **k: None)
    control = work.control(1)
    assert not harness.is_correct(control)
    assert control["mismatch_rows"]["value"] > 0
    assert control["resident_err"]["value"] > 1.0


def _no_evictions(monkeypatch):
    """Phase D skipped: every worker's budget treated as unlimited."""
    from repro.serving import cluster_vector

    def none(j_idx, budget, **kw):
        return np.zeros(len(j_idx), bool), np.zeros(len(j_idx)), 0, 0
    monkeypatch.setattr(cluster_vector, "_evict_worker", none)


def _one_invoker_left_out(monkeypatch):
    """Invoker 0's apps left out of the replay."""
    import dataclasses
    from repro.serving import cluster_vector
    orig = cluster_vector._run_vector

    def without(table, spec, cluster, *args, **kw):
        gone = table.worker_assignment(cluster.n_workers,
                                       cluster.balancing) == 0
        return orig(dataclasses.replace(
            table, counts=np.where(gone, 0, table.counts).astype(np.int32)),
            spec, cluster, *args, **kw)
    monkeypatch.setattr(cluster_vector, "_run_vector", without)


@pytest.mark.parametrize("fault", [_no_evictions, _one_invoker_left_out])
def test_a_broken_replay_is_not_correct(resolved, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(resolved)
    assert out["correct"] is False
    assert out["checks"]["mismatch_rows"]["value"] > 0


# -- the cell's readers ------------------------------------------------------


def _metric(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "bench", "metrics", name + ".py"),
        "m_" + name.replace(".", "_"))


def _synthetic():
    """Two timed calls of 100 ms, each with its cluster leaf spans (96 ms
    of them), a windows scan of 2 ms on the device, and one leaf span
    after the window."""
    host, mods, ops = [], [], []
    for k in range(2):
        t = k * 100 * MS
        host += [[trace.CALL, t, 100 * MS],
                 ["cluster.merge", t + 1 * MS, 10 * MS],
                 ["cluster.bucket", t + 11 * MS, 1 * MS],
                 ["cluster.gap_columns", t + 12 * MS, 5 * MS],
                 ["cluster.put", t + 17 * MS, 1 * MS],
                 ["PjitFunction(_hybrid_windows_scan)", t + 17 * MS, MS],
                 ["cluster.pull", t + 18 * MS, 3 * MS],
                 ["cluster.replay", t + 21 * MS, 20 * MS],
                 ["cluster.evict", t + 41 * MS, 50 * MS],
                 ["cluster.results", t + 91 * MS, 6 * MS]]
        mods.append(["jit__hybrid_windows_scan", t + 18 * MS, 2 * MS])
        ops.append(["%while", t + 18 * MS, 2 * MS])
    host.append(["cluster.evict", 300 * MS, 50 * MS])      # outside
    dev = {"/device:TPU:0": {trace.MODULES_LINE: mods,
                             trace.OPS_LINE: ops}}
    return {"devices": dev, "host": host}


@pytest.mark.parametrize("name,seconds", [
    ("merge_s.cluster", 0.010),
    ("windows_s.cluster", 0.010),       # (1 + 5 + 1 + 3) ms a call
    ("replay_s.cluster", 0.020),
    ("evict_s.cluster", 0.050),
    ("results_s.cluster", 0.006),
])
def test_span_readers_sum_their_spans_per_call(name, seconds):
    assert _metric(name).reduce(_synthetic(), LOG) == pytest.approx(seconds)


def test_windows_reader_logs_every_span_and_the_least_coverage():
    logged = {}
    _metric("windows_s.cluster").reduce(
        _synthetic(), dict(log=lambda *a, **k: logged.update(k)))
    assert logged["covered"] == pytest.approx(0.96)
    assert logged["cluster.evict"] == pytest.approx(0.050)


@pytest.mark.parametrize("name", ["merge_s.cluster", "windows_s.cluster",
                                  "replay_s.cluster", "evict_s.cluster",
                                  "results_s.cluster"])
def test_span_readers_are_null_without_a_cluster_span(name):
    tr = {"devices": {}, "host": [[trace.CALL, 0, MS],
                                  ["sweep.pull", 0, MS]]}
    assert _metric(name).reduce(tr, LOG) is None


def test_device_readers_reduce_the_scan_and_the_idle_share():
    tr = _synthetic()
    # 2 scans of 2 ms in a 200 ms window: 98% idle
    assert _metric("device_idle_share.cluster").reduce(tr, LOG) == \
        pytest.approx(98.0)
    peaks = dict(hbm_bytes_per_s=1e9)
    got = _metric("windows_roofline.cluster").reduce(
        tr, dict(LOG, least_bytes=2_000_000, peaks=peaks))
    assert got == pytest.approx(50.0)   # 2 ms least of 4 ms on the chip
    assert _metric("windows_roofline.cluster").reduce(
        {"devices": {}, "host": tr["host"]},
        dict(LOG, least_bytes=1, peaks=peaks)) is None


def test_eviction_share_reads_the_counters(monkeypatch):
    from repro import telemetry
    reader = _metric("evicting_event_share.cluster")
    monkeypatch.setattr(telemetry, "counters", lambda: {
        "cluster.events": 400, "cluster.evictions": 3,
        "cluster.evict_rounds": 2, "sweep.app_steps": 9})
    assert reader.reduce({}, LOG) == pytest.approx(0.75)
    monkeypatch.setattr(telemetry, "counters", lambda: {"sweep.app_steps": 9})
    assert reader.reduce({}, LOG) is None


def test_least_bytes_count_events_bounds_and_flags():
    from bench import cluster_cost
    assert cluster_cost.windows_least_bytes(1000, 10) == 1000 * 12 + 10
