"""Cluster-engine conformance: the vectorized columnar engine vs the
per-event scalar oracle, plus the checked-in small-fleet golden.

The exactness contract (see ``repro.serving.cluster_vector``): cold counts,
per-app cold %, latencies and every load/unload/prewarm/eviction counter
are bit-identical between engines — on oversubscribed fleets too, where
the vectorized engine replays HBM evictions to a fixed point; resident
byte-seconds (and hence wasted GB-minutes) agree to float64
accumulation-order tolerance. The suite pins that contract across policy
families, both balancing modes, hedging, controller checkpoint/restore
(including the ``checkpoint_at_minute=0.0`` regression and a checkpoint
dropped mid-eviction-storm) and the eviction machinery itself: storm
conformance, the pessimistic screen short-circuit, the
``max_eviction_rounds`` scalar fallback and the single-image-over-budget
construction guard.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core.experiment import (FixedSpec, HybridSpec, NoUnloadSpec,
                                   as_spec, run, sweep)
from repro.core.workload import Trace
from repro.core.workload_spec import WorkloadSpec, azure_like, flash_crowd
from repro.runtime.straggler import HedgePolicy
from repro.serving.apptable import AppTable, fnv1a64, fnv1a64_app_indices
from repro.serving.cluster_sim import ClusterSim
from repro.serving.cluster_vector import (ClusterSpec, run_cluster,
                                          sweep_cluster)

from golden_traces import cluster_oversubscribed_fleet, cluster_small_fleet

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden")

_COUNTERS = ("cold_starts", "warm_starts", "prewarms", "unloads",
             "evictions", "budget_overflows", "bytes_moved")


def _oversubscribe(table, factor=40.0, budget=30e9):
    """Inflate model images ~``factor``x so per-worker assigned bytes
    oversubscribe ``budget`` several times over (single images stay under
    it, clearing the construction guard)."""
    wb = np.minimum((table.memory_mb * 2 ** 20 * factor).astype(np.int64),
                    np.int64(0.8 * budget))
    return dataclasses.replace(table, weight_bytes=wb)


@pytest.fixture(scope="module")
def azure_table():
    return AppTable.from_spec(
        azure_like(220, days=0.25, seed=11, max_events=24))


@pytest.fixture(scope="module")
def flash_table():
    return AppTable.from_spec(
        flash_crowd(160, days=0.25, seed=3, max_events=48))


def _assert_results_equal(vec, sca, err=""):
    np.testing.assert_array_equal(vec.cold_pct_per_app, sca.cold_pct_per_app,
                                  err_msg=err)
    np.testing.assert_array_equal(vec.latencies_s, sca.latencies_s,
                                  err_msg=err)
    np.testing.assert_allclose(vec.wasted_gb_minutes, sca.wasted_gb_minutes,
                               rtol=1e-9, err_msg=err)
    assert len(vec.stats_per_worker) == len(sca.stats_per_worker), err
    for w, (sv, ss) in enumerate(zip(vec.stats_per_worker,
                                     sca.stats_per_worker)):
        for key in _COUNTERS:
            assert sv[key] == ss[key], f"{err}: worker {w} {key}"
        np.testing.assert_allclose(sv["resident_byte_seconds"],
                                   ss["resident_byte_seconds"], rtol=1e-9,
                                   err_msg=f"{err}: worker {w}")
    assert vec.restored_mid_run == sca.restored_mid_run, err


def _conform(table, policy, cluster):
    vec = run_cluster(table, policy, cluster, engine="vector")
    sca = run_cluster(table, policy, cluster, engine="scalar")
    _assert_results_equal(vec, sca,
                          err=f"{type(policy).__name__}/{cluster.name}")
    return vec


# --------------------------------------------------------------------------
# Engine conformance across policy families and balancing modes
# --------------------------------------------------------------------------


@pytest.mark.parametrize("policy,balancing", [
    (HybridSpec(), "affinity"),
    (HybridSpec(), "hash"),
    (FixedSpec(keep_alive=20.0), "affinity"),
    (NoUnloadSpec(), "hash"),
])
def test_conformance_azure(azure_table, policy, balancing):
    _conform(azure_table, policy,
             ClusterSpec(n_workers=7, hbm_budget_bytes=float("inf"),
                         balancing=balancing))


def test_conformance_flash_crowd(flash_table):
    res = _conform(flash_table, HybridSpec(),
                   ClusterSpec(n_workers=5, hbm_budget_bytes=float("inf")))
    assert res.latencies_s.size == flash_table.n_events


def test_hedging_parity(azure_table):
    # Same rank-indexed uniform streams in both engines -> identical
    # stragglers, hence bit-equal latencies even under hedging.
    hedged = ClusterSpec(n_workers=7, hbm_budget_bytes=float("inf"),
                         hedge=HedgePolicy())
    res = _conform(azure_table, FixedSpec(keep_alive=15.0), hedged)
    plain = run_cluster(azure_table, FixedSpec(keep_alive=15.0),
                        ClusterSpec(n_workers=7,
                                    hbm_budget_bytes=float("inf")),
                        engine="vector")
    assert not np.array_equal(res.latencies_s, plain.latencies_s)


# --------------------------------------------------------------------------
# Controller checkpoint/restore
# --------------------------------------------------------------------------


def test_checkpoint_at_zero_regression(azure_table):
    """checkpoint_at_minute=0.0 means "checkpoint at the first event" — a
    falsy check used to silently drop it. Both engines must restore, and the
    save/restore round-trip must not perturb the trajectory."""
    base = dict(n_workers=6, hbm_budget_bytes=float("inf"))
    ck0 = _conform(azure_table, HybridSpec(),
                   ClusterSpec(checkpoint_at_minute=0.0, **base))
    assert ck0.restored_mid_run
    plain = run_cluster(azure_table, HybridSpec(), ClusterSpec(**base),
                        engine="scalar")
    assert not plain.restored_mid_run
    np.testing.assert_array_equal(ck0.cold_pct_per_app,
                                  plain.cold_pct_per_app)
    np.testing.assert_array_equal(ck0.latencies_s, plain.latencies_s)


def test_checkpoint_mid_and_past_end(azure_table):
    base = dict(n_workers=6, hbm_budget_bytes=float("inf"))
    mid = _conform(azure_table, FixedSpec(keep_alive=10.0),
                   ClusterSpec(checkpoint_at_minute=100.0, **base))
    assert mid.restored_mid_run
    never = _conform(azure_table, FixedSpec(keep_alive=10.0),
                     ClusterSpec(checkpoint_at_minute=1e9, **base))
    assert not never.restored_mid_run


# --------------------------------------------------------------------------
# Golden fleet fixtures (both engines vs checked-in oracle runs)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["scalar", "vector"])
@pytest.mark.parametrize("fixture,fname", [
    (cluster_small_fleet, "cluster_small.json"),
    (cluster_oversubscribed_fleet, "cluster_oversub.json"),
])
def test_golden_fleet(engine, fixture, fname):
    with open(os.path.join(GOLDEN_DIR, fname)) as f:
        want = json.load(f)
    workload, policy, cluster = fixture()
    assert want["n_apps"] == workload.n_apps
    assert want["n_workers"] == cluster.n_workers
    res = run_cluster(workload, policy, cluster, engine=engine)
    err = f"{engine} vs golden {fname} (see scripts/regen_golden.py)"
    np.testing.assert_array_equal(
        res.cold_pct_per_app, np.asarray(want["cold_pct_per_app"]),
        err_msg=err)
    for q, v in want["latency_pct"].items():
        assert res.latency_pct(float(q)) == v, f"{err}: p{q}"
    np.testing.assert_allclose(res.wasted_gb_minutes,
                               want["wasted_gb_minutes"], rtol=1e-9,
                               err_msg=err)
    for w, ws in enumerate(want["stats_per_worker"]):
        for key in _COUNTERS:
            assert res.stats_per_worker[w][key] == ws[key], \
                f"{err}: worker {w} {key}"


# --------------------------------------------------------------------------
# Worker placement and hashing
# --------------------------------------------------------------------------


@pytest.mark.parametrize("balancing", ["affinity", "hash"])
def test_worker_assignment_matches_oracle(azure_table, balancing):
    cluster = ClusterSpec(n_workers=5, hbm_budget_bytes=float("inf"),
                          balancing=balancing)
    sim = ClusterSim(azure_table.to_registry(),
                     as_spec(FixedSpec(keep_alive=5.0)), cluster.to_config())
    sim.run(azure_table.to_trace())
    expect = azure_table.worker_assignment(5, balancing)
    for i in range(azure_table.n_apps):
        if azure_table.counts[i] > 0:
            assert sim._assign[azure_table.app_id(i)] == expect[i], i


def test_fnv1a64_vectorized_matches_scalar():
    idx = np.array([0, 5, 17, 999999, 1000000, 10 ** 7 + 3])
    got = fnv1a64_app_indices(idx)
    for i, h in zip(idx, got):
        assert int(h) == fnv1a64(f"app-{int(i):06d}"), i
    with pytest.raises(ValueError, match="non-negative"):
        fnv1a64_app_indices(np.array([-1]))


# --------------------------------------------------------------------------
# HBM eviction regime (fixed-point replay vs the oracle)
# --------------------------------------------------------------------------


def _two_app_trace(times, duration=30.0):
    return Trace(specs=None,
                 times=[np.asarray(t, np.float64) for t in times],
                 duration_minutes=duration)


def test_eviction_pressure_conformance():
    # Two 10 GB apps resident together on one 16 GB worker: the regime the
    # PR 6 engine refused. Both engines now evict the same victim at the
    # same tick and every counter matches.
    table = AppTable.from_trace(_two_app_trace([[0.0], [1.0]]),
                                exec_s=1.0, memory_mb=512.0,
                                weight_bytes=np.array([10e9, 10e9], np.int64))
    cluster = ClusterSpec(n_workers=1, hbm_budget_bytes=16e9)
    res = _conform(table, NoUnloadSpec(), cluster)
    assert res.evictions >= 1
    assert res.budget_overflows == 0


@pytest.mark.parametrize("policy,balancing", [
    (HybridSpec(), "affinity"),
    (FixedSpec(keep_alive=20.0), "hash"),
    (NoUnloadSpec(), "affinity"),
])
def test_eviction_storm_conformance(flash_table, policy, balancing):
    # Flash-crowd eviction storm: hundreds of soonest-expiry evictions per
    # worker, bit-identical across engines for every policy family.
    res = _conform(_oversubscribe(flash_table), policy,
                   ClusterSpec(n_workers=3, hbm_budget_bytes=30e9,
                               balancing=balancing))
    assert res.evictions > 100


def test_eviction_storm_with_hedging(flash_table):
    res = _conform(_oversubscribe(flash_table), HybridSpec(),
                   ClusterSpec(n_workers=3, hbm_budget_bytes=30e9,
                               hedge=HedgePolicy()))
    assert res.evictions > 100


def test_checkpoint_mid_eviction_storm(flash_table):
    # Controller checkpoint/restore dropped into the middle of an eviction
    # storm: the save/restore round-trip must not perturb the trajectory.
    res = _conform(_oversubscribe(flash_table), HybridSpec(),
                   ClusterSpec(n_workers=3, hbm_budget_bytes=30e9,
                               checkpoint_at_minute=60.0))
    assert res.restored_mid_run
    assert res.evictions > 100


def test_screen_short_circuits_eviction_free_runs(azure_table, monkeypatch):
    # Workers whose assigned bytes fit at once never enter the fixed-point
    # loop: poison the replay and run eviction-free fleets through it.
    from repro.serving import cluster_vector

    def _boom(*args, **kwargs):
        raise AssertionError(
            "fixed-point eviction replay ran on an eviction-free fleet")

    monkeypatch.setattr(cluster_vector, "_evict_worker", _boom)
    # infinite budget: the screen skips Phase D entirely
    _conform(azure_table, FixedSpec(keep_alive=10.0),
             ClusterSpec(n_workers=5, hbm_budget_bytes=float("inf")))
    # finite but sufficient: every worker passes the assigned-bytes sum test
    run_cluster(azure_table, FixedSpec(keep_alive=10.0),
                ClusterSpec(n_workers=5,
                            hbm_budget_bytes=float(
                                azure_table.weight_bytes.sum())),
                engine="vector")


def test_max_eviction_rounds_falls_back_to_scalar(flash_table):
    table = _oversubscribe(flash_table)
    cluster = ClusterSpec(n_workers=3, hbm_budget_bytes=30e9)
    with pytest.warns(RuntimeWarning, match="engine='scalar'"):
        res = run_cluster(table, FixedSpec(keep_alive=20.0), cluster,
                          engine="vector", max_eviction_rounds=0)
    sca = run_cluster(table, FixedSpec(keep_alive=20.0), cluster,
                      engine="scalar")
    _assert_results_equal(res, sca, err="max_eviction_rounds fallback")
    assert res.evictions >= 1


def test_single_image_over_budget_raises_in_both_engines():
    table = AppTable.from_trace(_two_app_trace([[0.0], [1.0]]),
                                exec_s=1.0, memory_mb=512.0,
                                weight_bytes=np.array([20e9, 1e9], np.int64))
    cluster = ClusterSpec(n_workers=1, hbm_budget_bytes=16e9)
    for engine in ("vector", "scalar"):
        with pytest.raises(ValueError, match="larger than the budget"):
            run_cluster(table, NoUnloadSpec(), cluster, engine=engine)


def test_eviction_screen_passes_on_interleaved_residency():
    # Assigned bytes exceed the budget in *sum*, but the first app expires
    # (at the second app's tick) before the third loads — the exact
    # occupancy replay proves the run eviction-free and the engines agree.
    table = AppTable.from_trace(
        _two_app_trace([[0.0], [10.0], [20.0]]),
        exec_s=1.0, memory_mb=512.0,
        weight_bytes=np.array([10e9, 1e9, 10e9], np.int64))
    cluster = ClusterSpec(n_workers=1, hbm_budget_bytes=16e9)
    _conform(table, FixedSpec(keep_alive=0.5), cluster)


def _gigabyte_table(times, gigabytes, duration):
    """One app per list of arrival minutes, app ``i``'s image
    ``gigabytes[i]`` GB, one-second requests."""
    return AppTable.from_trace(
        _two_app_trace(times, duration=duration), exec_s=1.0,
        memory_mb=512.0,
        weight_bytes=(np.asarray(gigabytes) * 1e9).astype(np.int64))


_EVERY_30 = np.arange(10) * 30.0


def _eviction_path_case(path, flash_table):
    """(table, policy, cluster, check) for one path of the fixed point;
    ``check(result, calls, counts)`` asserts the path ran, from the
    replay's result, each worker replay's keywords and result, and the
    call's counters."""
    one = ClusterSpec(n_workers=1, hbm_budget_bytes=16e9)
    if path == "fires_on_one_tick":
        # Apps 0 and 1 share a schedule (equal prewarm_at: the app id
        # orders them), app 2 runs 3 s behind; all three pre-warms come
        # due before app 3's arrival, the tick that fires them, and only
        # two 6 GB images fit.
        table = _gigabyte_table([_EVERY_30, _EVERY_30, _EVERY_30 + 0.05,
                                 _EVERY_30 + 29.0], [6, 6, 6, 1], 330.0)

        def check(res, calls, counts):
            assert sum(s["prewarms"] for s in res.stats_per_worker) > 0
            assert any(np.diff(kw["block_at"]).max(initial=0) >= 3
                       for kw, _ in calls)
            assert res.evictions > 0
        return table, HybridSpec(use_arima=False), one, check
    if path == "victim_turns_cold_then_evicts":
        # B's load evicts A, whose warm next arrival turns cold and, over
        # budget itself, evicts B: a check the table did not start with.
        table = _gigabyte_table([[0.0, 5.0], [1.0]], [10, 10], 30.0)

        def check(res, calls, counts):
            (kw, out), = calls
            assert out[3] == 2 > len(kw["viol"])
            assert res.cold_pct_per_app[0] == 100.0
        return table, FixedSpec(keep_alive=10.0), one, check
    if path == "every_candidate_evicted":
        # The 15 GB load fits only once both resident 6 GB images go.
        table = _gigabyte_table([[0.0], [1.0], [2.0]], [6, 6, 15], 30.0)

        def check(res, calls, counts):
            assert counts["cluster.evict_rounds"] == 1
            assert (counts["cluster.evict_candidates"] == res.evictions
                    == 2)
        return table, FixedSpec(keep_alive=10.0), one, check
    if path == "span_to_run_end":
        # No unload: app 0's only gap is resident to the run's end (no
        # expiry, no next arrival) until app 2's load evicts it, the
        # soonest-expiry tie (all at inf) broken by the app id.
        table = _gigabyte_table([[0.0], [1.0], [2.0]], [6, 6, 10], 30.0)

        def check(res, calls, counts):
            assert res.evictions == 1
            assert res.stats_per_worker[0]["unloads"] == 3
        return table, NoUnloadSpec(), one, check
    budget = {"storm_20gb": 20e9, "storm_45gb": 45e9}[path]

    def check(res, calls, counts):
        assert res.evictions > 100
    return (_oversubscribe(flash_table, budget=budget), HybridSpec(),
            ClusterSpec(n_workers=3, hbm_budget_bytes=budget), check)


@pytest.mark.parametrize("path", [
    "fires_on_one_tick", "victim_turns_cold_then_evicts",
    "every_candidate_evicted", "span_to_run_end", "storm_20gb",
    "storm_45gb"])
def test_eviction_fixed_point_paths(flash_table, monkeypatch, path):
    """Each case drives one path of the worker replay against the oracle:
    a fire block resolved fire by fire in ``(prewarm_at, app_id)`` order,
    a load turned cold by an eviction that then evicts, a load that takes
    every candidate, a victim resident to the run's end, and the
    flash-crowd storm at two more budgets. (An over-budget load with no
    candidate at all cannot arise in a cluster replay: no app is pinned
    while another loads, and no image exceeds the budget.)"""
    from repro import telemetry
    from repro.serving import cluster_vector

    table, policy, cluster, check = _eviction_path_case(path, flash_table)
    calls = []
    replay = cluster_vector._evict_worker

    def recorded(j_idx, budget, **kw):
        out = replay(j_idx, budget, **kw)
        calls.append((kw, out))
        return out
    monkeypatch.setattr(cluster_vector, "_evict_worker", recorded)
    before = telemetry.counters()
    res = _conform(table, policy, cluster)
    after = telemetry.counters()
    check(res, calls, {k: after.get(k, 0) - before.get(k, 0)
                       for k in after})


# --------------------------------------------------------------------------
# AppTable bridges and workload coercion
# --------------------------------------------------------------------------


def test_apptable_uniform_spec_needs_metadata():
    with pytest.raises(ValueError, match="patterns"):
        AppTable.from_spec(WorkloadSpec.uniform(8))
    tab = AppTable.from_spec(WorkloadSpec.uniform(8, seed=2), exec_s=0.5,
                             memory_mb=256.0)
    assert tab.n_apps == 8
    assert np.all(tab.exec_s == 0.5)


def test_apptable_padded_trace_needs_metadata():
    trace = _two_app_trace([[0.0, 5.0], [1.0]])
    with pytest.raises(ValueError, match="padded-only"):
        AppTable.from_trace(trace)
    tab = AppTable.from_trace(trace, exec_s=[0.1, 0.2], memory_mb=128.0)
    np.testing.assert_array_equal(tab.counts, [2, 1])
    back = tab.to_trace()
    assert back.specs is not None
    np.testing.assert_array_equal(back.events(0), [0.0, 5.0])
    reg = tab.to_registry()
    assert reg.get("app-000000").weight_bytes == 128 * 2 ** 20


def test_run_cluster_rejects_unknown_engine(azure_table):
    with pytest.raises(ValueError, match="unknown cluster engine"):
        run_cluster(azure_table, HybridSpec(), engine="warp")


# --------------------------------------------------------------------------
# Experiment-grid plumbing: trace x policy x cluster
# --------------------------------------------------------------------------


def test_sweep_cells_match_single_runs(azure_table):
    specs = [FixedSpec(keep_alive=10.0), NoUnloadSpec()]
    clusters = [ClusterSpec(n_workers=3, hbm_budget_bytes=float("inf")),
                ClusterSpec(n_workers=3, hbm_budget_bytes=float("inf"),
                            balancing="hash")]
    grid = sweep_cluster(azure_table, specs, clusters)
    assert grid.shape == (1, 2, 2)
    for s, spec in enumerate(specs):
        for c, cl in enumerate(clusters):
            single = run_cluster(azure_table, spec, cl)
            _assert_results_equal(grid.row(0, s, c), single,
                                  err=f"cell ({s},{c})")


def test_experiment_run_and_sweep_cluster_axis(azure_table):
    cl = ClusterSpec(n_workers=4, hbm_budget_bytes=float("inf"))
    single = run_cluster(azure_table, FixedSpec(keep_alive=10.0), cl)
    via_run = run(azure_table, FixedSpec(keep_alive=10.0), cluster=cl)
    _assert_results_equal(via_run, single, err="experiment.run(cluster=)")
    grid = sweep(traces=[azure_table], specs=[FixedSpec(keep_alive=10.0)],
                 clusters=[cl])
    assert grid.shape == (1, 1, 1)
    _assert_results_equal(grid.row(0, 0, 0), single,
                          err="experiment.sweep(clusters=)")
