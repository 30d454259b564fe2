"""Spans and counters of one invoker-cluster replay (``repro.telemetry``).

Under ``jax.profiler`` the vectorized cluster engine writes one leaf span
per phase (``cluster.merge``, ``cluster.replay``, ``cluster.evict``,
``cluster.results``) and four per policy-window chunk
(``cluster.bucket``, ``cluster.gap_columns``, ``cluster.put``,
``cluster.pull``) on the calling thread; with or without a profiler it
counts events, evictions, fixed-point rounds, screened workers and the
candidate images the fixed point examined. Neither changes a result.
"""
import glob
import os

import numpy as np

import jax

from repro import telemetry
from repro.serving.cluster_vector import run_cluster

from golden_traces import cluster_oversubscribed_fleet, cluster_small_fleet

LEAVES = {"cluster.merge", "cluster.bucket", "cluster.gap_columns",
          "cluster.put", "cluster.pull", "cluster.replay", "cluster.evict",
          "cluster.results"}
COUNTERS = ("cluster.events", "cluster.evictions", "cluster.evict_rounds",
            "cluster.risky_workers", "cluster.evict_candidates")


def _profiled(tmp_path, fn):
    """``fn()`` under the profiler; its result and the host events of
    every line as (name, start_ns, end_ns)."""
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
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    return out, events


def _fields(res):
    return [res.cold_pct_per_app, res.latencies_s,
            np.asarray(res.wasted_gb_minutes)] + [
        np.asarray([s[k] for s in res.stats_per_worker])
        for k in sorted(res.stats_per_worker[0])]


def test_cluster_spans_fire_and_change_no_result(tmp_path):
    table, policy, cluster = cluster_oversubscribed_fleet()
    before = telemetry.counters()
    on, events = _profiled(tmp_path,
                           lambda: run_cluster(table, policy, cluster,
                                               engine="vector"))
    mid = telemetry.counters()
    off = run_cluster(table, policy, cluster, engine="vector")
    scalar = run_cluster(table, policy, cluster, engine="scalar")
    names = {n for n, _, _ in events if n.startswith("cluster.")}
    assert names == LEAVES
    for a, b in zip(_fields(on), _fields(off)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(on.cold_pct_per_app,
                                  scalar.cold_pct_per_app)
    np.testing.assert_array_equal(on.latencies_s, scalar.latencies_s)
    assert on.evictions == scalar.evictions > 0
    for w, s in enumerate(scalar.stats_per_worker):
        for k in ("cold_starts", "warm_starts", "prewarms", "unloads",
                  "evictions", "budget_overflows", "bytes_moved"):
            assert on.stats_per_worker[w][k] == s[k], (w, k)
    # Leaves of one thread: they never overlap one another.
    leaves = sorted((s, e) for n, s, e in events if n in LEAVES)
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(leaves, leaves[1:]))
    delta = {k: mid.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    assert delta["cluster.events"] == on.latencies_s.size
    assert delta["cluster.evictions"] == sum(
        s["evictions"] for s in on.stats_per_worker)
    assert delta["cluster.risky_workers"] == cluster.n_workers
    assert delta["cluster.evict_rounds"] > 0


def test_cluster_counters_count_without_a_profiler():
    table, policy, cluster = cluster_oversubscribed_fleet()
    before = telemetry.counters()
    res = run_cluster(table, policy, cluster, engine="vector")
    after = telemetry.counters()
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}
    assert delta["cluster.events"] == res.latencies_s.size == table.n_events
    assert delta["cluster.evictions"] == res.evictions


def test_evict_candidates_counts_the_examined_images():
    """Candidates examined over every fixed-point round: on the evicting
    golden fleet the 1,705 images its 414 rounds' candidate sets hold
    (with its 458 evictions and the oracle's results), none on an
    eviction-free fleet."""
    def delta(fleet):
        table, policy, cluster = fleet()
        before = telemetry.counters()
        res = run_cluster(table, policy, cluster, engine="vector")
        after = telemetry.counters()
        return res, {k: after.get(k, 0) - before.get(k, 0) for k in COUNTERS}

    res, got = delta(cluster_oversubscribed_fleet)
    table, policy, cluster = cluster_oversubscribed_fleet()
    scalar = run_cluster(table, policy, cluster, engine="scalar")
    np.testing.assert_array_equal(res.latencies_s, scalar.latencies_s)
    assert res.evictions == scalar.evictions
    assert got["cluster.evict_rounds"] == 414
    assert got["cluster.evictions"] == 458
    assert got["cluster.evict_candidates"] == 1705
    res, got = delta(cluster_small_fleet)
    assert res.evictions == 0
    assert got["cluster.evict_candidates"] == 0


def test_a_new_margin_reuses_the_compiled_windows_scan():
    """The scan's knobs are traced: only ``n_bins`` (a shape) is static,
    so a second config of the same bin count compiles nothing."""
    import dataclasses
    from repro.serving import cluster_vector
    table, policy, cluster = cluster_oversubscribed_fleet()
    run_cluster(table, policy, cluster, engine="vector")
    compiled = cluster_vector._hybrid_windows_scan._cache_size()
    for margin in (0.1031, 0.1077):
        run_cluster(table, dataclasses.replace(policy, margin=margin),
                    cluster, engine="vector")
    assert cluster_vector._hybrid_windows_scan._cache_size() == compiled
