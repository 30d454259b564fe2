"""Deterministic trace constructors shared by the conformance suite, the
golden-trace regression tests, and ``scripts/regen_golden.py``.

Each constructor documents which float32-exactness regime it exercises:
the batched float32 engine (Pallas) is fed every app's gaps differenced on
the host in float64, so its idle times are exact whenever the per-app
*rebased* times are float32-representable — which each constructor
guarantees by keeping times on a dyadic grid with a bounded significand.
"""
import numpy as np

from repro.core.histogram import HistogramConfig
from repro.core.policy import HybridConfig
from repro.core.workload import Trace
from repro.core.workload_spec import WorkloadSpec

MINUTES_14D = 14 * 1440.0

# 48 bins keeps the Pallas interpret path fast while exercising every gate.
CFG48 = HybridConfig(histogram=HistogramConfig(range_minutes=48.0),
                     use_arima=False)
CFG240 = HybridConfig(use_arima=False)

# Sub-millisecond inter-arrival grid: 2**-16 minutes ~ 0.9 ms.
SUBMS = 2.0 ** -16


def _trace(times, duration):
    return Trace(specs=None, times=[np.asarray(t, np.float64) for t in times],
                 duration_minutes=float(duration))


def bursty_subms_multiweek(n_apps: int = 24, seed: int = 5) -> Trace:
    """Two-week trace of apps each active inside its own <=4h neighborhood.

    Absolute timestamps sit deep into the trace (t ~ 2e4 minutes) while the
    inter-arrival structure goes down to sub-millisecond — absolute times
    need ~31 significant bits, far beyond float32, so an un-rebased float32
    engine scrambles the IATs. After per-app rebasing every time is a
    2**-16-minute multiple below 2**8 minutes (24 significant bits): exactly
    float32-representable, hence exact cold-count parity. Pair with CFG48.

    App mix per residue class: dense sub-ms bursts with multi-minute
    inter-burst gaps / OOB-heavy (> 48 min IATs) / sub-``min_samples``
    (1–4 events) / keep-alive-boundary riders (IATs exactly on the standard
    keep-alive and on bin edges +- one sub-ms grid step).
    """
    rng = np.random.default_rng(seed)
    times = []
    for i in range(n_apps):
        # coarse 1/8-minute start anywhere in the first 13 days
        t0 = rng.integers(0, int((MINUTES_14D - 400.0) * 8)) / 8.0
        kind = i % 4
        if kind == 0:
            # bursts of ~8 sub-ms-spaced events, gaps of 1..40 min between
            iats = []
            for _ in range(4):
                iats.extend(rng.integers(1, 64, 7) * SUBMS)   # 15us..1ms-ish
                iats.append(float(rng.integers(64, 2560)) / 64.0)
            iats = np.asarray(iats[:-1])
        elif kind == 1:
            # mostly OOB for the 48-minute histogram range
            iats = rng.integers(49 * 64, 60 * 64, 4) / 64.0
        elif kind == 2:
            n_ev = int(rng.integers(1, 5))
            iats = rng.integers(1, 40 * 64, max(n_ev - 1, 0)) / 64.0
        else:
            # exact boundary riders: standard keep-alive (48.0) and bin
            # edges hit dead-on and missed by one sub-ms grid step
            iats = np.asarray([48.0, 48.0 + SUBMS, 1.0, 1.0 - SUBMS,
                               1.0 + SUBMS, 2.0, 48.0 - SUBMS, 3.0, 3.0,
                               3.0, 3.0])
        offsets = np.concatenate([[0.0], np.cumsum(iats)])
        assert offsets[-1] < 256.0, "span must stay float32-exact on the grid"
        times.append(t0 + offsets)
    return _trace(times, MINUTES_14D)


def coarse_twoweek(n_apps: int = 32, seed: int = 9) -> Trace:
    """Two-week full-span trace on the 1/64-minute grid (21 significant
    bits: float32-exact even before rebasing). Mixes concentrated bimodal
    apps (histogram windows activate), near-uniform apps (low CV -> standard
    keep-alive), OOB-heavy apps, and Poisson-ish apps. Pair with CFG48."""
    rng = np.random.default_rng(seed)
    times = []
    for i in range(n_apps):
        kind = i % 4
        n_ev = int(rng.integers(16, 48))
        if kind == 0:      # bimodal: concentrated -> high CV -> windows
            iats = np.where(rng.uniform(size=n_ev - 1) < 0.5, 10.0, 30.0)
            iats = iats + rng.integers(-8, 8, n_ev - 1) / 64.0
        elif kind == 1:    # spread quasi-uniform -> low CV -> standard
            iats = rng.integers(1 * 64, 47 * 64, n_ev - 1) / 64.0
        elif kind == 2:    # OOB-heavy
            iats = rng.integers(49 * 64, 300 * 64, n_ev - 1) / 64.0
        else:              # short-gap machine traffic
            iats = rng.integers(8, 12 * 64, n_ev - 1) / 64.0
        t = np.concatenate([[rng.integers(0, 64 * 64) / 64.0],
                            np.cumsum(iats)])
        t = t[t < MINUTES_14D - 1.0]
        times.append(np.sort(t))
    return _trace(times, MINUTES_14D)


def synthesized_small(n_apps: int = 64, seed: int = 7) -> Trace:
    """Padded-only ``WorkloadSpec.uniform`` trace (native float32
    timestamps — trivially exact in every engine; ``min_events=1`` keeps the
    legacy every-app-invoked guarantee). Pair with CFG240."""
    return WorkloadSpec.uniform(n_apps, days=3.0, seed=seed, max_events=16,
                                min_events=1).materialize()


GOLDEN_TRACES = {
    # name -> (constructor, config)
    "bursty_subms_multiweek": (bursty_subms_multiweek, CFG48),
    "coarse_twoweek": (coarse_twoweek, CFG48),
    "synthesized_small": (synthesized_small, CFG240),
}


def cluster_small_fleet():
    """The cluster golden: a small azure-like fleet on 6 workers.

    Pins the scalar per-event oracle's per-app cold %, wasted GB-minutes
    and latency percentiles (``tests/golden/cluster_small.json``); the
    conformance suite replays BOTH cluster engines against it. ARIMA stays
    on so the golden covers the forecaster path; the budget is infinite
    because the vectorized engine models the no-eviction regime.
    """
    from repro.core.experiment import HybridSpec
    from repro.core.workload_spec import azure_like
    from repro.serving.cluster_vector import ClusterSpec

    workload = azure_like(120, days=0.25, seed=17, max_events=24)
    policy = HybridSpec()
    cluster = ClusterSpec(n_workers=6, hbm_budget_bytes=float("inf"))
    return workload, policy, cluster


def cluster_oversubscribed_fleet():
    """The eviction-regime cluster golden: a flash-crowd fleet whose model
    images are inflated ~40x, so three 30 GB workers stay oversubscribed
    and the soonest-keep-alive-expiry eviction path runs constantly.

    Pins the scalar per-event oracle's trajectory INCLUDING per-worker
    eviction counters (``tests/golden/cluster_oversub.json``); the
    conformance suite replays BOTH cluster engines against it, so the
    vectorized fixed-point eviction replay is anchored to a checked-in
    oracle run, not just to a live oracle of the same code age.
    """
    import dataclasses

    from repro.core.experiment import HybridSpec
    from repro.core.workload_spec import flash_crowd
    from repro.serving.apptable import AppTable
    from repro.serving.cluster_vector import ClusterSpec

    table = AppTable.from_spec(
        flash_crowd(96, days=0.25, seed=23, max_events=32))
    # ~40x the Azure-like allocated-memory column: single images stay under
    # the 30 GB per-worker budget (construction guard) but each worker's
    # assigned set oversubscribes it several times over.
    wb = np.minimum((table.memory_mb * 2 ** 20 * 40).astype(np.int64),
                    np.int64(24e9))
    table = dataclasses.replace(table, weight_bytes=wb)
    policy = HybridSpec()
    cluster = ClusterSpec(n_workers=3, hbm_budget_bytes=30e9)
    return table, policy, cluster
