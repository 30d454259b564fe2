"""Plain reference of the invoker-cluster replay (the paper's §5.3).

Written from the paper (Shahrad et al., ATC'20: §4.2's hybrid policy,
§5.3's OpenWhisk invokers) and the semantics the configuration file
states, in straightforward numpy and Python: one loop over each app's
events for its windows, one event-by-event warm pool per invoker. It
imports nothing of the program under test.

* Placement is stateless: ``"hash"`` puts app ``i`` (id ``app-%06d``) on
  invoker FNV-1a-64(id) mod n; ``"affinity"`` deals apps round-robin in
  order of first arrival (ties by app index). So each invoker is
  replayed whole and on its own.
* Clock: an event at ``t`` minutes arrives at ``t * 60`` seconds and ends
  ``exec_s`` later; the policy sees end times in minutes,
  ``(t * 60 + exec_s) / 60``, and their gaps.
* Windows: after each event, the §4.2 hybrid decision over the app's
  end-time gaps (the same rules as ``bench/reference/hybrid.py``, whose
  helpers this file uses): ``(load, unload)`` as float32 values, the
  keep-alive their float64 difference.
* Warm pool, per invoker, in arrival order ``(t, app)``: at each arrival
  first every keep-alive due by then expires, then every pre-warm due by
  then fires in ``(prewarm_at, app)`` order (a fired image stays for the
  keep-alive), then the request loads its image if it is not resident
  (cold), then its end ``exec_s`` later is processed at once: with
  ``load > 0`` the image unloads and a pre-warm is set for
  ``end + load``; else it stays until ``end + unload``. Expiries and
  pre-warms happen only at arrivals on the same invoker.
* Memory: a load that would pass the budget first evicts resident images
  of other apps in ``(unload_at, app)`` order until it fits; where
  nothing is left to evict it goes over budget and is counted.
* Cold latency: ``base + bytes / bandwidth``, plus the compile miss on an
  app's first load; a request's latency adds its execution seconds.
* At the end of the trace every resident image unloads; resident
  byte-seconds sum ``(unload - load) * bytes`` in unload order.

Departures from the program's stated semantics: none intended. Pinning
(a request's image is never a victim while it executes) cannot arise,
since each request's end is processed right after its start; hedged
requests and controller checkpoints are not modelled (the configuration
has neither).

``clock=np.float32`` is the control, the precision below the float64
clock the configuration states: times, ends, schedules, windows' gaps,
latencies and residency are computed in float32.
"""
from __future__ import annotations

import heapq
from typing import Dict, Sequence

import numpy as np

from bench.reference.hybrid import PCT_SCALE, _f32, _n_bins

MINUTE = 60.0
_FNV_OFFSET, _FNV_PRIME = 0xcbf29ce484222325, 0x100000001b3
_U64 = (1 << 64) - 1
INF = float("inf")


def fnv1a64(s: str) -> int:
    h = _FNV_OFFSET
    for b in s.encode():
        h = ((h ^ b) * _FNV_PRIME) & _U64
    return h


def placement(times: np.ndarray, counts: np.ndarray, n_workers: int,
              balancing: str) -> np.ndarray:
    """Each app's invoker (apps without events: invoker 0 under
    ``"affinity"``; they make no load)."""
    n = len(counts)
    if balancing == "hash":
        return np.array([fnv1a64(f"app-{i:06d}") % n_workers
                         for i in range(n)], np.int64)
    first = np.where(counts > 0, times[:, 0], np.inf)
    order = np.lexsort((np.arange(n), first))
    out = np.zeros(n, np.int64)
    active = int((counts > 0).sum())
    out[order[:active]] = np.arange(active) % n_workers
    return out


def arrival_ranks(times: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Each event's position in arrival order ``(t * 60, app)``, flat in
    ``(app, k)`` order: where a replay's latencies of the whole fleet
    put app ``a``'s event ``k``."""
    rows = np.repeat(np.arange(len(counts)), counts)
    cols = np.arange(len(rows)) - np.repeat(np.cumsum(counts) - counts,
                                            counts)
    order = np.lexsort((rows, times[rows, cols] * MINUTE))
    rank = np.empty(len(rows), np.int64)
    rank[order] = np.arange(len(rows))
    return rank


def windows(ends: np.ndarray, counts: np.ndarray, cfg: dict,
            clock=np.float64):
    """(load, unload) [n, W] decided after each event from the end-time
    gaps ``ends`` (minutes, sorted, +inf padded) under one hybrid config
    (``bench/reference/hybrid.py``'s keys), in the clock's type."""
    clock = np.dtype(clock)
    n, W = ends.shape
    B = _n_bins(cfg)
    bin_m = clock.type(cfg["bin_minutes"])
    bin_f32 = np.float32(cfg["bin_minutes"])
    rng_f32 = np.float32(cfg["range_minutes"])
    head_num = int(round(cfg["head_percentile"] * 100))
    tail_num = int(round(cfg["tail_percentile"] * 100))
    m_lo = _f32(1.0 - cfg["margin"])
    m_hi = _f32(1.0 + cfg["margin"])
    cv_t = float(cfg["cv_threshold"])

    t = np.asarray(ends, clock)
    hist = np.zeros((n, B), np.int64)
    total = np.zeros(n, np.int64)
    sum_sq = np.zeros(n, np.int64)
    oob = np.zeros(n, np.int64)
    load = np.zeros((n, W), clock)
    unload = np.zeros((n, W), clock)
    apps = np.arange(n)

    def pct_bin(cum, numer):
        need = -(-np.maximum(total * numer, PCT_SCALE) // PCT_SCALE)
        return np.minimum(np.sum(cum < need[:, None], axis=1), B)

    for k in range(W):
        valid = k < counts
        if not valid.any():
            break
        if k:
            it = (np.where(valid, t[:, k], 0)
                  - np.where(valid, t[:, k - 1], 0)).astype(clock)
            b = np.floor(it / bin_m).astype(np.int64)
            in_b = valid & (b < B)
            at = apps[in_b], b[in_b]
            sum_sq[in_b] += 2 * hist[at] + 1
            hist[at] += 1
            total += in_b
            oob += valid & (b >= B)
        seen = total + oob
        s1 = total.astype(np.float64)
        use = ((seen >= cfg["min_samples"]) & (total > 0)
               & ~(oob > cfg["oob_threshold"] * seen)
               & (B * sum_sq >= (1.0 + cv_t * cv_t) * s1 * s1))
        cum = np.cumsum(hist, axis=1)
        h_load = _f32(pct_bin(cum, head_num)) * bin_f32 * m_lo
        h_unload = np.maximum(np.minimum(
            _f32(pct_bin(cum, tail_num) + 1) * bin_f32, rng_f32) * m_hi,
            h_load)
        load[:, k] = np.where(use, h_load, 0)
        unload[:, k] = np.where(use, h_unload, rng_f32)
    return load, unload


def replay_invoker(apps: Sequence[int], times: np.ndarray,
                   counts: np.ndarray, exec_s: np.ndarray,
                   weight_bytes: np.ndarray, duration: float, cfg: dict,
                   budget: float, costs: Dict[str, float],
                   clock=np.float64) -> dict:
    """Replay one invoker holding ``apps`` (fleet indices) under the hybrid
    config ``cfg``. ``costs``: ``base_load_s``, ``h2d_bytes_per_s``,
    ``compile_miss_s``. Returns ``cold`` [len(apps)], ``latency`` (flat,
    apps in the given order, each app's events in order), the invoker's
    counters, ``resident_byte_seconds`` and ``resident_bound`` (see
    :func:`resident_bound`)."""
    clock = np.dtype(clock)
    c = clock.type
    apps = np.asarray(apps, np.int64)
    cnt = counts[apps].astype(np.int64)
    W = max(int(cnt.max()) if len(cnt) else 1, 1)
    arrive = np.asarray(times[apps, :W], clock) * c(MINUTE)
    x = np.asarray(exec_s[apps], clock)
    end = arrive + x[:, None]
    load_w, unload_w = windows(end / c(MINUTE), cnt, cfg, clock)
    keep_w = (unload_w.astype(np.float64)
              - load_w.astype(np.float64)).astype(clock)
    as_list = (lambda a: a.tolist()) if clock == np.float64 else list

    rows = np.repeat(np.arange(len(apps)), cnt)
    cols = np.arange(len(rows)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    order = np.lexsort((apps[rows], arrive[rows, cols]))
    ev_a, ev_k = rows[order].tolist(), cols[order].tolist()
    t_ev = as_list(arrive[rows, cols][order])
    e_ev = as_list(end[rows, cols][order])
    x_ev = as_list(x[rows[order]])
    la_ev = as_list(load_w[rows, cols][order])
    ua_ev = as_list(unload_w[rows, cols][order])
    ka_ev = as_list(keep_w[rows, cols][order])
    gid = apps.tolist()
    nbytes = weight_bytes[apps].tolist()
    base = [c(costs["base_load_s"]) + c(b) / c(costs["h2d_bytes_per_s"])
            for b in nbytes]
    miss = c(costs["compile_miss_s"])
    zero, minute = c(0.0), c(MINUTE)

    m = len(apps)
    loaded = [False] * m
    cached = [False] * m
    since = [zero] * m
    unload_at = [INF] * m
    prewarm_at = [INF] * m
    keep = [zero] * m                     # keep-alive of the last windows
    ver = [0] * m                         # bumps on every schedule change
    expiries, prewarms = [], []           # (when, app id, local, ver)
    st = dict(cold_starts=0, warm_starts=0, prewarms=0, unloads=0,
              evictions=0, budget_overflows=0, bytes_moved=0)
    acc = dict(used=0, res=zero, terms=0, scale=0.0)

    def unload(a, now):
        loaded[a] = False
        dt = max(now - since[a], zero)
        acc["res"] = acc["res"] + dt * c(nbytes[a])
        acc["terms"] += 1
        acc["scale"] += abs(float(now)) * nbytes[a]
        acc["used"] -= nbytes[a]
        unload_at[a] = INF
        ver[a] += 1
        st["unloads"] += 1

    def make_room(a, now):
        need = nbytes[a]
        if acc["used"] + need <= budget:
            return
        cand = sorted((unload_at[b], gid[b], b) for b in range(m)
                      if loaded[b] and b != a)
        for _, _, b in cand:
            if acc["used"] + need <= budget:
                break
            unload(b, now)
            st["evictions"] += 1
        if acc["used"] + need > budget:
            st["budget_overflows"] += 1

    def load(a, now):
        make_room(a, now)
        lat = base[a] if cached[a] else base[a] + miss
        loaded[a] = cached[a] = True
        since[a] = now
        acc["used"] += nbytes[a]
        acc["terms"] += 1
        acc["scale"] += abs(float(now)) * nbytes[a]
        st["bytes_moved"] += nbytes[a]
        return lat

    def schedule(heap, when, a):
        ver[a] += 1
        heapq.heappush(heap, (when, gid[a], a, ver[a]))

    cold = np.zeros(m, np.int64)
    latency = np.zeros(len(ev_a), clock)
    pos = np.cumsum(cnt) - cnt
    for i, a in enumerate(ev_a):
        now = t_ev[i]
        while expiries and expiries[0][0] <= now:
            _, _, b, v = heapq.heappop(expiries)
            if v == ver[b] and loaded[b]:
                unload(b, now)
        due = []
        while prewarms and prewarms[0][0] <= now:
            _, _, b, v = heapq.heappop(prewarms)
            if v == ver[b] and not loaded[b]:
                due.append(b)
        for b in due:
            load(b, now)
            prewarm_at[b] = INF
            unload_at[b] = now + keep[b] * minute
            schedule(expiries, unload_at[b], b)
            st["prewarms"] += 1
        if loaded[a]:
            lat = zero
            st["warm_starts"] += 1
        else:
            lat = load(a, now)
            cold[a] += 1
            st["cold_starts"] += 1
        prewarm_at[a] = unload_at[a] = INF
        ver[a] += 1
        latency[pos[a] + ev_k[i]] = lat + x_ev[i]
        e, la, ka = e_ev[i], la_ev[i], ka_ev[i]
        keep[a] = ka
        load_at = la if la > 0 else zero
        if load_at <= 0:
            unload_at[a] = e + (load_at + ka) * minute
            schedule(expiries, unload_at[a], a)
        else:
            unload(a, e)
            prewarm_at[a] = e + load_at * minute
            schedule(prewarms, prewarm_at[a], a)
    t_end = c(duration) * minute
    for a in range(m):
        if loaded[a]:
            unload(a, t_end)
    st["bytes_moved"] = float(st["bytes_moved"])
    return dict(cold=cold, latency=latency.astype(np.float64), stats=st,
                resident_byte_seconds=float(acc["res"]),
                resident_bound=resident_bound(acc["terms"], acc["scale"]))


def resident_bound(terms: int, scale: float) -> float:
    """How far two float64 sums of one invoker's resident byte-seconds
    may lie apart when both start from the same float64 load and unload
    times (``L = terms`` loads and unloads, ``S = scale`` = the sum of
    ``|t| * bytes`` over them).

    One sum adds ``(t_unload - t_load) * bytes`` in unload order: each
    difference and product rounds once, and the running sum of at most
    ``L`` terms adds at most ``L`` more roundings of a partial sum no
    larger than ``S``. The other sums ``t * bytes`` per app, subtracts the
    loads' sum from the unloads' and adds the apps up: at most ``L`` terms
    per app and ``L`` apps. With ``u = eps / 2`` the two errors together
    are at most ``(3 L + 4) u S``; the bound is ``2 (L + 2) eps S``, which
    is larger and covers the ``1 / (1 - L u)`` factors of the exact
    analysis."""
    return 2.0 * (terms + 2) * float(np.finfo(np.float64).eps) * scale
