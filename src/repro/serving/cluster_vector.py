"""Vectorized fleet-scale cluster engine (the columnar ClusterSim).

The per-event oracle in :mod:`repro.serving.cluster_sim` replays one global
heap-merged event stream through per-worker warm pools — exact, but ~10^5
events/s of pure Python. This module computes the *same trajectory* from the
columnar :class:`repro.serving.apptable.AppTable` in three array passes:

  A. **Merged events.** Flatten the padded time frame to one event list,
     rank it by the oracle's ``(t, app_idx)`` sort, and draw the shared
     per-rank hedging uniforms so both engines see identical stragglers.

  B. **Policy windows.** The windows an app's pool consults after event
     ``k`` depend only on that app's end-time column — not on warm/cold
     outcomes — so a chunked ``lax.scan`` of
     :func:`repro.core.policy_math.hybrid_gap_step_math` over gaps
     differenced and binned in float64 on the host (the Pallas engine's
     gap columns; the device sees int32/float32 only) yields every per-gap
     residency bound up front. Apps whose out-of-bounds counter ever
     trips the ARIMA gate are recomputed through the scalar policy (same
     post-pass idiom as ``simulator._run_hybrid_sweep``).

  C. **Gap replay.** With windows known, each inter-arrival gap closes in
     closed form: keep-alive expiries and pre-warm fires happen at the
     first *worker tick* (any arrival on that worker) past the scheduled
     time, found with one ``searchsorted`` per worker. Cold verdicts,
     loads/unloads, residency time, latency, and per-worker stats all fall
     out as segmented reductions.

  D. **HBM evictions to a fixed point.** Workers whose assigned image
     bytes exceed the budget (a cheap pessimistic screen — everyone else
     skips this phase entirely) replay their per-worker occupancy in the
     oracle's processing order (expiries, pre-warm fires, request loads,
     end-of-request unloads, phase-ordered exactly as
     ``WarmPool.tick``/``on_request`` interleave them). One int64 table
     for the call holds every risky worker's running image bytes at two
     positions a tick — after its expiries and fires, after its request's
     load — and exposes every over-budget load. Each violation is resolved
     the way ``WarmPool._ensure_budget`` would — evict resident, unpinned
     apps in ``(unload_at, app_id)`` order until the load fits — then the
     occupancy is patched *in place* (an eviction only removes residency
     between the eviction and the victim's next arrival, which flips cold)
     and the walk goes on to the next over-budget position. Because an
     eviction never adds occupancy before the violation that caused it,
     the walk is monotone and the schedule converges to the oracle's in at
     most one resolution per ``_ensure_budget`` call that evicts; its work
     follows the violations and the images resident at each, not the
     worker's events.

Spans and counters (``repro.telemetry``; spans are recorded only under a
profiler, on the calling thread): ``cluster.merge`` (phase A and the
end-time frame phase B reads); per policy-window chunk ``cluster.bucket``
(the chunk's row copy), ``cluster.gap_columns``, ``cluster.put`` (the
host-to-device put and the scan's dispatch) and ``cluster.pull`` (waiting
for the scan, the copy back and the scatter into the window arrays);
``cluster.replay`` (phase C), ``cluster.evict`` (phase D: the screen and
every worker's fixed point) and ``cluster.results`` (loads and unloads,
latencies, per-worker stats). Counters, per call: ``cluster.events``
(events replayed), ``cluster.evictions``, ``cluster.evict_rounds``
(fixed-point resolutions), ``cluster.evict_candidates`` (candidate images
examined over those resolutions) and ``cluster.risky_workers`` (workers
past the phase-D screen).

Exactness contract (enforced by ``tests/test_cluster_conformance.py``):
cold counts, per-app cold %, latencies and every
load/unload/prewarm/**eviction** counter are *bit-identical* to the oracle
— including on oversubscribed fleets where HBM pressure evicts (the
fig_cluster 18x16 GB scenario, flash-crowd eviction storms); resident
byte-seconds agree to float64 accumulation-order tolerance. A
``max_eviction_rounds`` escape hatch caps the fixed-point work; past it the
front door falls back to ``engine="scalar"`` with a warning instead of
silently diverging. Like the oracle's ``WarmPool``, construction refuses a
single image larger than the per-worker budget outright.
"""
from __future__ import annotations

import dataclasses
import heapq
import warnings
from functools import partial
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..core import policy_math
from ..core.experiment import (FixedSpec, HybridSpec, NoUnloadSpec,
                               PolicySpec, SpesSpec, as_spec)
from ..core.simulator import (DEFAULT_APP_CHUNK, _chunked_buckets,
                              _gap_columns, _put_gaps, _step_config_for,
                              _window_sequences_scan)
from ..core.workload import Trace
from ..core.workload_spec import WorkloadSpec
from ..runtime.straggler import HedgePolicy
from .apptable import AppTable
from .cluster_sim import MINUTE, ClusterConfig, ClusterResult, ClusterSim
from .registry import (BASE_LOAD_LATENCY, COMPILE_MISS_LATENCY,
                       H2D_BANDWIDTH)

__all__ = ["CLUSTER_ENGINES", "ClusterSpec", "ClusterSweep",
           "EvictionRoundsExceeded", "as_table", "run_cluster",
           "sweep_cluster"]

CLUSTER_ENGINES = ("auto", "vector", "scalar")


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """Declarative cluster shape: the third axis of an experiment grid.

    Mirrors :class:`repro.serving.cluster_sim.ClusterConfig` knob-for-knob
    (same defaults) as a frozen spec, so ``trace x policy x cluster`` grids
    compose through ``experiment.run(..., cluster=...)`` and
    ``experiment.sweep(..., clusters=[...])``.
    """
    n_workers: int = 18
    hbm_budget_bytes: float = 16e9
    balancing: str = "affinity"          # "affinity" | "hash"
    hedge: Optional[HedgePolicy] = None
    checkpoint_at_minute: Optional[float] = None
    label: Optional[str] = None

    @property
    def name(self) -> str:
        return self.label or f"{self.balancing}-{self.n_workers}w"

    def validate(self) -> None:
        if self.n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {self.n_workers}")
        if self.balancing not in ("affinity", "hash"):
            raise ValueError(f"unknown balancing {self.balancing!r}; "
                             "use 'affinity' or 'hash'")

    def to_config(self) -> ClusterConfig:
        """The oracle's mutable config (the ``engine="scalar"`` bridge)."""
        return ClusterConfig(
            n_workers=self.n_workers, hbm_budget_bytes=self.hbm_budget_bytes,
            hedge=self.hedge, checkpoint_at_minute=self.checkpoint_at_minute,
            balancing=self.balancing)


def as_table(workload, *, exec_s=None, memory_mb=None,
             weight_bytes=None) -> AppTable:
    """Coerce the workload axis: AppTable passes through, WorkloadSpec and
    Trace are converted columnar."""
    if isinstance(workload, AppTable):
        return workload
    if isinstance(workload, WorkloadSpec):
        return AppTable.from_spec(workload, exec_s=exec_s,
                                  memory_mb=memory_mb,
                                  weight_bytes=weight_bytes)
    if isinstance(workload, Trace):
        return AppTable.from_trace(workload, exec_s=exec_s,
                                   memory_mb=memory_mb,
                                   weight_bytes=weight_bytes)
    raise TypeError(f"expected an AppTable, WorkloadSpec or Trace, "
                    f"got {type(workload).__name__}")


# --------------------------------------------------------------------------
# Phase B: per-gap policy windows from the end-time columns
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(2,))
def _hybrid_windows_scan(gaps, knobs: policy_math.HybridStepConfig,
                         n_bins: int):
    """Scan the hybrid step over one chunk's end-time gap columns
    (``simulator._gap_columns``: differenced and binned in float64 on the
    host, so the device computes in int32/float32 only).

    ``knobs`` is the config with ``n_bins`` taken out (``None``): its
    leaves are traced, so a new margin, percentile or threshold runs the
    scan already compiled; ``n_bins`` sizes the histogram and is static.
    Returns the residency bounds decided *at* each event (they govern the
    following gap) and the sticky any-step out-of-bounds-heavy flag that
    routes an app to the ARIMA post-pass.
    """
    cfg = knobs._replace(n_bins=n_bins)
    load, unload, total, oob = _window_sequences_scan(gaps, cfg)
    return load, unload, jnp.any(
        policy_math.oob_heavy(total, oob, cfg.oob_threshold), axis=1)


@partial(jax.jit, static_argnums=(2, 3))
def _hybrid_windows_scan_sharded(gaps, knobs: policy_math.HybridStepConfig,
                                 n_bins: int, mesh):
    """:func:`_hybrid_windows_scan` partitioned along the app axis of
    ``mesh`` (outputs carry apps on axis 0; the knobs are replicated). No
    collectives — shard outputs concatenate in fixed device order,
    bit-identical to the unsharded scan."""
    from ..distributed.scaleout import shard_along_apps
    fn = lambda g, k: _hybrid_windows_scan(g, k, n_bins)
    return shard_along_apps(fn, mesh, (0, None), 0)(gaps, knobs)


@jax.jit
def _spes_windows_scan(e_min, knobs: policy_math.SpesStepConfig):
    """Scan the fused SPES-predictor step over one chunk's end-time
    columns, emitting the residency bounds decided *at* each event (knob
    leaves are [1, 1] columns; the config axis is squeezed away)."""
    n = e_min.shape[0]
    dt = e_min.dtype
    init = (
        jnp.full((n,), -jnp.inf, dt),                       # prev end time
        jnp.zeros((1, n), jnp.float32),                     # EW mean
        jnp.zeros((1, n), jnp.float32),                     # EW residual var
        jnp.zeros((n,), jnp.int32),                         # observations
        jnp.zeros((1, n), dt),                              # load bound
        jnp.broadcast_to(knobs.standard_keep.astype(dt), (1, n)),
        jnp.zeros((1, n), jnp.int32),                       # cold (unused)
        jnp.zeros((1, n), dt),                              # waste (unused)
    )

    def body(carry, t_col):
        out = policy_math.fused_spes_step_math(t_col, *carry, cfg=knobs)
        return out, (out[4][0], out[5][0])

    _, (load_seq, unload_seq) = jax.lax.scan(body, init, e_min.T)
    return load_seq.T, unload_seq.T


@partial(jax.jit, static_argnums=(2,))
def _spes_windows_scan_sharded(e_min, knobs: policy_math.SpesStepConfig,
                               mesh):
    """:func:`_spes_windows_scan` partitioned along the app axis of
    ``mesh`` (knobs replicate; outputs carry apps on axis 0)."""
    from ..distributed.scaleout import shard_along_apps
    fn = lambda ts, ks: _spes_windows_scan(ts, ks)
    return shard_along_apps(fn, mesh, (0, None), 0)(e_min, knobs)


def _policy_windows(table: AppTable, spec: PolicySpec, e_min2d: np.ndarray,
                    counts: np.ndarray, app_chunk: int, devices=None):
    """(load_at, unload_at) bounds [n, M] decided after each event.

    Bounds are float64 minutes past the execution end — exactly the values
    ``policy_math.window_bounds`` hands the oracle's warm pool (float32
    window values widen exactly; keep-alive is recovered as their float64
    difference, which is how ``AppHistogram.windows`` defines it).
    """
    n, m_ev = e_min2d.shape
    la = np.zeros((n, m_ev))
    ua = np.zeros((n, m_ev))
    if isinstance(spec, NoUnloadSpec):
        ua[:] = np.inf
        return la, ua
    if isinstance(spec, FixedSpec):
        ua[:] = float(spec.keep_alive)
        return la, ua
    if isinstance(spec, SpesSpec):
        from ..core.simulator import _spes_knobs
        from ..distributed import scaleout
        cfg = spec.to_config()
        knobs = _spes_knobs([cfg])
        mesh = scaleout.mesh_for(devices)
        ua[:] = cfg.standard_keep_alive   # zero-event rows: never read
        with jax.enable_x64(True):
            for sel, sub in _chunked_buckets(e_min2d, counts, app_chunk):
                if mesh is None:
                    la_seq, ua_seq = _spes_windows_scan(
                        jnp.asarray(sub, jnp.float64), knobs)
                else:
                    padded = scaleout.pad_app_rows(
                        np.ascontiguousarray(sub, np.float64),
                        mesh.devices.size)
                    la_seq, ua_seq = _spes_windows_scan_sharded(
                        jax.device_put(padded,
                                       scaleout.app_sharding(mesh, 2)),
                        knobs, mesh)
                k = len(sel)
                width = sub.shape[1]
                la[sel, :width] = np.asarray(la_seq)[:k]
                ua[sel, :width] = np.asarray(ua_seq)[:k]
        return la, ua
    if not isinstance(spec, HybridSpec):
        raise TypeError(
            f"the vectorized cluster engine needs a declarative PolicySpec "
            f"(Fixed/NoUnload/Hybrid/Spes), got {type(spec).__name__}; "
            f"arbitrary Policy objects run on engine='scalar'")

    from ..distributed import scaleout
    hybrid = spec.to_config()
    cfg = _step_config_for(hybrid)
    knobs = cfg._replace(n_bins=None)
    mesh = scaleout.mesh_for(devices)
    ua[:] = hybrid.standard_keep_alive       # zero-event rows: never read
    heavy = np.zeros(n, bool)
    chunks = _chunked_buckets(e_min2d, counts, app_chunk)
    while True:
        with telemetry.span("cluster.bucket"):
            chunk = next(chunks, None)
        if chunk is None:
            break
        sel, sub = chunk
        with telemetry.span("cluster.gap_columns"):
            gaps = _gap_columns(sub, cfg.bin_minutes, cfg.n_bins)[0]
        with telemetry.span("cluster.put"):
            gaps = _put_gaps(gaps, mesh)
            if mesh is None:
                la_seq, ua_seq, flag = _hybrid_windows_scan(
                    gaps, knobs, cfg.n_bins)
            else:
                la_seq, ua_seq, flag = _hybrid_windows_scan_sharded(
                    gaps, knobs, cfg.n_bins, mesh)
        with telemetry.span("cluster.pull"):
            k = len(sel)
            width = sub.shape[1]
            la[sel, :width] = np.asarray(la_seq)[:k]
            ua[sel, :width] = np.asarray(ua_seq)[:k]
            heavy[sel] = np.asarray(flag)[:k]

    # Forecast post-pass: the fused step carries no forecaster, so any app
    # whose OOB counter ever looked heavy (a superset of "the ARIMA branch
    # was ever consulted") replays through the batched forecasting
    # subsystem — one rescan plus one grid ARIMA fit over every flagged
    # (app, event) window, bit-identical to stepping the stateful scalar
    # policy through each event.
    if hybrid.use_arima and heavy.any():
        from ..forecast.replay import hybrid_window_sequences
        rows = np.nonzero(heavy)[0]
        la_r, ua_r = hybrid_window_sequences(
            e_min2d[rows], counts[rows].astype(np.int64), hybrid,
            app_chunk=app_chunk)
        la[rows] = la_r
        ua[rows] = ua_r
    return la, ua


# --------------------------------------------------------------------------
# Phase C: closed-form gap replay
# --------------------------------------------------------------------------


def _first_tick_ge(ticks_by_w, woff, tick_src, worker_q, thr_q):
    """First worker tick at time >= threshold, per query.

    ``ticks_by_w`` holds every arrival time grouped by worker (sorted within
    each group); a keep-alive expiry or pre-warm only *happens* when some
    event on that worker ticks the pool. Returns ``(time, flat_idx)`` with
    ``(inf, -1)`` when no tick qualifies. Queries are grouped by worker so
    each group is one exact float64 ``searchsorted`` — no scaled-offset key
    tricks that could round two distinct times together.
    """
    q_order = np.argsort(worker_q, kind="stable")
    wq = worker_q[q_order]
    tq = thr_q[q_order]
    n_workers = len(woff) - 1
    qoff = np.zeros(n_workers + 1, np.int64)
    np.cumsum(np.bincount(wq, minlength=n_workers), out=qoff[1:])
    t_sorted = np.full(tq.shape, np.inf)
    i_sorted = np.full(tq.shape, -1, np.int64)
    for w in range(n_workers):
        a, b = qoff[w], qoff[w + 1]
        if b == a:
            continue
        seg = ticks_by_w[woff[w]:woff[w + 1]]
        if not len(seg):
            continue
        pos = np.searchsorted(seg, tq[a:b], side="left")
        ok = pos < len(seg)
        pos_c = np.minimum(pos, len(seg) - 1)
        t_sorted[a:b] = np.where(ok, seg[pos_c], np.inf)
        i_sorted[a:b] = np.where(ok, tick_src[woff[w] + pos_c], -1)
    t_out = np.empty_like(t_sorted)
    i_out = np.empty_like(i_sorted)
    t_out[q_order] = t_sorted
    i_out[q_order] = i_sorted
    return t_out, i_out


class EvictionRoundsExceeded(RuntimeError):
    """The eviction fixed point ran past ``max_eviction_rounds``.

    Raised by the worker replay; :func:`run_cluster` catches it and falls
    back to ``engine="scalar"`` with a warning rather than spinning (or
    silently diverging from) the oracle's sequential eviction cascade.
    """


def _app_tie_ranks(table: AppTable) -> np.ndarray:
    """Eviction tie-break keys matching the oracle's heap order.

    ``WarmPool._ensure_budget`` pops ``(unload_at, app_id)`` tuples, so
    equal expiries tie-break on the app-id *string*. Canonical
    ``app-%06d`` ids compare in index order while they are 6 digits wide;
    wider fleets (and explicit non-canonical ids) get their true
    lexicographic rank.
    """
    n = table.n_apps
    if table.app_ids is not None:
        ids = np.asarray(table.app_ids)
    elif n > 1_000_000:          # "app-1000000" sorts before "app-999999"
        ids = np.array([table.app_id(i) for i in range(n)])
    else:
        return np.arange(n, dtype=np.int64)
    ranks = np.empty(n, np.int64)
    ranks[np.argsort(ids)] = np.arange(n)
    return ranks


def _eviction_tables(risky, woff, tick_src, budget, *, rows, t_flat, wb,
                     tie, cold, stay, fired, pre, need_u, need_f, ui_stay,
                     ui_fire, tau_i, u_stay, q_fire, p_pre):
    """One occupancy table for every risky worker of a call (phase D's
    set-up). Yields ``(j_idx, kw)`` per risky worker, in ``risky`` order:
    ``j_idx`` its flat events in rank order (phase C's ``tick_src``) and
    ``kw`` the keyword arrays :func:`_evict_worker` takes.

    The oracle's processing order puts every memory op at one of the
    worker's event ranks (its *tick*): keep-alive expiries and pre-warm
    fires at the tick that triggers them, a request's load and its
    pre-warm branch's end-of-request unload at its own. Within a tick,
    ``WarmPool.tick`` / ``on_request`` run expiries, then fires by
    ``(prewarm_at, app_id)``, then the request, then the unload. Only
    fires and cold loads are checked against the budget, so the table
    holds two positions a tick: ``2 t`` after the tick's expiries and
    fires, ``2 t + 1`` after its request's load. ``occ`` there is the
    running int64 image bytes of the worker (per-tick sums of whole bytes,
    so exact in any order).

    A fire block's occupancy only grows from fire to fire, so a tick's
    fires can only go over budget if the whole block does; only those
    ticks' fires are sorted by ``(prewarm_at, app_id)`` (``blocks``, one
    group per over-budget fire block, in position order).
    """
    segs = [(int(woff[w]), int(woff[w + 1])) for w in risky]
    ticks = np.concatenate([tick_src[a:b] for a, b in segs])
    m = ticks.size
    toff = np.zeros(len(segs) + 1, np.int64)
    np.cumsum([b - a for a, b in segs], out=toff[1:])
    on = np.zeros(rows.size, bool)
    on[ticks] = True
    tpos = np.empty(rows.size, np.int64)      # flat event -> its tick
    tpos[ticks] = np.arange(m)

    ui = np.where(stay, ui_stay, ui_fire)
    sched = need_u | need_f                   # a scheduled unload
    g_exp = np.flatnonzero(on & sched & (ui >= 0))
    tk_exp = tpos[ui[g_exp]]
    g_fire = np.flatnonzero(on & fired)
    tk_fire = tpos[tau_i[g_fire]]
    n_fire = np.bincount(tk_fire, minlength=m)
    app_t = rows[ticks]
    w_t = wb[app_t]
    cold_t = cold[ticks]
    unload = np.where(pre[ticks], w_t, 0)
    unload[toff[1:] - 1] = 0                  # never across workers
    # Whole bytes: the float64 per-tick sums are exact.
    wf = wb.astype(np.float64)
    delta = np.empty((m, 2), np.int64)
    delta[:, 0] = (np.bincount(tk_fire, weights=wf[rows[g_fire]],
                               minlength=m)
                   - np.bincount(tk_exp, weights=wf[rows[g_exp]],
                                 minlength=m))
    delta[1:, 0] -= unload[:-1]               # the previous request's
    delta[:, 1] = np.where(cold_t, w_t, 0)
    delta = delta.ravel()
    first = 2 * toff
    delta[first[1:-1]] -= np.add.reduceat(delta, first[:-1])[:-1]
    occ = np.cumsum(delta)

    # Over-budget positions as the table stands: fire blocks, cold loads.
    cap = int(np.floor(budget))
    occ2 = occ.reshape(m, 2)
    f_on = (n_fire > 0) & (occ2[:, 0] > cap)
    viol = np.flatnonzero(np.column_stack([f_on, cold_t
                                           & (occ2[:, 1] > cap)]))
    on_fire = f_on[tk_fire]
    blocks = g_fire[on_fire]
    o = np.lexsort((tie[rows[blocks]], p_pre[blocks], tk_fire[on_fire]))
    blocks = blocks[o]
    boff = np.zeros(int(f_on.sum()) + 1, np.int64)
    np.cumsum(n_fire[f_on], out=boff[1:])

    # Resident spans, one per active gap, in table positions: from the
    # load (its own request, or its fire) to the scheduled expiry (before
    # that tick's fires), the next arrival (a warm continuation) or the
    # worker's end; ``-1`` for a gap that holds nothing.
    start = 2 * tpos + 1
    start[g_fire] = 2 * tk_fire
    active = on & (stay | fired)
    end = np.empty(rows.size, np.int64)
    end[ticks] = np.repeat(first[1:], np.diff(toff))
    end[~active] = -1
    end[g_exp] = 2 * tk_exp
    warm = active & ~sched
    end[warm] = 2 * tpos[np.flatnonzero(warm) + 1] + 1
    u_time = np.where(stay, u_stay, q_fire)

    vb = np.searchsorted(viol, first)
    bb = np.searchsorted(np.flatnonzero(f_on), toff)
    for i in range(len(segs)):
        yield ticks[toff[i]:toff[i + 1]], dict(
            t0=int(toff[i]), occ=occ, viol=viol[vb[i]:vb[i + 1]],
            blocks=blocks, block_at=boff[bb[i]:bb[i + 1] + 1], tpos=tpos,
            start=start, end=end, u_time=u_time, warm=warm, rows=rows,
            t_flat=t_flat, wb=wb, tie=tie, cold=cold)


def _evict_worker(j_idx, budget, *, t0, occ, viol, blocks, block_at,
                  tpos, start, end, u_time, warm, rows, t_flat, wb, tie,
                  cold, max_rounds):
    """Exact HBM-eviction replay for one worker (phase D).

    ``j_idx`` holds the worker's flat event indices in rank order, its
    ticks ``t0 + 0, 1, ...`` of the call's table ``occ``
    (:func:`_eviction_tables`); ``viol`` are its over-budget positions
    there and ``blocks[block_at[b]:block_at[b + 1]]`` the fires of its
    ``b``-th over-budget fire block. Each over-budget load (a fire, in
    ``(prewarm_at, app_id)`` order within its block, or a cold request)
    is resolved like ``WarmPool._ensure_budget``:
    resident spans covering it are candidates, evicted in ``(unload_at,
    app_id)`` order until the load fits (or counted as a budget overflow
    when nothing evictable remains). An eviction removes the victim's
    occupancy only between the load and the victim's span end — its next
    arrival (flipped to a cold load, in place in ``cold``) or scheduled
    expiry — so the patch is a slice subtraction.

    Occupancy only falls, and a request only turns into a check at a
    victim's next arrival, after the load: so the over-budget positions
    are walked once, with a heap of the requests turned cold, each
    re-tested when reached. The candidates are kept as the walk advances:
    each tick's gap joins a pool once the walk passes its request, and
    leaves it once its span ended or was evicted; the pool holds about one
    gap per app of the worker, not the worker's events.

    Returns ``(evicted_local, evict_time_local, overflows, rounds)``.
    """
    cap = int(np.floor(budget))      # whole bytes: occ > budget iff > cap
    rounds = overflows = examined = 0
    victims, times = [], []
    walk = viol.tolist()
    turned = []                      # requests turned cold: a heap
    pool = np.zeros(0, np.int64)
    pooled = 0                       # local ticks whose gaps joined
    n_block = 0
    i = 0

    def resolve(cand, excess, v, t_v):
        """Evict ``cand``'s soonest expiries until ``excess`` bytes are
        freed; the bytes freed."""
        nonlocal rounds, overflows, examined
        rounds += 1
        if rounds > max_rounds:
            raise EvictionRoundsExceeded(
                f"eviction fixed point exceeded max_eviction_rounds="
                f"{max_rounds} on one worker")
        examined += len(cand)
        a_c = rows[cand]
        w_c = wb[a_c]
        o = np.lexsort((tie[a_c], u_time[cand]))
        k = int(w_c[o].cumsum().searchsorted(excess)) + 1
        if k > len(cand):
            k = len(cand)
            overflows += 1
        freed = 0
        for g, w_g in zip(cand[o[:k]].tolist(), w_c[o[:k]].tolist()):
            e = int(end[g])
            occ[v:e] -= w_g
            end[g] = -1              # gone: never a candidate again
            freed += w_g
            victims.append(g)
            times.append(t_v)
            if warm[g]:
                # The victim's next arrival finds the image gone: cold.
                cold[g + 1] = True
                heapq.heappush(turned, e)
        return freed

    while True:
        if turned and (i == len(walk) or turned[0] < walk[i]):
            v = heapq.heappop(turned)
        elif i < len(walk):
            v = walk[i]
            i += 1
        else:
            break
        t = v // 2 - t0
        if not v % 2:
            block = blocks[block_at[n_block]:block_at[n_block + 1]]
            n_block += 1
        if int(occ[v]) <= cap:
            continue
        if t > pooled:
            pool = np.concatenate([pool, j_idx[pooled:t]])
            pooled = t
        pool = pool[end[pool] > v]
        older = pool[start[pool] < v]
        t_v = float(t_flat[j_idx[t]])
        if v % 2:                    # a cold request's load
            a_v = rows[j_idx[t]]
            resolve(older[rows[older] != a_v], int(occ[v]) - cap, v, t_v)
            continue
        # A fire block: its fires in order, each on the bytes before it.
        w_b = wb[rows[block]]
        used = int(occ[v]) - int(w_b.sum())
        for j, (g, w_g) in enumerate(zip(block.tolist(), w_b.tolist())):
            used += w_g
            if used <= cap:
                continue
            cand = np.concatenate([older, block[:j]])
            cand = cand[end[cand] > v]       # not evicted since
            used -= resolve(cand[rows[cand] != rows[g]], used - cap, v, t_v)
    telemetry.count("cluster.evict_candidates", examined)
    evicted = np.zeros(len(j_idx), bool)
    evict_t = np.zeros(len(j_idx))
    local = tpos[np.asarray(victims, np.int64)] - t0
    evicted[local] = True
    evict_t[local] = times
    return evicted, evict_t, overflows, rounds


def _run_vector(table: AppTable, spec: PolicySpec, cluster: ClusterSpec,
                app_chunk: int, devices=None,
                max_eviction_rounds: Optional[int] = None) -> ClusterResult:
    n = table.n_apps
    n_workers = cluster.n_workers
    counts = np.asarray(table.counts, np.int64)
    t_end = float(table.duration_minutes) * MINUTE

    budget = float(cluster.hbm_budget_bytes)
    if np.isfinite(budget) and n and table.weight_bytes.max() > budget:
        i_big = int(np.argmax(table.weight_bytes))
        raise ValueError(
            f"endpoint {table.app_id(i_big)!r} needs "
            f"{int(table.weight_bytes[i_big])} bytes but the HBM budget is "
            f"{budget:.0f}: a single image larger than the budget can "
            f"never fit (evicting everything still leaves the pool over "
            f"budget forever)")

    # ---- Phase A: the merged event stream -------------------------------
    with telemetry.span("cluster.merge"):
        m_ev = table.times.shape[1]
        valid = np.arange(m_ev)[None, :] < counts[:, None]
        rows, cols = np.nonzero(valid)          # row-major: (app, k) order
        n_events = len(rows)
        t_flat = table.times[rows, cols].astype(np.float64) * MINUTE
        order = np.lexsort((rows, t_flat))      # oracle sort: (t, app_idx)
        rank = np.empty(n_events, np.int64)
        rank[order] = np.arange(n_events)

        x_flat = table.exec_s[rows].astype(np.float64)
        if cluster.hedge is not None and n_events:
            u1, u2 = cluster.hedge.event_uniforms(n_events)
            x_flat = np.asarray(cluster.hedge.latency_from_uniforms(
                x_flat, u1[rank], u2[rank]), np.float64)
        e_flat = t_flat + x_flat
        e_min_flat = e_flat / MINUTE

        e_min2d = np.full((n, m_ev), np.inf)
        e_min2d[rows, cols] = e_min_flat
    telemetry.count("cluster.events", n_events)

    # ---- Phase B: policy windows per gap --------------------------------
    la2d, ua2d = _policy_windows(table, spec, e_min2d, counts, app_chunk,
                                 devices=devices)

    # ---- Phase C: closed-form gap replay --------------------------------
    with telemetry.span("cluster.replay"):
        la = la2d[rows, cols]
        ua = ua2d[rows, cols]
        ka_sec = (ua - la) * MINUTE             # == keep_alive * MINUTE

        assign = table.worker_assignment(n_workers, cluster.balancing)
        w_flat = assign[rows]
        tick_src = np.lexsort((t_flat, w_flat))  # per-worker sorted arrivals
        ticks_by_w = t_flat[tick_src]
        woff = np.zeros(n_workers + 1, np.int64)
        np.cumsum(np.bincount(w_flat, minlength=n_workers), out=woff[1:])

        last = cols == counts[rows] - 1
        first = cols == 0
        nxt = np.full(n_events, np.inf)
        nxt[~last] = t_flat[np.nonzero(~last)[0] + 1]

        stay = la <= 0.0                        # keep loaded through the gap
        u_stay = e_flat + ua * MINUTE           # expiry schedule (stay)
        p_pre = e_flat + la * MINUTE            # pre-warm schedule (else)

        # Stay branch: unloaded at the first tick past the expiry — which
        # exists whenever the next arrival is cold; the run end finalizes
        # the last gap when no tick ever reaches it.
        need_u = stay & ((nxt >= u_stay) | last)
        ut_stay = np.full(n_events, np.inf)
        ui_stay = np.full(n_events, -1, np.int64)
        ut_stay[need_u], ui_stay[need_u] = _first_tick_ge(
            ticks_by_w, woff, tick_src, w_flat[need_u], u_stay[need_u])

        # Pre-warm branch: unloaded immediately at the execution end; the
        # fire happens at the first tick past the schedule unless the app's
        # own next arrival (which cancels the pre-warm) comes first.
        pre = ~stay
        tau = np.full(n_events, np.inf)
        tau_i = np.full(n_events, -1, np.int64)
        tau[pre], tau_i[pre] = _first_tick_ge(
            ticks_by_w, woff, tick_src, w_flat[pre], p_pre[pre])
        fired = pre & np.isfinite(tau) & (last | (tau <= nxt))
        q_fire = tau + ka_sec                   # post-fire expiry schedule
        need_f = fired & ((nxt >= q_fire) | last)
        ut_fire = np.full(n_events, np.inf)
        ui_fire = np.full(n_events, -1, np.int64)
        ut_fire[need_f], ui_fire[need_f] = _first_tick_ge(
            ticks_by_w, woff, tick_src, w_flat[need_f], q_fire[need_f])

        # Cold verdicts: event k is cold iff gap k-1 lost the image.
        next_cold = np.where(stay, nxt >= u_stay,
                             np.where(fired, nxt >= q_fire, True))
        cold = np.empty(n_events, bool)
        cold[first] = True
        not_first = np.nonzero(~first)[0]
        cold[not_first] = next_cold[not_first - 1]

    # ---- Phase D: HBM evictions to a fixed point ------------------------
    with telemetry.span("cluster.evict"):
        # Cheap pessimistic screen first: a worker whose assigned apps all
        # fit at once can never evict; only workers past the sum test replay
        # their exact processing-order occupancy (and most find no
        # violation).
        wb = table.weight_bytes.astype(np.float64)
        evicted = np.zeros(n_events, bool)
        evict_time = np.zeros(n_events)
        overflow_w = np.zeros(n_workers, np.int64)
        active = counts > 0
        risky, rounds = np.zeros(0, np.int64), 0
        if np.isfinite(budget) and n_events:
            per_w_assigned = np.bincount(assign[active], weights=wb[active],
                                         minlength=n_workers)
            risky = np.nonzero(per_w_assigned > budget)[0]
            if len(risky):
                max_rounds = (max_eviction_rounds if max_eviction_rounds
                              is not None else np.inf)
                tables = _eviction_tables(
                    risky, woff, tick_src, budget, rows=rows, t_flat=t_flat,
                    wb=np.asarray(table.weight_bytes, np.int64),
                    tie=_app_tie_ranks(table), cold=cold, stay=stay,
                    fired=fired, pre=pre, need_u=need_u, need_f=need_f,
                    ui_stay=ui_stay, ui_fire=ui_fire, tau_i=tau_i,
                    u_stay=u_stay, q_fire=q_fire, p_pre=p_pre)
                for w, (j_w, kw) in zip(risky, tables):
                    ev_l, evt_l, n_over, used = _evict_worker(
                        j_w, budget, max_rounds=max_rounds - rounds, **kw)
                    evicted[j_w] = ev_l
                    evict_time[j_w] = evt_l
                    overflow_w[w] = n_over
                    rounds += used
    telemetry.count("cluster.risky_workers", len(risky))
    telemetry.count("cluster.evict_rounds", rounds)
    telemetry.count("cluster.evictions", int(evicted.sum()))

    with telemetry.span("cluster.results"):
        # Loads and unloads (time, worker, bytes) for residency + stats. An
        # evicted span's scheduled expiry never happens — its unload is the
        # eviction itself, at the evicting load's tick time.
        sched_u = need_u & ~evicted
        sched_f = need_f & ~evicted
        load_m = [cold, fired]
        load_t = [t_flat[cold], tau[fired]]
        unload_m = [pre, sched_u, sched_f, evicted]
        # Expiries missing their tick are finalized at the run end.
        unload_t = [e_flat[pre],
                    np.where(np.isfinite(ut_stay[sched_u]),
                             ut_stay[sched_u], t_end),
                    np.where(np.isfinite(ut_fire[sched_f]),
                             ut_fire[sched_f], t_end),
                    evict_time[evicted]]

        lw = np.concatenate([w_flat[m] for m in load_m]) if n_events else \
            np.zeros(0, np.int64)
        uw = np.concatenate([w_flat[m] for m in unload_m]) if n_events else \
            np.zeros(0, np.int64)
        lr = np.concatenate([rows[m] for m in load_m]) if n_events else \
            np.zeros(0, np.int64)
        ur = np.concatenate([rows[m] for m in unload_m]) if n_events else \
            np.zeros(0, np.int64)
        lb = wb[lr]
        ub = wb[ur]
        lt = np.concatenate(load_t) if n_events else np.zeros(0)
        ut = np.concatenate(unload_t) if n_events else np.zeros(0)

        n_loads = np.bincount(lr, minlength=n)
        n_unloads = np.bincount(ur, minlength=n)
        if not np.array_equal(n_loads, n_unloads):  # pragma: no cover
            raise AssertionError("cluster_vector invariant violated: "
                                 "per-app loads != unloads")

        # ---- Results ----------------------------------------------------
        base_cold = BASE_LOAD_LATENCY + wb / H2D_BANDWIDTH
        start_lat = np.where(
            cold,
            base_cold[rows] + np.where(first, COMPILE_MISS_LATENCY, 0.0),
            0.0)
        lat = np.empty(n_events)
        lat[rank] = start_lat + x_flat          # oracle (arrival) order

        cold_per_app = np.bincount(rows, weights=cold.astype(np.float64),
                                   minlength=n)
        inv = counts.astype(np.float64)
        # Per-app first, per-worker second: the load/unload time sums cancel
        # within each app's handful of events instead of across the fleet,
        # keeping resident time at float64 accumulation accuracy.
        res_app = (np.bincount(ur, weights=ut * ub, minlength=n)
                   - np.bincount(lr, weights=lt * lb, minlength=n))
        resident_bs = np.bincount(assign, weights=res_app,
                                  minlength=n_workers)

        stats = []
        cold_w = np.bincount(w_flat[cold], minlength=n_workers)
        warm_w = (np.bincount(w_flat, minlength=n_workers) - cold_w)
        fire_w = np.bincount(w_flat[fired], minlength=n_workers)
        unl_w = np.bincount(uw, minlength=n_workers)   # includes evictions
        evict_w = np.bincount(w_flat[evicted], minlength=n_workers)
        moved_w = np.bincount(lw, weights=lb, minlength=n_workers)
        for w in range(n_workers):
            stats.append(dict(
                cold_starts=int(cold_w[w]), warm_starts=int(warm_w[w]),
                prewarms=int(fire_w[w]), unloads=int(unl_w[w]),
                evictions=int(evict_w[w]),
                budget_overflows=int(overflow_w[w]),
                bytes_moved=float(moved_w[w]),
                resident_byte_seconds=float(resident_bs[w])))

        restored = (cluster.checkpoint_at_minute is not None
                    and n_events > 0 and bool(np.any(
                        t_flat >= cluster.checkpoint_at_minute * MINUTE)))
    return ClusterResult(
        cold_pct_per_app=100.0 * cold_per_app / np.maximum(inv, 1),
        latencies_s=lat,
        wasted_gb_minutes=float(resident_bs.sum()) / 1e9 / 60.0,
        stats_per_worker=stats,
        restored_mid_run=restored)


# --------------------------------------------------------------------------
# Front door
# --------------------------------------------------------------------------


def run_cluster(workload, policy, cluster: Optional[ClusterSpec] = None, *,
                engine: str = "auto", app_chunk: Optional[int] = None,
                devices=None, max_eviction_rounds: Optional[int] = None,
                exec_s=None, memory_mb=None,
                weight_bytes=None) -> ClusterResult:
    """Run one workload x policy x cluster cell.

    ``workload`` is an :class:`AppTable`, ``WorkloadSpec`` or ``Trace``
    (``exec_s``/``memory_mb``/``weight_bytes`` fill in per-app metadata the
    workload itself does not carry). ``engine="auto"`` picks the vectorized
    engine — including on oversubscribed fleets, where HBM evictions are
    replayed to a fixed point; ``"scalar"`` runs the per-event oracle on
    the same table. ``max_eviction_rounds`` (an ``EngineOptions``-style
    execution knob; default unlimited) caps the total fixed-point
    resolutions — past it the run falls back to the scalar oracle with a
    warning instead of spinning. ``devices`` shards the policy-window
    scan's app rows (see :mod:`repro.distributed.scaleout`; results stay
    bit-identical).
    """
    if engine not in CLUSTER_ENGINES:
        raise ValueError(f"unknown cluster engine {engine!r}; expected one "
                         f"of {CLUSTER_ENGINES}")
    cluster = cluster if cluster is not None else ClusterSpec()
    cluster.validate()
    spec = as_spec(policy)
    table = as_table(workload, exec_s=exec_s, memory_mb=memory_mb,
                     weight_bytes=weight_bytes)
    if engine != "scalar":
        try:
            return _run_vector(table, spec, cluster,
                               app_chunk or DEFAULT_APP_CHUNK,
                               devices=devices,
                               max_eviction_rounds=max_eviction_rounds)
        except EvictionRoundsExceeded as e:
            warnings.warn(
                f"{e}; falling back to engine='scalar' (raise "
                f"max_eviction_rounds to keep the vectorized engine)",
                RuntimeWarning, stacklevel=2)
    sim = ClusterSim(table.to_registry(), spec, cluster.to_config())
    return sim.run(table.to_trace())


@dataclasses.dataclass
class ClusterSweep:
    """A (T, S, C) grid: policy x cluster sweeps over T workloads.

    ``results[t][s][c]`` is the :class:`ClusterResult` of workload ``t``
    under policy spec ``s`` on cluster shape ``c`` — each cell identical to
    the corresponding single :func:`run_cluster` call.
    """
    tables: List[AppTable]
    specs: List[PolicySpec]
    clusters: List[ClusterSpec]
    results: List[List[List[ClusterResult]]]

    @property
    def shape(self):
        return (len(self.tables), len(self.specs), len(self.clusters))

    def row(self, t: int, s: int, c: int = 0) -> ClusterResult:
        return self.results[t][s][c]


def sweep_cluster(workloads: Union[Sequence, object], specs: Sequence,
                  clusters: Optional[Sequence[ClusterSpec]] = None, *,
                  engine: str = "auto", app_chunk: Optional[int] = None,
                  devices=None,
                  max_eviction_rounds: Optional[int] = None) -> ClusterSweep:
    """Evaluate the full workload x policy x cluster grid.

    Each workload is converted to a columnar :class:`AppTable` ONCE and
    reused across every (policy, cluster) cell.
    """
    if not isinstance(workloads, (list, tuple)):
        workloads = [workloads]
    specs = [as_spec(s) for s in specs]
    clusters = list(clusters) if clusters is not None else [ClusterSpec()]
    if not specs or not clusters or not len(workloads):
        raise ValueError("sweep_cluster needs at least one workload, one "
                         "PolicySpec and one ClusterSpec")
    tables = [as_table(w) for w in workloads]
    results = [[[run_cluster(tab, s, c, engine=engine, app_chunk=app_chunk,
                             devices=devices,
                             max_eviction_rounds=max_eviction_rounds)
                 for c in clusters] for s in specs] for tab in tables]
    return ClusterSweep(tables=tables, specs=specs, clusters=clusters,
                        results=results)
