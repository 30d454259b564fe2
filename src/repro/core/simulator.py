"""Trace-driven cold-start simulator engines (Section 5.1 of the paper).

The public front door lives in :mod:`repro.core.experiment` —
``run(trace, spec)`` / ``sweep(trace, specs)`` over declarative
:class:`~repro.core.experiment.PolicySpec` grids. This module holds the
engines those drive, all computing their decisions through the
single-source policy math in :mod:`repro.core.policy_math`:

  * :func:`simulate_scalar` — event-driven reference. Walks each app's
    invocation sequence, querying any :class:`repro.core.policy.Policy`
    (including the full hybrid policy with its ARIMA path). This is the
    float64 oracle and handles arbitrary policies.

  * the vectorized sweep engines (:func:`_run_fixed_sweep` /
    :func:`_run_hybrid_sweep`): all apps advance together through a
    ``lax.scan`` over padded event indices, and S stacked policy
    configurations advance together along a *traced config axis* — the
    trace is bucketed, chunked and scanned ONCE for the whole
    grid. The hybrid scan is factored (see
    :class:`repro.core.policy_math.HybridSweepBlock`): histogram
    sufficient statistics are carried once per distinct histogram shape,
    percentile windows / gates once per distinct variant, so a
    CV-threshold grid pays one histogram update per step, not S. Apps are
    bucketed by event count so a handful of very chatty apps do not
    inflate the scan length for everyone, and each bucket is chunked over
    apps with double-buffered host→device transfer so ~1M-app traces fit
    in device memory. ARIMA cannot run inside a scan; apps that may have
    consulted the forecaster (any out-of-bounds idle time) are replayed per
    config through the batched forecasting subsystem and their results
    overridden (the paper: ARIMA governs ~0.7% of invocations).

  * On TPU the sweep scan runs as a Pallas kernel
    (:func:`repro.kernels.histogram.hybrid_sweep_scan_pallas`) in
    float32 that walks each chunk's event columns itself, the state held
    in VMEM, with the per-config knobs delivered as an SMEM config block
    via scalar prefetch; ``engine="pallas"`` exercises it in interpret
    mode elsewhere (decided from the backend at call time).

Float32 exactness (the TPU story): TPUs have no float64, so the Pallas
engine carries float32 state. Absolute timestamps on a multi-week
trace (t ~ 2e4 minutes) cannot hold sub-minute inter-arrival structure in
float32. The Pallas driver therefore never sends the kernel a clock: on the
host it differences each app's events in float64 (the oracle's own
arithmetic), classifies every gap into its histogram bin there, and sends
the bin codes plus the idle time rounded down and up to float32
(``_gap_columns``). Window bounds are float32 values, so comparing the
rounded-down idle time with the load bound and the rounded-up one with the
unload bound gives the oracle's warm verdict exactly; cold counts, windows
and histograms match the oracle bit for bit, and waste is within one float32
ulp per gap. Trailing waste is reconstructed afterward in float64 from
the trace's clock. The decision layer itself (percentile thresholds,
windows, CV gate) is dtype-invariant by construction — see
:mod:`repro.core.policy_math`.

Exactly as in the paper, function execution time is simulated as 0 (so idle
time == inter-arrival time) to account wasted memory time conservatively, and
the first invocation of every app is a cold start.

The legacy module-level ``simulate*`` entry points were removed after their
deprecation cycle (they raise an ``AttributeError`` pointing at
``experiment.run``); all code goes through ``experiment.run``/``sweep``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from . import policy_math
from .policy import (FixedKeepAlivePolicy, HybridConfig, HybridHistogramPolicy,
                     Policy, is_warm, loaded_idle_time)
from .workload import Trace

__all__ = [
    "SimResult", "simulate_scalar", "BUCKET_EDGES", "DEFAULT_APP_CHUNK",
]

BUCKET_EDGES = (64, 512, 4096, 1 << 62)

# Apps per device-resident chunk of the hybrid scan: bounds the cumulative
# count state ([chunk, n_bins]) regardless of fleet size. Sweeps divide it
# by the config-axis length so total device state stays bounded too.
DEFAULT_APP_CHUNK = 131072
_MIN_AUTO_CHUNK = 4096

# Elements of one row block of the host gap columns (``_gap_columns``):
# large enough that the Python part of a block's numpy calls, which holds
# the GIL, is small beside their work on the pool's threads, and small
# enough to keep a block's float64 temporaries to a few MiB.
_GAP_BLOCK = 1 << 19


@dataclasses.dataclass
class SimResult:
    cold: np.ndarray            # [n_apps] cold-start counts
    invocations: np.ndarray     # [n_apps] invocation counts
    wasted_minutes: np.ndarray  # [n_apps] loaded-but-idle memory time
    # Final per-app policy windows (None for engines/paths that predate the
    # conformance harness; filled by all engines here).
    final_prewarm: Optional[np.ndarray] = None     # [n_apps] float64
    final_keep_alive: Optional[np.ndarray] = None  # [n_apps] float64

    @property
    def cold_pct(self) -> np.ndarray:
        return 100.0 * self.cold / np.maximum(self.invocations, 1)

    def cold_pct_percentile(self, q: float = 75.0) -> float:
        return float(np.percentile(self.cold_pct, q))

    @property
    def total_wasted(self) -> float:
        return float(self.wasted_minutes.sum())

    @property
    def always_cold_fraction(self) -> float:
        # Only apps that were actually invoked can be always-cold; apps with
        # zero invocations trivially satisfy cold >= invocations (0 >= 0) and
        # must not inflate the fraction (paper Fig. 12 counts invoked apps).
        invoked = self.invocations > 0
        if not invoked.any():
            return 0.0
        return float(np.mean(self.cold[invoked] >= self.invocations[invoked]))


# --------------------------------------------------------------------------
# Scalar reference engine
# --------------------------------------------------------------------------

def simulate_scalar(trace: Trace, policy: Policy,
                    include_trailing: bool = True,
                    app_indices: Optional[Sequence[int]] = None) -> SimResult:
    idx = range(trace.n_apps) if app_indices is None else app_indices
    n = trace.n_apps
    cold = np.zeros(n, np.int64)
    inv = np.zeros(n, np.int64)
    waste = np.zeros(n, np.float64)
    final_pre = np.zeros(n, np.float64)
    final_keep = np.zeros(n, np.float64)
    for i in idx:
        t = trace.events(i)
        app = trace.app_id(i)
        inv[i] = len(t)
        w = policy.windows(app)
        if len(t):
            cold[i] += 1  # first invocation is always cold
            w = policy.on_invocation(app, None)
            for k in range(1, len(t)):
                it = float(t[k]) - float(t[k - 1])  # exec time = 0 => IT == IAT
                if not is_warm(it, w):
                    cold[i] += 1
                waste[i] += loaded_idle_time(it, w)
                w = policy.on_invocation(app, it)
            if include_trailing:
                tail_gap = trace.duration_minutes - float(t[-1])
                waste[i] += loaded_idle_time(tail_gap, w) if tail_gap > 0 else 0.0
        final_pre[i], final_keep[i] = w.prewarm, w.keep_alive
    return SimResult(cold, inv, waste, final_pre, final_keep)


# --------------------------------------------------------------------------
# Vectorized JAX engines — fixed keep-alive family
# --------------------------------------------------------------------------

def _fixed_step(keep_alive, carry, t_now):
    # ``keep_alive`` is [S, 1]: S stacked configs broadcast against the [n]
    # time column; cold/waste carries are [S, n], the clock stays [n].
    prev_t, cold, waste = carry
    valid = jnp.isfinite(t_now)
    it = t_now - prev_t
    first = ~jnp.isfinite(prev_t)
    warm = policy_math.warm_from_bounds(it, 0.0, keep_alive)
    is_cold = valid & (first | ~warm)
    gap_waste = jnp.where(valid & ~first,
                          policy_math.idle_from_bounds(it, 0.0, keep_alive),
                          0.0)
    new_prev = jnp.where(valid, t_now, prev_t)
    return (new_prev, cold + is_cold, waste + gap_waste), None


@partial(jax.jit, static_argnums=(3,))
def _fixed_scan(times, keep_alive, duration, include_trailing: bool):
    """Scan one event-count bucket for S stacked keep-alive values.

    times: [n, width]; keep_alive: [S, 1] (traced — new grid points never
    retrace). Returns (cold [S, n], waste [S, n]).
    """
    n = times.shape[0]
    S = keep_alive.shape[0]
    tdtype = times.dtype
    init = (jnp.full((n,), -jnp.inf, tdtype),
            jnp.zeros((S, n), jnp.int32), jnp.zeros((S, n), tdtype))
    (last_t, cold, waste), _ = jax.lax.scan(
        partial(_fixed_step, keep_alive), init, times.T)
    if include_trailing:
        tail = jnp.maximum(duration - last_t, 0.0)
        waste = waste + jnp.where(
            jnp.isfinite(last_t),
            policy_math.idle_from_bounds(tail, 0.0, keep_alive), 0.0)
    return cold, waste


@partial(jax.jit, static_argnums=(3, 4))
def _fixed_scan_sharded(times, keep_alive, duration, include_trailing: bool,
                        mesh):
    """:func:`_fixed_scan` partitioned along the app axis of ``mesh``.

    Per-shard programs are row slices of the single-device scan (no
    collectives; keep-alive knobs and the duration replicate), so the
    concatenated outputs are bit-identical. The mesh is a hashable static:
    one compilation per (mesh, shapes), same as the unsharded path.
    """
    from ..distributed.scaleout import shard_along_apps
    fn = lambda ts, ks, dur: _fixed_scan(ts, ks, dur, include_trailing)
    return shard_along_apps(fn, mesh, (0, None, None), -1)(
        times, keep_alive, duration)


def _run_fixed_sweep(trace: Trace, keeps: Sequence[float],
                     include_trailing: bool = True, *,
                     padded=None, devices=None) -> dict:
    """S fixed keep-alive configs in one pass (``inf`` == never unload).

    float64 time state: two-week traces (t ~ 2e4 minutes) lose the
    sub-millisecond IAT bits in float32, flipping warm/cold verdicts
    exactly at the keep-alive boundary vs the scalar oracle.
    ``padded`` is the trace's precomputed ``to_padded()`` pair — the
    experiment layer prepares each trace once and reuses it across every
    policy family and config (and, in a trace-axis sweep, the whole grid).
    ``devices`` shards each bucket's app rows (see
    :mod:`repro.distributed.scaleout`; results stay bit-identical).
    """
    from ..distributed import scaleout
    times, counts = padded if padded is not None else trace.to_padded()
    S, n = len(keeps), trace.n_apps
    mesh = scaleout.mesh_for(devices)
    cold = np.zeros((S, n), np.int64)
    waste = np.zeros((S, n), np.float64)
    with jax.enable_x64(True):
        ks = jnp.asarray(np.asarray(keeps, np.float64)[:, None])
        dur = jnp.float64(trace.duration_minutes)
        for sel, sub in _buckets(times, counts):
            sub = np.ascontiguousarray(sub, np.float64)
            if mesh is None:
                c, w = _fixed_scan(jnp.asarray(sub), ks, dur,
                                   include_trailing)
            else:
                sub = scaleout.pad_app_rows(sub, mesh.devices.size)
                dev = jax.device_put(sub, scaleout.app_sharding(mesh, 2))
                c, w = _fixed_scan_sharded(dev, ks, dur, include_trailing,
                                           mesh)
            cold[:, sel] = np.asarray(c)[:, :len(sel)]
            waste[:, sel] = np.asarray(w)[:, :len(sel)]
    keep = np.broadcast_to(np.asarray(keeps, np.float64)[:, None],
                           (S, n)).copy()
    return dict(cold=cold, invocations=counts.astype(np.int64),
                wasted_minutes=waste, final_prewarm=np.zeros((S, n)),
                final_keep_alive=keep)


# --------------------------------------------------------------------------
# Vectorized JAX engines — SPES predictor family
# --------------------------------------------------------------------------


def _spes_knobs(cfgs) -> policy_math.SpesStepConfig:
    """Stack S predictor configs into traced [S, 1] knob columns.

    Each config goes through ``SpesStepConfig.from_host`` first, so host
    rounding (e.g. ``1 - alpha``) happens exactly once and the traced knobs
    equal the scalar policy's by construction.
    """
    ks = [policy_math.SpesStepConfig.from_host(
        alpha=c.alpha, band_margin=c.band_margin, band_sigma=c.band_sigma,
        min_samples=c.min_samples, standard_keep=c.standard_keep_alive)
        for c in cfgs]
    col = lambda xs, dt: jnp.asarray(np.asarray(xs, dt)[:, None])
    return policy_math.SpesStepConfig(
        alpha=col([k.alpha for k in ks], np.float32),
        om_alpha=col([k.om_alpha for k in ks], np.float32),
        band_margin=col([k.band_margin for k in ks], np.float32),
        band_sigma=col([k.band_sigma for k in ks], np.float32),
        min_samples=col([k.min_samples for k in ks], np.int32),
        standard_keep=col([k.standard_keep for k in ks], np.float32))


@jax.jit
def _spes_scan(times, knobs: policy_math.SpesStepConfig):
    """Scan one event-count bucket for S stacked predictor configs.

    times: [n, width]; knob leaves: [S, 1] (traced — a new grid point never
    retraces). The forecast state is float32 regardless of the time dtype
    (see ``policy_math.spes_update``); the clock and observation count are
    config-independent. Trailing waste is left to the host
    (``_absolute_results``). Returns (cold [S,n], waste [S,n], last_t [n],
    load [S,n], unload [S,n]).
    """
    n = times.shape[0]
    S = knobs.alpha.shape[0]
    tdtype = times.dtype
    init = (
        jnp.full((n,), -jnp.inf, tdtype),                  # shared clock
        jnp.zeros((S, n), jnp.float32),                    # EW mean
        jnp.zeros((S, n), jnp.float32),                    # EW residual var
        jnp.zeros((n,), jnp.int32),                        # observations
        jnp.zeros((S, n), tdtype),                         # load bound
        jnp.broadcast_to(knobs.standard_keep.astype(tdtype), (S, n)),
        jnp.zeros((S, n), jnp.int32),                      # cold
        jnp.zeros((S, n), tdtype),                         # waste
    )
    step = lambda carry, t: (
        policy_math.fused_spes_step_math(t, *carry, cfg=knobs), None)
    carry, _ = jax.lax.scan(step, init, times.T)
    (last_t, _, _, _, load, unload, cold, waste) = carry
    return cold, waste, last_t, load, unload


@partial(jax.jit, static_argnums=(2,))
def _spes_scan_sharded(times, knobs: policy_math.SpesStepConfig, mesh):
    """:func:`_spes_scan` partitioned along the app axis of ``mesh``.

    The knob columns replicate; every output carries apps on its last
    axis, so shard outputs concatenate in fixed device order —
    bit-identical to the unsharded scan (no cross-app math in the step).
    """
    from ..distributed.scaleout import shard_along_apps
    fn = lambda ts, ks: _spes_scan(ts, ks)
    return shard_along_apps(fn, mesh, (0, None), -1)(times, knobs)


def _run_spes_sweep(trace: Trace, cfgs, include_trailing: bool = True, *,
                    app_chunk: Optional[int] = None,
                    padded=None, devices=None) -> dict:
    """S SPES predictor configs over one bucketed/chunked trace pass.

    Always the float64 fused path (under x64): like the fixed family, this
    family has no per-bin state, and the float32 decision layer
    (``policy_math.spes_update`` rounds once from a float64 computation)
    makes the scan oracle-exact — so the "pallas" engine aliases it.
    ``devices`` shards each chunk's app rows like the other sweep engines.
    """
    from ..distributed import scaleout
    times, counts = padded if padded is not None else trace.to_padded()
    S, n = len(cfgs), trace.n_apps
    mesh = scaleout.mesh_for(devices)
    ndev = 1 if mesh is None else mesh.devices.size
    knobs = _spes_knobs(cfgs)
    cold = np.zeros((S, n), np.int64)
    waste = np.zeros((S, n), np.float64)
    pre = np.zeros((S, n), np.float64)
    keep = np.empty((S, n), np.float64)
    for s, c in enumerate(cfgs):
        keep[s, :] = c.standard_keep_alive   # zero-event rows: never scanned
    duration = float(trace.duration_minutes)
    if app_chunk is None:
        chunk = max(DEFAULT_APP_CHUNK // max(S, 1), _MIN_AUTO_CHUNK)
    else:
        chunk = int(app_chunk)
    with jax.enable_x64(True):
        for sel, sub in _chunked_buckets(times, counts, chunk):
            sub = np.ascontiguousarray(sub, np.float64)
            if mesh is None:
                c, w, last_t, lo, ub = _spes_scan(jax.device_put(sub), knobs)
            else:
                sub = scaleout.pad_app_rows(sub, ndev)
                dev = jax.device_put(sub, scaleout.app_sharding(mesh, 2))
                c, w, last_t, lo, ub = _spes_scan_sharded(dev, knobs, mesh)
            k = len(sel)
            c, w, lo, ub = (np.asarray(x)[..., :k] for x in (c, w, lo, ub))
            last_t = np.asarray(last_t)[:k]
            t0 = np.zeros(k, np.float64)
            cold[:, sel] = c
            waste[:, sel], pre[:, sel], keep[:, sel] = _absolute_results(
                w, last_t, lo, ub, t0, duration, include_trailing)
    return dict(cold=cold, invocations=counts.astype(np.int64),
                wasted_minutes=waste, final_prewarm=pre,
                final_keep_alive=keep)


def _bucket_rows(counts: np.ndarray):
    """Yield (app_index_array, width) grouped by event count: the apps of
    each bucket and the most events any of them has."""
    lo = 0
    for edge in BUCKET_EDGES:
        sel = np.where((counts > lo) & (counts <= edge))[0]
        if len(sel):
            yield sel, int(counts[sel].max())
        lo = edge


def _buckets(times: np.ndarray, counts: np.ndarray):
    """Yield (app_index_array, trimmed_times) grouped by event count."""
    for sel, width in _bucket_rows(counts):
        yield sel, times[sel, :width]


def _chunked_rows(counts: np.ndarray, app_chunk: int):
    """Bucket by event count, then chunk each bucket over apps: yield
    (app_index_array, width) for each chunk.

    The last chunk of a bucket is ragged when the bucket size is not a
    multiple of ``app_chunk`` — every consumer below handles that, but an
    invalid chunk size is rejected loudly here rather than producing empty
    chunks downstream.
    """
    if app_chunk < 1:
        raise ValueError(
            f"app_chunk must be a positive app count, got {app_chunk}")
    for sel, width in _bucket_rows(counts):
        _check_scan_width(width)
        for lo in range(0, len(sel), app_chunk):
            yield sel[lo:lo + app_chunk], width


def _chunked_buckets(times: np.ndarray, counts: np.ndarray, app_chunk: int):
    """:func:`_chunked_rows` with each chunk's trimmed times:
    (app_index_array, times[app_index_array, :width])."""
    for sel, width in _chunked_rows(counts, app_chunk):
        yield sel, times[sel, :width]


def _check_scan_width(width: int) -> None:
    """The scaled percentile compare (policy_math) multiplies cumulative
    counts — bounded by the scan width — by PCT_SCALE in int32; guard every
    engine identically rather than overflowing silently."""
    if width > policy_math.MAX_SCALED_COUNT:
        raise ValueError(
            f"bucket scan width {width} overflows the int32 scaled "
            f"percentile compare (max {policy_math.MAX_SCALED_COUNT} "
            f"events per app)")


# --------------------------------------------------------------------------
# Vectorized JAX engines — hybrid histogram family (the sweep engine)
# --------------------------------------------------------------------------


def _cum_dtype_for(width: int):
    """Narrowest cum-count dtype for a bucket scanning ``width`` events.

    Per-app cumulative counts are bounded by the bucket's scan length, so
    short-trace buckets (the overwhelming majority of a realistic fleet) can
    carry int8/int16 state — the suffix add over [n_apps, n_bins] is the
    bandwidth hot spot of the whole simulation.
    """
    if width <= 127:
        return jnp.int8
    if width <= 32766:
        return jnp.int16
    return jnp.int32


def _step_config_for(cfg: HybridConfig) -> policy_math.HybridStepConfig:
    h = cfg.histogram
    return policy_math.HybridStepConfig.from_host(
        n_bins=h.n_bins, head_pct=h.head_percentile,
        tail_pct=h.tail_percentile, margin=h.margin,
        bin_minutes=h.bin_minutes, range_minutes=h.range_minutes,
        cv_threshold=cfg.cv_threshold, min_samples=cfg.min_samples,
        oob_threshold=cfg.oob_fraction_threshold,
        standard_keep=cfg.standard_keep_alive)


def _build_sweep_block(cfgs: Sequence[HybridConfig],
                       time_dtype) -> policy_math.HybridSweepBlock:
    """Factor S hybrid configs into the group/window/gate/config layers.

    All configs must share ``n_bins`` (the driver bands by it); within a
    band the distinct (bin_minutes, n_bins) pairs become histogram groups,
    distinct window/gate knob tuples become variants, and each config keeps
    only selector indices — see ``policy_math.HybridSweepBlock``.
    """
    base = [_step_config_for(c) for c in cfgs]
    groups, g_of = {}, []
    for c in base:
        key = (float(c.bin_minutes), int(c.n_bins))
        g_of.append(groups.setdefault(key, len(groups)))
    wvars, w_of = {}, []
    for gi, c in zip(g_of, base):
        key = (gi, int(c.head_numer), int(c.tail_numer), float(c.bin_f32),
               float(c.range_f32), float(c.margin_lo), float(c.margin_hi))
        w_of.append(wvars.setdefault(key, len(wvars)))
    tvars, t_of = {}, []
    for gi, c in zip(g_of, base):
        key = (gi, int(c.min_samples), float(c.cv_factor),
               float(c.oob_threshold))
        t_of.append(tvars.setdefault(key, len(tvars)))
    dvars, d_of = {}, []
    for c in base:
        d_of.append(dvars.setdefault(float(c.standard_keep), len(dvars)))
    col = lambda vals, dt: np.asarray(vals, dt)[:, None]
    gk, wk, tk = list(groups), list(wvars), list(tvars)
    return policy_math.HybridSweepBlock(
        g_bin_minutes=col([k[0] for k in gk], time_dtype),
        g_n_bins=col([k[1] for k in gk], np.int32),
        w_group=np.asarray([k[0] for k in wk], np.int32),
        w_head_numer=col([k[1] for k in wk], np.int32),
        w_tail_numer=col([k[2] for k in wk], np.int32),
        w_bin_f32=col([k[3] for k in wk], np.float32),
        w_range_f32=col([k[4] for k in wk], np.float32),
        w_margin_lo=col([k[5] for k in wk], np.float32),
        w_margin_hi=col([k[6] for k in wk], np.float32),
        t_group=np.asarray([k[0] for k in tk], np.int32),
        t_min_samples=col([k[1] for k in tk], np.int32),
        t_cv_factor=col([k[2] for k in tk], np.float32),
        t_oob_threshold=col([k[3] for k in tk], np.float32),
        d_standard_keep=col(list(dvars), np.float32),
        c_window=np.asarray(w_of, np.int32),
        c_gate=np.asarray(t_of, np.int32),
        c_std=np.asarray(d_of, np.int32),
    )


def _build_pallas_cfg(cfgs: Sequence[HybridConfig]):
    """Pack S configs into the (int32, float32) SMEM config blocks the
    Pallas sweep kernel reads via scalar prefetch."""
    rows_i, rows_f = [], []
    for c in cfgs:
        h = _step_config_for(c)
        rows_i.append([h.n_bins, h.head_numer, h.tail_numer, h.min_samples])
        rows_f.append([h.margin_lo, h.margin_hi, h.bin_f32, h.range_f32,
                       h.cv_factor, h.oob_threshold, h.standard_keep])
    return np.asarray(rows_i, np.int32), np.asarray(rows_f, np.float32)


def _sweep_identities(
        blk: policy_math.HybridSweepBlock) -> policy_math.SweepIdentities:
    """Static structure of a sweep block: which selector arrays are the
    identity (all of them, for a single-config run), so the traced layers
    skip those gathers — see ``policy_math.SweepIdentities``."""
    ident = lambda idx, m: (idx.shape[0] == m
                            and np.array_equal(np.asarray(idx), np.arange(m)))
    G = blk.g_n_bins.shape[0]
    W = blk.w_group.shape[0]
    T = blk.t_group.shape[0]
    D = blk.d_standard_keep.shape[0]
    return policy_math.SweepIdentities(
        w=ident(blk.w_group, G), t=ident(blk.t_group, G),
        c_window=ident(blk.c_window, W), c_gate=ident(blk.c_gate, T),
        c_std=ident(blk.c_std, D))


@partial(jax.jit, static_argnums=(2, 3, 4))
def _hybrid_sweep_scan(times, blk: policy_math.HybridSweepBlock,
                       cum_dtype, n_bins: int,
                       ids: policy_math.SweepIdentities =
                       policy_math.SweepIdentities()):
    """One factored sweep scan over a [n, width] chunk; S configs in one
    pass, config knobs traced (a new grid point never recompiles). The
    residency bounds are carried through the scan (refreshed at each app's
    events from the post-update group state — see
    ``policy_math.fused_hybrid_sweep_step_math``), so the final bounds ARE
    the windows decided at each app's last event; the init carry is
    decide(zero state) = (0, standard_keep)."""
    n = times.shape[0]
    tdtype = times.dtype
    _check_scan_width(times.shape[1])
    if blk.g_n_bins.ndim == 0:
        # Degenerate single-config block (scalar knob leaves): rank-2/1
        # state, no config axis anywhere — the layers broadcast against
        # scalars, reproducing the dedicated pre-sweep engine's program
        # (leading unit axes measurably pessimize XLA:CPU).
        layer = lambda *a: ()
    else:
        layer = lambda leaf: (leaf.shape[0],)
    gd = layer(blk.g_n_bins)
    sd = layer(blk.c_window)
    std = blk.d_standard_keep if ids.c_std else blk.d_standard_keep[blk.c_std]
    init = (
        jnp.full((n,), -jnp.inf, tdtype),                  # shared clock
        jnp.zeros(gd + (n, n_bins), cum_dtype),
        jnp.zeros(gd + (n,), jnp.int32),
        jnp.zeros(gd + (n,), tdtype),                      # cv_sum
        jnp.zeros(gd + (n,), tdtype),                      # cv_sum_sq
        jnp.zeros(sd + (n,), tdtype),                      # load bound
        jnp.broadcast_to(std.astype(tdtype), sd + (n,)),   # unload bound
        jnp.zeros(sd + (n,), jnp.int32),                   # cold
        jnp.zeros(sd + (n,), tdtype),                      # waste
    )
    step = lambda carry, t: (
        policy_math.fused_hybrid_sweep_step_math(
            t, *carry, blk=blk, ids=ids), None)
    with jax.named_scope("sweep.scan"):
        carry, _ = jax.lax.scan(step, init, times.T)
    (last_t, gcum, goob, gcv_sum, gcv_sum_sq, prewarm, unload_at,
     cold, waste) = carry
    oob_seen = goob > 0 if ids.t else (goob > 0)[blk.t_group]
    if not ids.c_gate:
        oob_seen = oob_seen[blk.c_gate]
    return cold, waste, oob_seen, last_t, prewarm, unload_at


@partial(jax.jit, static_argnums=(3, 4, 5))
def _hybrid_sweep_scan_pallas(gaps, cfg_i32, cfg_f32, n_bins: int,
                              interpret: bool = False, tile_apps: int = 1024):
    """Same sweep, walked by the Pallas TPU scan kernel (float32). The
    driver feeds each chunk as host-made gap columns ``gaps`` = (it_lo,
    it_hi, code), each [n, width] — see :func:`_gap_columns`. The config
    block rides in SMEM via scalar prefetch; per-config state is
    unfactored (grid (S, app tiles, time blocks)) in the kernel's
    lane-dense layout: apps on the last axis, [S, 1, n] vectors,
    [S, n_bins, n] cumulative counts, and the columns [width, n]. The
    kernel pads the apps to its tile and the steps to its time block
    (:func:`repro.kernels.histogram.scan_blocks`)."""
    from ..kernels import histogram

    S = cfg_i32.shape[0]
    it_lo, it_hi, code = (x.T for x in gaps)
    n = code.shape[1]
    vec = lambda dt: jnp.zeros((S, 1, n), dt)
    init = (
        jnp.zeros((S, n_bins, n), jnp.int32),
        vec(jnp.int32),
        vec(jnp.float32),
        vec(jnp.float32),
        vec(jnp.float32),                                  # prewarm
        jnp.broadcast_to(cfg_f32[:, 6:7, None], (S, 1, n)),  # unload_at
        vec(jnp.int32),
        vec(jnp.float32),
    )
    with jax.named_scope("sweep.scan"):
        (_, oob, _, _, prewarm, unload_at, cold, waste) = \
            histogram.hybrid_sweep_scan_pallas(
                it_lo, it_hi, code, init, cfg_i32, cfg_f32,
                tile_apps=tile_apps, interpret=interpret)
    return cold[:, 0], waste[:, 0], oob[:, 0] > 0, prewarm[:, 0], \
        unload_at[:, 0]


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _hybrid_sweep_scan_sharded(times, blk: policy_math.HybridSweepBlock,
                               cum_dtype, n_bins: int,
                               ids: policy_math.SweepIdentities, mesh):
    """:func:`_hybrid_sweep_scan` partitioned along the app axis of
    ``mesh``.

    The config block replicates; every output of the factored scan carries
    apps on its LAST axis, so out_axes=-1 reassembles shards in fixed
    device order — bit-identical to the unsharded scan (no collectives, no
    cross-app math anywhere in the step). Callers pad rows to a multiple
    of the mesh size (+inf rows are masked by the scan's own ``isfinite``
    gate) and slice the outputs back.
    """
    from ..distributed.scaleout import shard_along_apps
    fn = lambda ts, b: _hybrid_sweep_scan(ts, b, cum_dtype, n_bins, ids)
    return shard_along_apps(fn, mesh, (0, None), -1)(times, blk)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _hybrid_sweep_scan_pallas_sharded(gaps, cfg_i32, cfg_f32, n_bins: int,
                                      interpret: bool, tile_apps: int, mesh):
    """:func:`_hybrid_sweep_scan_pallas` partitioned along the app axis.

    Each shard pads its own rows to the kernel tile and slices them back,
    so the assembled outputs keep the driver's row count; the SMEM config
    blocks replicate."""
    from ..distributed.scaleout import shard_along_apps
    fn = lambda g, ci, cf: _hybrid_sweep_scan_pallas(
        g, ci, cf, n_bins, interpret, tile_apps)
    return shard_along_apps(fn, mesh, (0, None, None), -1)(
        gaps, cfg_i32, cfg_f32)


@functools.cache
def _gap_pool() -> ThreadPoolExecutor:
    """The host threads that build gap columns, one per usable core. Made
    on first use and kept for the life of the process."""
    return ThreadPoolExecutor(len(os.sched_getaffinity(0)),
                              thread_name_prefix="gap_columns")


def _gap_columns(times: np.ndarray, bin_minutes: float, n_bins: int,
                 rows: Optional[np.ndarray] = None,
                 width: Optional[int] = None):
    """The Pallas kernel's event columns for one chunk of times (host).

    The chunk is ``times[rows, :width]`` (every row and column where
    ``None``), read in place block by block: no copy of the chunk is
    made. Each event's idle time is differenced in float64 from the
    trace's own clock, exactly as the scalar oracle does, and classified
    there (``policy_math.encode_gaps``). The kernel gets the codes and the
    idle time as a float32 bracket ``it_lo <= it <= it_hi`` (rounded down
    and up), which keeps its warm verdicts exact against float32 window
    bounds (``policy_math.warm_from_bounds``) and its waste within one
    float32 ulp of each gap. Returns ``(it_lo, it_hi, code)``, each
    [n, width], and each app's last event time (-inf without events).

    Every element's arithmetic is independent of its block, so the blocks
    (about ``_GAP_BLOCK`` elements each) are spread over
    :func:`_gap_pool` whenever the chunk holds more than two of them.
    """
    n = len(times) if rows is None else len(rows)
    width = times.shape[1] if width is None else int(width)
    it_lo = np.empty((n, width), np.float32)
    it_hi = np.empty((n, width), np.float32)
    code = np.empty((n, width), np.int32)
    last = np.empty(n, np.float64)
    step = max(_GAP_BLOCK // max(width, 1), 1)

    def block(r0):
        r1 = min(r0 + step, n)
        src = slice(r0, r1) if rows is None else rows[r0:r1]
        clock = np.asarray(times[src, :width], np.float64)
        valid = np.isfinite(clock)
        first = np.zeros_like(valid)
        first[:, 0] = valid[:, 0]
        # +inf padding follows the last event: the subtrahend is replaced
        # there, so no inf - inf is formed (any thread may run this block,
        # and a caller's errstate does not reach it).
        gap = np.zeros_like(clock)
        np.subtract(clock[:, 1:], np.where(valid[:, 1:], clock[:, :-1], 0.0),
                    out=gap[:, 1:], where=valid[:, 1:])
        code[r0:r1] = policy_math.encode_gaps(gap, valid, first, bin_minutes,
                                              n_bins)
        lo, hi = it_lo[r0:r1], it_hi[r0:r1]
        lo[...] = gap
        hi[...] = lo
        # Rows are sorted, so gap >= 0: a float32 above it is positive and
        # one below it is not negative, and its neighbour toward the gap
        # (``np.nextafter``) is one step of its bit pattern away.
        np.subtract(lo.view(np.int32), 1, out=lo.view(np.int32),
                    where=lo > gap)
        np.add(hi.view(np.int32), 1, out=hi.view(np.int32), where=hi < gap)
        n_ev = valid.sum(axis=1)
        last[r0:r1] = np.where(
            n_ev > 0, clock[np.arange(r1 - r0), np.maximum(n_ev - 1, 0)],
            -np.inf)

    starts = range(0, n, step)
    if len(starts) > 2:
        for _ in _gap_pool().map(block, starts):
            pass
    else:
        for r0 in starts:
            block(r0)
    return (it_lo, it_hi, code), last


def _put_gaps(gaps, mesh=None):
    """Device copies of :func:`_gap_columns` output. On a mesh the app
    rows are padded to the device count with event-free rows and sharded
    along apps."""
    from ..distributed import scaleout
    gaps = [np.ascontiguousarray(g) for g in gaps]
    if mesh is None:
        return tuple(jax.device_put(g) for g in gaps)
    n = mesh.devices.size
    return tuple(
        jax.device_put(scaleout.pad_app_rows(g, n, fill),
                       scaleout.app_sharding(mesh, g.ndim))
        for g, fill in zip(gaps, (0.0, 0.0, policy_math.GAP_PAD)))


def _window_sequences_scan(gaps, cfg: policy_math.HybridStepConfig):
    """One hybrid config stepped over one chunk's gap columns
    (:func:`_gap_columns`), in the row layout: int32 and float32 only, so
    the device never sees a clock. Returns, each [n, width], the float32
    (load, unload) bounds decided at every event (they govern the
    following gap) and the in-bounds and OOB counts after it — what the
    cluster engine's and the forecast replay's window scans read."""
    it_lo, it_hi, code = gaps
    n = code.shape[0]
    f32 = jnp.float32
    init = (jnp.zeros((n, cfg.n_bins), jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), f32), jnp.zeros((n,), f32), jnp.zeros((n,), f32),
            jnp.full((n,), cfg.standard_keep, f32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), f32))

    def body(carry, column):
        lo, hi, c = column
        out = policy_math.hybrid_gap_step_math(
            lo, hi, policy_math.decode_gaps(c, cfg.n_bins), *carry, cfg=cfg,
            gather=True)
        return out, (out[4], out[5], out[0][:, -1].astype(jnp.int32),
                     out[1])

    _, seqs = jax.lax.scan(body, init, (it_lo.T, it_hi.T, code.T))
    return tuple(x.T for x in seqs)


def _absolute_results(waste, last_t, prewarm, unload_at, t0, duration,
                      include_trailing):
    """Reconstruct absolute-time results after a scan.

    Trailing waste is computed on the host in float64 from the last-event
    clock (offset by ``t0``), so the float32 engine never differences the
    large absolute timestamps. Works for [n] rows and stacked [S, n] sweeps
    (``last_t``/``t0`` broadcast along the config axis). Returns
    (waste64, prewarm64, keep64).
    """
    pre = np.asarray(prewarm, np.float64)
    ub = np.asarray(unload_at, np.float64)
    waste = np.asarray(waste, np.float64)
    if include_trailing:
        tail_gap = duration - (t0 + np.asarray(last_t, np.float64))
        waste = waste + policy_math.idle_from_bounds(tail_gap, pre, ub)
    return waste, pre, ub - pre


def _run_hybrid_sweep(trace: Trace, hybrids: Sequence[HybridConfig],
                      include_trailing: bool = True, *,
                      app_chunk: Optional[int] = None,
                      use_pallas: Optional[bool] = None,
                      tile_apps: int = 1024,
                      padded=None, devices=None) -> dict:
    """S hybrid configs over one bucketed/chunked trace pass.

    Configs are banded by bin count (so no config pays for another's wider
    histogram), but the trace preparation (``padded`` arrives precomputed
    from the experiment layer), each chunk's host→device transfer, and —
    within a band — the whole time layer and per-group histogram update are
    shared across the grid. ``use_pallas`` defaults to True on TPU (float32
    sweep kernel fed host-classified gaps) and False elsewhere (float64 jnp
    sweep, always oracle-exact). The ARIMA post-pass runs per config on
    its own forecast candidates.

    ``devices`` (None | int | "auto", see ``scaleout.mesh_for``) shards
    each chunk's app rows across a 1-D mesh: chunks are padded to a
    multiple of the mesh with masked +inf rows, ``device_put`` with a
    row sharding turns the one-chunk lookahead into per-device double
    buffering, and shard outputs concatenate in fixed device order —
    results stay bit-identical to the single-device run.
    """
    from ..distributed import scaleout
    S = len(hybrids)
    mesh = scaleout.mesh_for(devices)
    ndev = 1 if mesh is None else mesh.devices.size
    times, counts = padded if padded is not None else trace.to_padded()
    n = trace.n_apps
    cold = np.zeros((S, n), np.int64)
    waste = np.zeros((S, n), np.float64)
    pre = np.zeros((S, n), np.float64)
    keep = np.empty((S, n), np.float64)
    for s, h in enumerate(hybrids):
        keep[s, :] = h.standard_keep_alive
    oob_flags = np.zeros((S, n), bool)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    from ..kernels import interpret_mode
    interpret = interpret_mode()
    duration = float(trace.duration_minutes)

    # Band configs by histogram shape; one scan per band, shared trace
    # prep. The Pallas kernel reads gaps classified on the host, so its
    # bands also split by bin width (the classification's divisor).
    band_of = {}
    for s, h in enumerate(hybrids):
        width = _step_config_for(h).bin_minutes if use_pallas else None
        band_of.setdefault((h.histogram.n_bins, width), []).append(s)
    if app_chunk is None:
        # Bands run sequentially per chunk, so peak state scales with the
        # LARGEST band, not the whole grid. The Pallas path carries
        # per-config [S_band, chunk, n_bins] histogram state; the factored
        # jnp path carries it per GROUP, so its chunks can stay near the
        # single-config size (bigger chunks amortize per-op overhead
        # measurably on CPU).
        widest = max(len(idx) for idx in band_of.values())
        denom = widest if use_pallas else max(-(-widest // 16), 1)
        chunk = max(DEFAULT_APP_CHUNK // denom, _MIN_AUTO_CHUNK)
    else:
        chunk = int(app_chunk)
    bands = []
    for (n_bins, width), idx in sorted(band_of.items()):
        cfgs = [hybrids[s] for s in idx]
        if use_pallas:
            ci, cf = _build_pallas_cfg(cfgs)
            if mesh is None:
                fn = partial(_hybrid_sweep_scan_pallas, cfg_i32=ci,
                             cfg_f32=cf, n_bins=n_bins, interpret=interpret,
                             tile_apps=tile_apps)
            else:
                fn = lambda cur, ci=ci, cf=cf, nb=n_bins: \
                    _hybrid_sweep_scan_pallas_sharded(
                        cur, ci, cf, nb, interpret, tile_apps, mesh)
        else:
            blk = _build_sweep_block(cfgs, np.float64)
            ids = _sweep_identities(blk)
            if len(cfgs) == 1:
                # scalar knob leaves -> the scan drops the config axis
                # entirely (see _hybrid_sweep_scan)
                blk = policy_math.HybridSweepBlock(
                    *(np.asarray(x).reshape(()) for x in blk))
            if mesh is None:
                fn = lambda cur, blk=blk, nb=n_bins, ids=ids: \
                    _hybrid_sweep_scan(
                        cur, blk, _cum_dtype_for(cur.shape[1]), nb, ids)
            else:
                fn = lambda cur, blk=blk, nb=n_bins, ids=ids: \
                    _hybrid_sweep_scan_sharded(
                        cur, blk, _cum_dtype_for(cur.shape[1]), nb, ids,
                        mesh)
        bands.append((np.asarray(idx), fn, (n_bins, width)))

    def put(x):
        x = np.ascontiguousarray(x, np.float64)
        if mesh is None:
            return jax.device_put(x)
        x = scaleout.pad_app_rows(x, ndev)
        return jax.device_put(x, scaleout.app_sharding(mesh, x.ndim))

    def take(work):
        # the jnp path's row copy runs inside the generator; the Pallas
        # path's generator only picks the rows (``_gap_columns`` reads them)
        with telemetry.span("sweep.bucket"):
            return next(work, None)

    def run_all():
        # Streaming with a one-chunk lookahead: at most two chunk copies are
        # alive at once (the one scanning and the one whose host->device
        # transfer is enqueued ahead of blocking on the current result).
        # With a mesh, the row-sharded device_put enqueues one transfer PER
        # DEVICE, so the lookahead double-buffers per device.
        def prep(item):
            if not use_pallas:
                sel, sub = item
                with telemetry.span("sweep.put"):
                    cur = put(sub)
                return sel, sub.shape[1], {key: cur for _, _, key in bands}, \
                    None
            sel, width = item
            inputs, last = {}, None
            for _, _, key in bands:
                if key not in inputs:
                    with telemetry.span("sweep.gap_columns"):
                        gaps, last = _gap_columns(times, key[1], key[0],
                                                  sel, width)
                    with telemetry.span("sweep.put"):
                        inputs[key] = _put_gaps(gaps, mesh)
            return sel, width, inputs, last

        work = (_chunked_rows(counts, chunk) if use_pallas
                else _chunked_buckets(times, counts, chunk))
        pending = take(work)
        if pending is None:
            return
        pending = prep(pending)
        while pending is not None:
            sel, width, inputs, last = pending
            nxt = take(work)
            pending = None if nxt is None else prep(nxt)
            n_events = int(counts[sel].sum())
            tile = tile_apps if use_pallas else 0
            for idx, fn, key in bands:
                rows, steps = _scan_extent(len(sel), ndev, width, key[0],
                                           tile)
                telemetry.count("sweep.event_steps", len(idx) * n_events)
                telemetry.count("sweep.app_steps", len(idx) * rows * steps)
                with telemetry.span("sweep.dispatch"):
                    res = fn(inputs[key])
                # [..., :len(sel)] drops the masked mesh-padding rows (a
                # no-op on the unsharded path).
                with telemetry.span("sweep.pull"):
                    out = [np.asarray(o)[..., :len(sel)] for o in res]
                with telemetry.span("sweep.scatter"):
                    if use_pallas:      # the kernel carries no clock
                        out.insert(3, last)
                    c, w, oobh, last_t, pw, ub = out
                    at = np.ix_(idx, sel)
                    cold[at] = c
                    oob_flags[at] = oobh
                    waste[at], pre[at], keep[at] = _absolute_results(
                        w, last_t, pw, ub, 0.0, duration, include_trailing)

    if use_pallas:
        run_all()
    else:
        with jax.enable_x64(True):
            run_all()

    # Forecast post-pass: a forecaster cannot run inside the scan, so each
    # config's forecast candidates replay through the batched forecasting
    # subsystem (one fused-step rescan + one grid ARIMA fit over every
    # (app, event) window where the forecaster is consulted — bit-identical
    # to the scalar policy, see repro.forecast.replay). The candidates are
    # the apps whose OOB counter ever moved: an app can be OOB-heavy, and
    # consult the forecaster, in the middle of its trace without being so
    # at its end, and heaviness needs an OOB hit, so this is a superset of
    # the apps the forecaster governs; the replay is exact for any app.
    for s, h in enumerate(hybrids):
        if h.use_arima and oob_flags[s].any():
            from ..forecast.replay import replay_oob_apps
            aidx = np.where(oob_flags[s])[0]
            with telemetry.span("sweep.arima_replay"):
                out = replay_oob_apps(times, counts, duration, h, aidx,
                                      include_trailing)
            cold[s, aidx] = out["cold"]
            waste[s, aidx] = out["wasted_minutes"]
            pre[s, aidx] = out["final_prewarm"]
            keep[s, aidx] = out["final_keep_alive"]
    return dict(cold=cold, invocations=counts.astype(np.int64),
                wasted_minutes=waste, final_prewarm=pre,
                final_keep_alive=keep)


def _scan_extent(n: int, ndev: int, width: int, n_bins: int,
                 tile_apps: int) -> tuple:
    """(rows, steps) a scan takes through a ``width``-event chunk of ``n``
    apps. The mesh pads the rows to a multiple of its ``ndev`` devices;
    the Pallas scan kernel pads each device's rows to its app tile and the
    steps to its time block (:func:`repro.kernels.histogram.scan_blocks`).
    The jnp scan (``tile_apps`` 0) takes ``width`` steps."""
    per = -(-n // ndev)
    if not tile_apps or not per:
        return per * ndev, width
    from ..kernels.histogram import scan_blocks
    ta, tb = scan_blocks(per, width, n_bins, tile_apps)
    return -(-per // ta) * ta * ndev, -(-width // tb) * tb


# --------------------------------------------------------------------------
# Removed entry points (deprecation cycle completed in PR 3 -> PR 5)
# --------------------------------------------------------------------------

_REMOVED = {
    "simulate": "run(trace, spec)",
    "simulate_fixed_batch": "run(trace, FixedSpec(keep_alive))",
    "simulate_hybrid_batch": "run(trace, HybridSpec(...))",
    "simulate_hybrid_batch_reference": "run(trace, spec)",
}


def __getattr__(name: str):
    if name in _REMOVED:
        raise AttributeError(
            f"repro.core.simulator.{name} was removed after its deprecation "
            f"cycle; use repro.core.experiment.{_REMOVED[name]} instead "
            f"(arbitrary Policy objects still run via simulate_scalar)")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
