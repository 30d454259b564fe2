"""Single source of truth for the hybrid keep-alive policy math (paper §4).

Every engine in the repo — the scalar control-plane policy
(:class:`repro.core.policy.HybridHistogramPolicy` / ``AppHistogram``), the
vectorized ``lax.scan`` engines in :mod:`repro.core.simulator`, and the
Pallas TPU kernels in :mod:`repro.kernels.histogram` — computes its
decisions through the helpers below. A policy-formula change is a one-file
edit here; the conformance suite (``tests/test_engine_conformance.py``)
asserts the engines stay in exact agreement.

Mapping to the paper's §4 hybrid-policy description:

  * :func:`classify_idle_time`       — §4.2 range-limited IT histogram:
    1-minute bins up to a 4-hour range, beyond-range ITs counted as
    out-of-bounds (OOB).
  * :func:`suffix_add` / :func:`raw_count_at` — the fused engines' cumulative
    bin-count representation of that histogram (recording bin *b* is a
    suffix add over ``[b, n_bins)``, so percentiles read straight off the
    maintained prefix sums).
  * :func:`welford_update` / :func:`cv_gate` — §4.2 representativeness:
    coefficient of variation of the bin counts against its threshold, from
    accumulators maintained incrementally (:func:`bin_count_cv` reports
    the CV itself).
  * :func:`percentile_threshold_scaled` / :func:`first_bin_ge_scaled` /
    :func:`window_values` — §4.2 head/tail percentile windows: pre-warm =
    5th-percentile bin lower edge minus a 10% margin, keep-alive up to the
    99th-percentile bin upper edge plus the margin.
  * :func:`use_histogram_gate` / :func:`oob_heavy` — Fig. 10 decision tree:
    too few ITs or a too-uniform histogram (CV below threshold) falls back
    to the *standard keep-alive* (pre-warm 0, keep-alive = range); mostly
    OOB apps go to the time-series (ARIMA) path.
  * :func:`arima_window`             — §4.3 ARIMA windows: pre-warm just
    below the forecast IT, keep-alive covering a band around it.
  * :func:`warm_from_bounds` / :func:`idle_from_bounds` — §4.1 semantics:
    an invocation is warm iff it lands while the image is resident
    (``load_at <= IT <= unload_at``); loaded-but-idle time is the wasted
    memory the provider pays.

Dtype discipline (what makes the float32/TPU engines bit-match the float64
oracle):

  * The *decision layer* is dtype-invariant by construction: percentile
    thresholds are exact integer arithmetic (no float ``ceil``), the CV
    gate is three float32 products and a compare (no divide or square
    root, whose rounding differs between XLA's rewrites, the TPU and
    numpy), and the window values (``load_at`` / ``unload_at``) are always
    computed in float32 from exactly-representable integer state. Engines
    carry the resulting bounds in their own time dtype (a float32 value
    widens to float64 exactly), so warm/cold verdicts compare identical
    reals in every engine.
  * The *time layer* (inter-arrival times, waste accumulation) stays in the
    engine's dtype. The Pallas kernel receives its idle times already
    differenced and binned in float64 on the host (``encode_gaps``, a
    float32 bracket for the verdict; see ``simulator._gap_columns``).
  * Integer state must stay below 2**24 for the float32 casts to be exact
    and below 2**31 / PCT_SCALE for the scaled threshold compare; both hold
    for any trace this repo produces (per-app event counts are bounded by
    the 1-minute dataset granularity).

Helpers are polymorphic over numpy and jnp (host scalars stay numpy — the
scalar policy pays no jax dispatch overhead) and trace identically inside
``jax.lax.scan`` bodies and Pallas TPU kernel bodies. Helpers that need a
row-wise lookup take a ``gather`` flag: gathers are fast under XLA but not
Mosaic-lowerable, so Pallas bodies use the reduction forms (both forms are
asserted equivalent by the property suite).

Config knobs are *data*, not trace constants: :class:`HybridStepConfig`
packages one policy configuration into the exact dtypes the decision layer
consumes (integer percentile numerators, float32 margin factors, ...). Its
leaves may be python/numpy scalars (the scalar policy and single-config
paths) or traced arrays broadcast against the app axis — which is what lets
``repro.core.experiment.sweep`` stack S configurations into one traced
config axis and scan the trace once for the whole grid.
:func:`fused_hybrid_sweep_step_math` is that sweep step: the histogram
sufficient statistics are carried once per *distinct histogram shape*
(group layer), percentile windows once per distinct window variant, the
CV/min-samples gate once per distinct gate variant, and each of the S
configs just selects its (window, gate) pair — so a 16-point CV-threshold
grid pays for one histogram update per step, not 16.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PCT_SCALE",
    "MAX_SCALED_COUNT",
    "pct_numer",
    "scale_raw_threshold",
    "margin_factors",
    "window_bounds",
    "warm_from_bounds",
    "idle_from_bounds",
    "classify_idle_time",
    "GAP_PAD",
    "GAP_FIRST",
    "encode_gaps",
    "decode_gaps",
    "suffix_add",
    "raw_count_at",
    "welford_update",
    "bin_count_cv",
    "cv_gate_factor",
    "cv_gate_terms",
    "cv_gate",
    "percentile_threshold_scaled",
    "percentile_threshold_scaled_numer",
    "first_bin_ge_scaled",
    "first_bin_ge_scaled_grouped",
    "window_values",
    "window_values_from_factors",
    "standard_window_bounds",
    "use_histogram_gate",
    "use_histogram_gate_from_terms",
    "oob_heavy",
    "arima_window",
    "SpesStepConfig",
    "spes_update",
    "spes_window_from_counts",
    "fused_spes_step_math",
    "HybridStepConfig",
    "HybridSweepBlock",
    "SweepIdentities",
    "hybrid_gap_step_math",
    "hybrid_sweep_decide",
    "fused_hybrid_sweep_step_math",
]

# Percentiles are quantized to 1/100 of a percent and compared in exact
# integer arithmetic: ``cum >= ceil(total*pct/100)`` iff
# ``cum*PCT_SCALE >= total*pct_numer`` — no float rounding, so every engine
# derives the same percentile bin in any dtype.
PCT_SCALE = 10_000

#: Largest per-app cumulative count whose scaled compare (``cum *
#: PCT_SCALE``) still fits int32. Engines reject wider scans up front
#: (``simulator._check_scan_width``) instead of overflowing silently.
MAX_SCALED_COUNT = (2 ** 31 - 1) // PCT_SCALE


def _ns(*xs):
    """numpy for host values, jnp for traced/device values."""
    for x in xs:
        if isinstance(x, (jax.Array, jax.core.Tracer)):
            return jnp
    return np


def _f32(x):
    """Exact float32 view of a config knob, host or traced.

    Python/numpy scalars go through ``np.float32`` (the value every engine's
    decision layer compares against); traced arrays are cast — equal values
    by construction because config blocks are built host-side from the same
    python floats."""
    if isinstance(x, (jax.Array, jax.core.Tracer)):
        return x.astype(jnp.float32)
    return np.float32(x)


def _i32(x):
    """int32 view of a config knob, host or traced."""
    if isinstance(x, (jax.Array, jax.core.Tracer)):
        return x.astype(jnp.int32)
    return np.int32(x)


def _f64(x):
    """float64 view of a value, host or traced (traced callers run under
    x64 — every float64 engine scan does)."""
    if isinstance(x, (jax.Array, jax.core.Tracer)):
        return x.astype(jnp.float64)
    return np.float64(x)


# --------------------------------------------------------------------------
# Warm/cold + waste verdicts (§4.1)
# --------------------------------------------------------------------------


def window_bounds(prewarm, keep_alive):
    """(load_at, unload_at) residency offsets from the last execution end.

    ``prewarm <= 0`` means the image is never unloaded after the execution:
    it is resident on ``[0, keep_alive]``. Otherwise it is unloaded
    immediately, re-loaded at ``prewarm`` and kept until
    ``prewarm + keep_alive``.
    """
    if _both_float(prewarm, keep_alive):   # scalar control-plane fast path
        load_at = prewarm if prewarm > 0.0 else 0.0
        return load_at, load_at + keep_alive
    xp = _ns(prewarm, keep_alive)
    load_at = xp.where(prewarm > 0.0, prewarm, 0.0)
    return load_at, load_at + keep_alive


def _both_float(a, b) -> bool:
    return isinstance(a, (float, int)) and isinstance(b, (float, int))


def warm_from_bounds(it, load_at, unload_at, it_hi=None):
    """Warm iff the invocation arrives while the image is resident.

    ``it_hi`` (default ``it``) is for an idle time held as a float32
    bracket ``it <= exact <= it_hi`` (the Pallas kernel's inputs): the
    bounds are float32 values, so comparing the lower end against
    ``load_at`` and the upper end against ``unload_at`` gives exactly the
    verdict on the unrounded idle time."""
    hi = it if it_hi is None else it_hi
    return (it >= load_at) & (hi <= unload_at)


def idle_from_bounds(it, load_at, unload_at):
    """Loaded-but-idle memory time during a gap of length ``it`` (>= 0).

    The image sits idle from ``load_at`` until the arrival (or until
    ``unload_at`` if the gap outlives the keep-alive); arrivals before
    ``load_at`` never paid for a resident image.
    """
    if _both_float(it, load_at) and _both_float(it, unload_at):
        return max(min(it, unload_at) - load_at, 0.0)
    xp = _ns(it, load_at, unload_at)
    return xp.maximum(xp.minimum(it, unload_at) - load_at, 0.0)


# --------------------------------------------------------------------------
# Histogram update (§4.2)
# --------------------------------------------------------------------------


def classify_idle_time(it, active, bin_minutes: float, n_bins: int):
    """Bin an idle time: (clipped_bin, in_bounds, oob_hit)."""
    if isinstance(it, float):          # scalar control-plane fast path
        bin_idx = math.floor(it / bin_minutes)
        in_bounds = bool(active) and 0 <= bin_idx < n_bins
        oob_hit = bool(active) and bin_idx >= n_bins
        return min(max(bin_idx, 0), n_bins - 1), in_bounds, oob_hit
    xp = _ns(it, active)
    bin_idx = xp.floor(it / bin_minutes).astype(xp.int32)
    in_bounds = active & (bin_idx >= 0) & (bin_idx < n_bins)
    oob_hit = active & (bin_idx >= n_bins)
    safe = xp.clip(bin_idx, 0, n_bins - 1)
    return safe, in_bounds, oob_hit


# Gap codes: one int32 per (app, event) carrying the event's status and the
# histogram bin of the gap it closes, classified on the host in the
# oracle's own float64 (see ``encode_gaps``) for the float32 kernel.
GAP_PAD = -2        # no event (padding past the app's last event)
GAP_FIRST = -1      # the app's first event: no gap closes here


def encode_gaps(it, valid, first, bin_minutes: float, n_bins: int):
    """Gap codes of idle times ``it``: ``GAP_PAD`` / ``GAP_FIRST``, else
    the bin index of ``classify_idle_time`` with out-of-bounds gaps coded
    ``n_bins``. Works on numpy (the host, float64) and traced arrays."""
    xp = _ns(it, valid, first)
    rec = valid & ~first
    safe, in_b, _ = classify_idle_time(it, rec, bin_minutes, n_bins)
    code = xp.where(in_b, safe, n_bins)
    return xp.where(valid, xp.where(first, GAP_FIRST, code),
                    GAP_PAD).astype(xp.int32)


def decode_gaps(code, n_bins):
    """(valid, first, safe_bin, in_bounds, oob_hit) of gap codes — the
    ``classify_idle_time`` outputs the step math consumes. Traced-only."""
    valid = code != GAP_PAD
    first = code == GAP_FIRST
    in_b = (code >= 0) & (code < n_bins)
    return (valid, first, jnp.clip(code, 0, n_bins - 1), in_b,
            code >= n_bins)


def _bin_iota(cum, bin_axis: int):
    """int32 bin index of every element of ``cum`` along its bin axis."""
    return jax.lax.broadcasted_iota(jnp.int32, cum.shape, bin_axis % cum.ndim)


def _on_bins(v, cum, bin_axis: int):
    """Lift a per-app operand onto ``cum``'s bin axis.

    Per-app operands either lack the bin axis (``[n_apps]`` against
    ``[n_apps, n_bins]``) or already carry it with size 1 — the Pallas
    sweep kernel's lane-dense layout, ``[1, TA]`` against ``[n_bins, TA]``,
    where no reshape is needed (Mosaic cannot lift a rank-1 vector to
    rank 2 inside a kernel)."""
    return v if v.ndim == cum.ndim else jnp.expand_dims(v, bin_axis)


def suffix_add(cum, safe_bin, in_bounds, *, bin_axis: int = -1):
    """Record a hit at ``safe_bin`` into cumulative counts ``cum``.

    ``cum`` holds maintained prefix sums along ``bin_axis``; one
    observation is a +1 over the suffix ``[safe_bin, n_bins)``.
    Traced-only. Out-of-bounds rows start their suffix past the last bin
    (an empty mask), so the predicate is one int32 compare against the
    bin iota.
    """
    start = jnp.where(in_bounds, safe_bin, cum.shape[bin_axis])
    return cum + (_bin_iota(cum, bin_axis)
                  >= _on_bins(start, cum, bin_axis)).astype(cum.dtype)


def raw_count_at(cum, safe_bin, *, gather: bool, bin_axis: int = -1):
    """Pre-update raw count of ``safe_bin`` read off cumulative counts.

    ``gather=True`` uses row-wise dynamic indexing (fast under XLA; bins
    on the last axis); ``gather=False`` uses masked reductions along
    ``bin_axis`` (Mosaic/Pallas-lowerable; the result keeps the operand's
    rank, see :func:`_on_bins`). Both return the same int32 values.
    """
    if gather:
        take = lambda idx: jnp.take_along_axis(
            cum, idx[..., None], axis=-1)[..., 0].astype(jnp.int32)
        cum_at = take(safe_bin)
        cum_below = jnp.where(safe_bin > 0,
                              take(jnp.maximum(safe_bin - 1, 0)), 0)
        return cum_at - cum_below
    iota = _bin_iota(cum, bin_axis)
    keep = safe_bin.ndim == cum.ndim
    at = lambda b: jnp.sum(jnp.where(iota == _on_bins(b, cum, bin_axis),
                                     cum, 0), axis=bin_axis, keepdims=keep)
    return (at(safe_bin) - at(safe_bin - 1)).astype(jnp.int32)


def welford_update(cv_sum, cv_sum_sq, in_bounds, old_count):
    """O(1) update of the bin-count sum / sum-of-squares accumulators.

    A bin going ``old -> old+1`` changes the sum of squared counts by
    ``2*old + 1``. Accumulator dtype is preserved (float64 oracle, float32
    kernels); values are exact integers while below the dtype's mantissa.
    """
    if isinstance(cv_sum, float):      # scalar control-plane fast path
        inb = 1.0 if in_bounds else 0.0
        return cv_sum + inb, cv_sum_sq + inb * (2.0 * float(old_count) + 1.0)
    xp = _ns(cv_sum, cv_sum_sq)
    dt = cv_sum.dtype if hasattr(cv_sum, "dtype") else xp.float64
    inb = xp.asarray(in_bounds, dt) if xp is np else in_bounds.astype(dt)
    old = xp.asarray(old_count, dt) if xp is np else old_count.astype(dt)
    return cv_sum + inb, cv_sum_sq + inb * (2.0 * old + 1.0)


# --------------------------------------------------------------------------
# Representativeness (CV of bin counts, §4.2)
# --------------------------------------------------------------------------


def bin_count_cv(cv_sum, cv_sum_sq, n_bins: int, dtype=np.float32):
    """Coefficient of variation of the bin counts from the accumulators,
    for reporting. The decision gate never divides: see :func:`cv_gate`.
    """
    if isinstance(cv_sum, float):              # scalar paths (float64)
        mean = cv_sum / n_bins
        if mean <= 0.0:
            return 0.0
        var = max(cv_sum_sq / n_bins - mean * mean, 0.0)
        return math.sqrt(var) / max(mean, 1e-9)
    xp = _ns(cv_sum, cv_sum_sq)
    cvs = xp.asarray(cv_sum, dtype) if xp is np else cv_sum.astype(dtype)
    cvss = xp.asarray(cv_sum_sq, dtype) if xp is np else cv_sum_sq.astype(dtype)
    mean = cvs / n_bins
    var = xp.maximum(cvss / n_bins - mean * mean, dtype(0.0))
    return xp.where(mean > 0, xp.sqrt(var) / xp.maximum(mean, dtype(1e-9)),
                    dtype(0.0))


def cv_gate_factor(cv_threshold) -> np.float32:
    """``1 + t**2`` for the float32 threshold ``t``, rounded once to float32
    on the host: the constant of :func:`cv_gate`."""
    t = float(np.float32(cv_threshold))
    return np.float32(1.0 + t * t)


def cv_gate_terms(cv_sum, cv_sum_sq, n_bins):
    """The two sides' state-dependent terms of :func:`cv_gate`: float32
    ``(n_bins * Q, S * S)`` for S = sum and Q = sum of squares of the bin
    counts (exact while below 2**24). Host scalars or traced arrays."""
    if isinstance(cv_sum, float):              # scalar control-plane path
        f = np.float32
        return f(n_bins) * f(cv_sum_sq), f(cv_sum) * f(cv_sum)
    xp = _ns(cv_sum, cv_sum_sq, n_bins)
    f = lambda x: (np.asarray(x, np.float32) if xp is np
                   else jnp.asarray(x).astype(jnp.float32))
    s = f(cv_sum)
    return f(n_bins) * f(cv_sum_sq), s * s


def cv_gate(cv_sum, cv_sum_sq, n_bins, cv_factor):
    """Whether the bin counts' CV reaches the threshold ``t`` (§4.2):
    ``cv_factor`` is :func:`cv_gate_factor` of ``t``.

    With n bins, S = sum and Q = sum of squared counts, the CV is
    ``sqrt(n*Q/S**2 - 1)``, so for S > 0 ``CV >= t`` iff
    ``n*Q >= (1 + t**2) * S**2``. Evaluated as float32 products and one
    compare: no divide or square root (XLA rewrites a divide by a constant
    into a reciprocal multiply, and TPU division rounds differently from
    numpy's, which moved exact ties such as 48 samples in 48 distinct bins
    of 240), and no add after a product that a backend could fuse. Every
    backend rounds these identically, and for counts below 2**12 the test
    is exact. S = 0 passes; callers also gate on ``total > 0``.
    """
    nq, s2 = cv_gate_terms(cv_sum, cv_sum_sq, n_bins)
    return nq >= _f32(cv_factor) * s2


# --------------------------------------------------------------------------
# Percentile windows (§4.2)
# --------------------------------------------------------------------------


def pct_numer(pct: float) -> int:
    """Percentile as an exact integer numerator over PCT_SCALE."""
    return int(round(pct * (PCT_SCALE / 100.0)))


def percentile_threshold_scaled(total, pct: float):
    """Scaled percentile threshold: ``cum`` hits the pct-percentile iff
    ``cum * PCT_SCALE >= threshold`` (with the paper's floor of one
    sample). Pure integer math — dtype-invariant by construction."""
    return percentile_threshold_scaled_numer(total, pct_numer(pct))


def percentile_threshold_scaled_numer(total, numer):
    """:func:`percentile_threshold_scaled` from a precomputed integer
    numerator (``pct_numer``); ``numer`` may be a traced int32 per-config
    knob — the sweep engine's percentile axis."""
    if isinstance(total, int) and isinstance(numer, (int, np.integer)):
        return max(total * int(numer), PCT_SCALE)
    xp = _ns(total, numer)
    if xp is np:
        return np.maximum(np.int64(total) * numer, PCT_SCALE)
    return jnp.maximum(_i32(total) * _i32(numer), jnp.int32(PCT_SCALE))


def first_bin_ge_scaled(cum, thr_scaled, *, gather: bool,
                        bin_axis: int = -1):
    """First bin index where ``cum * PCT_SCALE >= thr_scaled``; ``n_bins``
    when no bin qualifies (only possible with zero in-bounds samples —
    callers gate on ``total > 0``).

    ``gather=True`` runs an O(log n_bins) binary search (XLA scan bodies);
    ``gather=False`` a masked min over the bin iota (Pallas bodies along
    ``bin_axis``, numpy host path). Identical results.
    """
    xp = _ns(cum, thr_scaled)
    n_bins = cum.shape[-1]
    if xp is np:
        cum = np.asarray(cum, np.int64)
        if cum.ndim == 1 and np.ndim(thr_scaled) == 0:
            # host fast path: cum is nondecreasing, so the masked min is a
            # binary search — cum*S >= thr iff cum >= ceil(thr/S)
            need = -(-int(thr_scaled) // PCT_SCALE)
            return int(np.searchsorted(cum, need, side="left"))
        iota = np.broadcast_to(np.arange(n_bins), cum.shape)
        hit = cum * PCT_SCALE >= np.asarray(thr_scaled)[..., None]
        return np.min(np.where(hit, iota, n_bins), axis=-1)
    if not gather:
        n_bins = cum.shape[bin_axis]
        hit = cum.astype(jnp.int32) * jnp.int32(PCT_SCALE) >= \
            _on_bins(thr_scaled, cum, bin_axis)
        return jnp.min(jnp.where(hit, _bin_iota(cum, bin_axis), n_bins),
                       axis=bin_axis, keepdims=thr_scaled.ndim == cum.ndim)
    rows_shape = cum.shape[:-1]
    lo = jnp.zeros(rows_shape, jnp.int32)
    hi = jnp.full(rows_shape, n_bins, jnp.int32)
    # search space is [0, n_bins] — n_bins + 1 candidate answers
    for _ in range(int(np.ceil(np.log2(n_bins + 1)))):
        mid = (lo + hi) // 2
        v = jnp.take_along_axis(
            cum, jnp.minimum(mid, n_bins - 1)[..., None],
            axis=-1)[..., 0].astype(jnp.int32)
        ge = (v * jnp.int32(PCT_SCALE) >= thr_scaled) & (mid < n_bins)
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, jnp.minimum(mid + 1, hi))
    return hi


def scale_raw_threshold(threshold):
    """Lift a raw *count* threshold into the scaled domain of
    :func:`first_bin_ge_scaled`: ``threshold * PCT_SCALE``, in the int32 the
    scaled compare runs in (callers guard widths via
    :data:`MAX_SCALED_COUNT`, so this never overflows).
    """
    xp = _ns(threshold)
    if xp is np:
        return np.int64(threshold) * PCT_SCALE
    return threshold.astype(jnp.int32) * jnp.int32(PCT_SCALE)


def first_bin_ge_scaled_grouped(gcum, group, thr_scaled):
    """Per-variant percentile search over *grouped* cumulative rows.

    ``gcum`` is [G, n_apps, n_bins] — one histogram state per distinct
    histogram shape; ``group`` [W] maps each window variant to its group;
    ``thr_scaled`` is [W, n_apps]. Returns the same bins as
    ``first_bin_ge_scaled(gcum[group], thr_scaled, gather=True)`` without
    materializing the [W, n_apps, n_bins] gather: each binary-search probe
    reads one [W, n_apps] slice straight out of the group state.
    """
    n_bins = gcum.shape[-1]
    cols = jnp.arange(thr_scaled.shape[-1], dtype=jnp.int32)[None, :]
    g = group[:, None].astype(jnp.int32)
    lo = jnp.zeros(thr_scaled.shape, jnp.int32)
    hi = jnp.full(thr_scaled.shape, n_bins, jnp.int32)
    for _ in range(int(np.ceil(np.log2(n_bins + 1)))):
        mid = (lo + hi) // 2
        v = gcum[g, cols, jnp.minimum(mid, n_bins - 1)].astype(jnp.int32)
        ge = (v * jnp.int32(PCT_SCALE) >= thr_scaled) & (mid < n_bins)
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, jnp.minimum(mid + 1, hi))
    return hi


def margin_factors(margin: float) -> Tuple[np.float32, np.float32]:
    """The float32 margin factors the decision layer multiplies by.

    Precomputed host-side (``1 ± margin`` rounds once, in float64, before
    the float32 cast) so a traced per-config margin axis reproduces the
    static path bit-for-bit.
    """
    return np.float32(1.0 - margin), np.float32(1.0 + margin)


def window_values(head_bin, tail_bin, bin_minutes: float,
                  range_minutes: float, margin: float):
    """(load_at, unload_at) in minutes from percentile bin indices.

    load_at   = head bin lower edge, reduced by the margin;
    unload_at = tail bin upper edge (clamped to the range), increased by
                the margin — never below load_at.
    Always computed AND returned in float32: window values are decisions,
    and float32 keeps them identical across engines (they widen to float64
    exactly).
    """
    lo, hi = margin_factors(margin)
    return window_values_from_factors(head_bin, tail_bin,
                                      np.float32(bin_minutes),
                                      np.float32(range_minutes), lo, hi)


def window_values_from_factors(head_bin, tail_bin, bin_f32, range_f32,
                               margin_lo, margin_hi):
    """:func:`window_values` from precomputed float32 knobs; all four knobs
    may be traced per-config arrays (the sweep window-variant axis)."""
    xp = _ns(head_bin, tail_bin, bin_f32, margin_lo)
    f = np.float32
    head = xp.asarray(head_bin, f) if xp is np else head_bin.astype(f)
    tail = xp.asarray(tail_bin, f) if xp is np else tail_bin.astype(f)
    load_at = head * bin_f32 * margin_lo
    unload_at = xp.minimum(tail * bin_f32, range_f32) * margin_hi
    return load_at, xp.maximum(unload_at, load_at)


def standard_window_bounds(standard_keep):
    """The fallback windows: never unload early, keep for the full range."""
    return np.float32(0.0), _f32(standard_keep)


# --------------------------------------------------------------------------
# Decision gates (Fig. 10)
# --------------------------------------------------------------------------


def oob_heavy(total, oob, oob_fraction_threshold):
    """Mostly-out-of-bounds check routing an app to the time-series path."""
    f = np.float32
    if isinstance(total, int):             # scalar control-plane fast path
        return bool(f(oob) > f(oob_fraction_threshold) * f(max(total + oob, 1)))
    return oob.astype(f) > _f32(oob_fraction_threshold) * \
        jnp.maximum(total + oob, 1).astype(f)


def use_histogram_gate(total, oob, cv_sum, cv_sum_sq, n_bins,
                       min_samples, cv_factor, oob_fraction_threshold):
    """Whether the histogram windows govern the next gap (else fall back to
    the standard keep-alive / time-series path). ``cv_factor`` is
    :func:`cv_gate_factor` of the CV threshold. Evaluated in int/float32
    so every engine takes the same branch."""
    if isinstance(total, int):             # scalar control-plane fast path
        return bool(
            total + oob >= min_samples and total > 0
            and not oob_heavy(total, oob, oob_fraction_threshold)
            and cv_gate(float(cv_sum), float(cv_sum_sq), n_bins, cv_factor))
    return use_histogram_gate_from_terms(
        total, oob, cv_gate_terms(cv_sum, cv_sum_sq, n_bins), min_samples,
        cv_factor, oob_fraction_threshold)


def use_histogram_gate_from_terms(total, oob, terms, min_samples, cv_factor,
                                  oob_fraction_threshold):
    """Traced-path gate from precomputed :func:`cv_gate_terms` — the sweep
    engine computes them once per histogram group and gates once per
    distinct (min_samples, cv_factor, oob_threshold) variant."""
    nq, s2 = terms
    seen = total + oob
    return (seen >= min_samples) & (nq >= _f32(cv_factor) * s2) \
        & (total > 0) & ~oob_heavy(total, oob, oob_fraction_threshold)


def arima_window(predicted_it: float, margin: float) -> Tuple[float, float]:
    """§4.3: (prewarm, keep_alive) around a forecast idle time — pre-warm
    just before the prediction, keep alive across a 2-margin band."""
    return predicted_it * (1.0 - margin), 2.0 * margin * predicted_it


# --------------------------------------------------------------------------
# SPES-style next-idle predictor (the PolicySpec predictor family)
# --------------------------------------------------------------------------


class SpesStepConfig(NamedTuple):
    """One SPES-predictor configuration in the dtypes the decision layer
    consumes. Leaves may be host scalars (the scalar policy) or traced
    ``[S, 1]`` arrays broadcast against the app axis (the sweep config
    axis), like :class:`HybridStepConfig`."""
    alpha: object          # f32 — exponential smoothing weight
    om_alpha: object       # f32 — (1 - alpha), rounded once on the host
    band_margin: object    # f32 — relative half-band around the forecast
    band_sigma: object     # f32 — residual-std multiplier widening the band
    min_samples: object    # i32 — observed ITs before the forecast governs
    standard_keep: object  # f32 — fallback keep-alive until warmed up

    @classmethod
    def from_host(cls, *, alpha: float, band_margin: float,
                  band_sigma: float, min_samples: int,
                  standard_keep: float) -> "SpesStepConfig":
        return cls(alpha=np.float32(alpha), om_alpha=np.float32(1.0 - alpha),
                   band_margin=np.float32(band_margin),
                   band_sigma=np.float32(band_sigma),
                   min_samples=np.int32(min_samples),
                   standard_keep=np.float32(standard_keep))


def spes_update(mean, var, n_obs, it32, active, alpha, om_alpha):
    """One exponentially-weighted update of the next-idle forecast state.

    State is ``(mean, var, n_obs)``: EW mean of the observed inter-arrival
    times, EW variance of the one-step forecast residuals (West's update:
    ``var' = (1 - a) * (var + a * err^2)``), and the observation count.
    The carried state is always float32 — like the histogram decision
    layer, the predictor state is a *decision* input, so every engine (the
    float64 fused scan, the scalar control-plane policy) holds identical
    values. The update itself is computed in float64 and rounded ONCE to
    float32: a float32 op-by-op pipeline is not engine-invariant (XLA
    freely contracts mul+add into FMA, numpy never does), while one wide
    computation with a single final rounding agrees across fusion choices
    except on the measure-zero float32 rounding boundary. The first
    observation seeds ``mean`` directly with zero variance; ``active``
    masks padding/first-event columns.
    """
    xp = _ns(mean, it32, active)
    first = n_obs == 0
    m, v = _f64(mean), _f64(var)
    err = _f64(it32) - m
    incr = _f64(alpha) * err
    upd_mean = xp.where(first, _f64(it32), m + incr)
    upd_var = xp.where(first, np.float64(0.0),
                       _f64(om_alpha) * (v + err * incr))
    new_mean = _f32(xp.where(active, upd_mean, m))
    new_var = _f32(xp.where(active, upd_var, v))
    return new_mean, new_var, n_obs + active


def spes_window_from_counts(mean, var, n_obs, min_samples, band_margin,
                            band_sigma, standard_keep):
    """(load_at, unload_at) residency bounds from the forecast state.

    The point forecast of the next idle time is the EW ``mean``; the
    confidence band around it is a relative margin plus ``band_sigma``
    residual standard deviations, so a perfectly regular app (var -> 0)
    converges to a tight window while an erratic one keeps a wide net.
    Below ``min_samples`` observations the standard keep-alive governs.
    Computed in float64 from the float32 state and rounded once to float32
    (the same FMA-invariance rationale as :func:`spes_update`); the
    returned float32 bounds widen to float64 exactly, so verdicts agree
    across engines.
    """
    xp = _ns(mean, var, n_obs)
    m = _f64(mean)
    half = _f64(band_margin) * m + _f64(band_sigma) * xp.sqrt(_f64(var))
    load = xp.maximum(m - half, np.float64(0.0))
    unload = xp.maximum(m + half, load)
    ready = n_obs >= _i32(min_samples)
    std_load, std_unload = standard_window_bounds(standard_keep)
    return (xp.where(ready, _f32(load), std_load),
            xp.where(ready, _f32(unload), std_unload))


def fused_spes_step_math(t_now, prev_t, mean, var, n_obs, load_at,
                         unload_at, cold, waste, *, cfg: SpesStepConfig):
    """One fused SPES-predictor step: warm/cold + waste verdict under the
    previously decided bounds, the EW forecast-state update, and the
    banded window decision for the next gap.

    Mirrors :func:`hybrid_gap_step_math`'s carry discipline: residency
    *bounds* are carried in the engine's time dtype, the forecast state
    stays float32, and the shared clock/observation count are
    config-independent (``mean``/``var``/bounds broadcast against a
    ``[S, 1]``-leaved ``cfg`` for the sweep engines).
    """
    wdtype = t_now.dtype
    valid = jnp.isfinite(t_now)
    first = ~jnp.isfinite(prev_t)
    it = t_now - prev_t

    # Verdict for the gap that just closed.
    is_cold = valid & (first | ~warm_from_bounds(it, load_at, unload_at))
    gap_waste = jnp.where(valid & ~first,
                          idle_from_bounds(it, load_at, unload_at),
                          jnp.zeros((), wdtype))

    # Forecast-state update (float32 decision layer).
    rec = valid & ~first
    mean, var, n_obs = spes_update(mean, var, n_obs,
                                   it.astype(jnp.float32), rec,
                                   cfg.alpha, cfg.om_alpha)
    new_load, new_unload = spes_window_from_counts(
        mean, var, n_obs, cfg.min_samples, cfg.band_margin, cfg.band_sigma,
        cfg.standard_keep)

    # Windows decided now govern the next gap of apps that saw an event.
    load_at = jnp.where(valid, new_load.astype(wdtype), load_at)
    unload_at = jnp.where(valid, new_unload.astype(wdtype), unload_at)
    prev_t = jnp.where(valid, t_now, prev_t)
    return (prev_t, mean, var, n_obs, load_at, unload_at,
            cold + is_cold, waste + gap_waste)


# --------------------------------------------------------------------------
# The fused simulator step (one invocation column for the whole fleet)
# --------------------------------------------------------------------------


class HybridStepConfig(NamedTuple):
    """One hybrid-policy configuration, precomputed into the exact dtypes
    the decision layer consumes.

    Leaves may be python/numpy scalars (static single-config paths) or
    traced scalars/arrays broadcastable against the app axis (the sweep
    config axis; the Pallas sweep kernel reads them out of SMEM). Being a
    NamedTuple, it is a pytree: it flows through ``jax.jit``/``lax.scan``
    as data, so a new grid point never retraces an engine.
    """
    n_bins: object        # i32 — effective bin count (<= allocated bins)
    head_numer: object    # i32 — head percentile numerator over PCT_SCALE
    tail_numer: object    # i32 — tail percentile numerator over PCT_SCALE
    margin_lo: object     # f32 — (1 - margin), rounded once on the host
    margin_hi: object     # f32 — (1 + margin)
    bin_minutes: object   # engine time dtype — IT binning divisor
    bin_f32: object       # f32 — bin width as the window values consume it
    range_f32: object     # f32 — histogram range for the window clamp
    cv_factor: object     # f32 — cv_gate_factor(cv_threshold)
    min_samples: object   # i32
    oob_threshold: object  # f32
    standard_keep: object  # f32 — fallback keep-alive (== range)

    @classmethod
    def from_host(cls, *, n_bins: int, head_pct: float, tail_pct: float,
                  margin: float, bin_minutes: float, range_minutes: float,
                  cv_threshold: float, min_samples: int, oob_threshold: float,
                  standard_keep: float) -> "HybridStepConfig":
        lo, hi = margin_factors(margin)
        return cls(
            n_bins=int(n_bins), head_numer=pct_numer(head_pct),
            tail_numer=pct_numer(tail_pct), margin_lo=lo, margin_hi=hi,
            bin_minutes=float(bin_minutes), bin_f32=np.float32(bin_minutes),
            range_f32=np.float32(range_minutes),
            cv_factor=cv_gate_factor(cv_threshold),
            min_samples=int(min_samples),
            oob_threshold=np.float32(oob_threshold),
            standard_keep=np.float32(standard_keep))


def hybrid_gap_step_math(it_lo, it_hi, gap, cum, oob, cv_sum, cv_sum_sq,
                         prewarm, unload_at, cold, waste, *,
                         cfg: HybridStepConfig, gather: bool,
                         bin_axis: int = -1):
    """One fused hybrid-policy step from a classified gap: warm/cold +
    waste verdict under the previously decided windows, histogram
    suffix-add update, Welford CV accumulation, and the percentile-window
    decision for the next gap.

    ``gap`` is the (valid, first, safe_bin, in_bounds, oob_hit) of the
    event (``decode_gaps`` of host-made codes) and ``it_lo <= it <= it_hi``
    brackets its idle time (equal when the engine's time dtype holds it;
    see :func:`warm_from_bounds`). Carries (prewarm, unload_at) residency
    *bounds* — not (prewarm, keep) — so no engine ever re-derives
    ``prewarm + keep`` in its own dtype. Works identically inside
    ``lax.scan`` bodies (``gather=True``) and Pallas kernel bodies
    (``gather=False``); ``cfg`` leaves may be static scalars or traced
    values (per-config SMEM scalars on TPU). ``bin_axis`` names ``cum``'s
    bin axis for the ``gather=False`` forms: the Pallas kernel keeps apps
    on lanes (``cum`` [n_bins, TA], per-app state [1, TA], ``bin_axis=0``).
    Returns the step's state; the clock stays with the caller."""
    valid, first, safe, in_b, oob_hit = gap
    wdtype = it_lo.dtype

    # Verdict for the gap that just closed.
    is_cold = valid & (first | ~warm_from_bounds(it_lo, prewarm, unload_at,
                                                 it_hi))
    gap_waste = jnp.where(valid & ~first,
                          idle_from_bounds(it_lo, prewarm, unload_at),
                          jnp.zeros((), wdtype))

    # Histogram + CV update on the cumulative representation.
    old = raw_count_at(cum, safe, gather=gather, bin_axis=bin_axis)
    new_cum = suffix_add(cum, safe, in_b, bin_axis=bin_axis)
    # last prefix sum == total in-bounds count (cum is nondecreasing; the
    # reduction form avoids a slice inside Pallas)
    total = (new_cum[..., -1] if gather else
             jnp.max(new_cum, axis=bin_axis,
                     keepdims=it_lo.ndim == cum.ndim)).astype(jnp.int32)
    oob = oob + oob_hit.astype(jnp.int32)
    cv_sum, cv_sum_sq = welford_update(cv_sum, cv_sum_sq, in_b, old)

    # Decision layer (int/float32 — dtype-invariant across engines).
    head_thr = percentile_threshold_scaled_numer(total, cfg.head_numer)
    tail_thr = percentile_threshold_scaled_numer(total, cfg.tail_numer)
    head_bin = first_bin_ge_scaled(new_cum, head_thr, gather=gather,
                                   bin_axis=bin_axis)
    tail_bin = first_bin_ge_scaled(new_cum, tail_thr, gather=gather,
                                   bin_axis=bin_axis) + 1
    new_load, new_unload = window_values_from_factors(
        head_bin, tail_bin, cfg.bin_f32, cfg.range_f32, cfg.margin_lo,
        cfg.margin_hi)
    use_hist = use_histogram_gate(total, oob, cv_sum, cv_sum_sq, cfg.n_bins,
                                  cfg.min_samples, cfg.cv_factor,
                                  cfg.oob_threshold)
    std_load, std_unload = standard_window_bounds(cfg.standard_keep)
    new_load = jnp.where(use_hist, new_load, std_load).astype(wdtype)
    new_unload = jnp.where(use_hist, new_unload, std_unload).astype(wdtype)

    # Windows decided now govern the next gap of apps that saw an event.
    prewarm = jnp.where(valid, new_load, prewarm)
    unload_at = jnp.where(valid, new_unload, unload_at)
    return (new_cum, oob, cv_sum, cv_sum_sq, prewarm, unload_at,
            cold + is_cold, waste + gap_waste)


# --------------------------------------------------------------------------
# The sweep step: S configurations over one trace column, factored
# --------------------------------------------------------------------------


class HybridSweepBlock(NamedTuple):
    """A whole hybrid-policy grid, factored into its distinct layers.

    The S stacked configurations of one ``experiment.sweep`` call usually
    differ in only one or two knobs (the paper's Figs. 15-17 sweep one knob
    at a time), so the sweep step deduplicates shared work:

      * group layer ``[G, ...]`` — distinct (bin_minutes, n_bins): the
        histogram sufficient statistics (cumulative counts, OOB, Welford CV
        accumulators) are carried and updated once per group;
      * window layer ``[W, ...]`` — distinct (group, percentiles, margin,
        range): percentile searches + window values once per variant;
      * gate layer ``[T, ...]`` — distinct (group, min_samples,
        cv_factor, oob_threshold): the representativeness gate once per
        variant;
      * config layer ``[S, ...]`` — every config just *selects* its
        (window, gate) pair; the per-config scan state is cold counts,
        waste, and the carried residency bounds (refreshed from group
        state at each app's events, see :func:`hybrid_sweep_decide`).

    All index leaves are i32 arrays; knob leaves follow the same dtype
    discipline as :class:`HybridStepConfig`, with shapes ``[layer, 1]`` so
    they broadcast against ``[layer, n_apps]`` state.
    """
    # group layer
    g_bin_minutes: object   # [G, 1] time dtype
    g_n_bins: object        # [G, 1] i32 (effective bins; allocation is max)
    # window-variant layer
    w_group: object         # [W] i32 — variant -> group row
    w_head_numer: object    # [W, 1] i32
    w_tail_numer: object    # [W, 1] i32
    w_bin_f32: object       # [W, 1] f32
    w_range_f32: object     # [W, 1] f32
    w_margin_lo: object     # [W, 1] f32
    w_margin_hi: object     # [W, 1] f32
    # gate-variant layer
    t_group: object         # [T] i32 — variant -> group row
    t_min_samples: object   # [T, 1] i32
    t_cv_factor: object     # [T, 1] f32 — cv_gate_factor(cv_threshold)
    t_oob_threshold: object  # [T, 1] f32
    # standard-keep layer (fallback windows, one per distinct keep-alive)
    d_standard_keep: object  # [D, 1] f32
    # config layer
    c_window: object        # [S] i32 — config -> window variant
    c_gate: object          # [S] i32 — config -> gate variant
    c_std: object           # [S] i32 — config -> standard-keep row


class SweepIdentities(NamedTuple):
    """Static structure flags for a :class:`HybridSweepBlock`.

    Each flag asserts that a selector index array is the identity mapping
    (known host-side when the block is built), letting the traced decision
    layers skip the corresponding gather — on CPU, per-step gathers cost
    more than the whole verdict math they route, and for a single-config
    run EVERY selector is the identity, so the S=1 path keeps the pre-sweep
    engine's gather-free form. Results are identical either way.
    """
    w: bool = False        # window variant w reads group w
    t: bool = False        # gate variant t reads group t
    c_window: bool = False  # config s uses window variant s
    c_gate: bool = False   # config s uses gate variant s
    c_std: bool = False    # config s uses standard-keep row s


def _sweep_decision_layers(gcum, goob, gcv_sum, gcv_sum_sq,
                           blk: HybridSweepBlock, ids: SweepIdentities):
    """The shared decision sub-layers from the current group state.

    Returns (w_load, w_unload) [W, n] float32 window-variant bounds and
    ``use_c`` [S, n] bool (per-config histogram-vs-standard gate verdict).

      * window layer: percentile searches once per distinct window variant;
      * gate layer: CV terms once per group, gate once per threshold
        tuple;
      * config layer: a gather (elided where ``ids`` proves it identity).
    """
    gtotal = gcum[..., -1].astype(jnp.int32)
    total_w = gtotal if ids.w else gtotal[blk.w_group]
    head_thr = percentile_threshold_scaled_numer(total_w, blk.w_head_numer)
    tail_thr = percentile_threshold_scaled_numer(total_w, blk.w_tail_numer)
    if ids.w:
        head_bin = first_bin_ge_scaled(gcum, head_thr, gather=True)
        tail_bin = first_bin_ge_scaled(gcum, tail_thr, gather=True) + 1
    else:
        head_bin = first_bin_ge_scaled_grouped(gcum, blk.w_group, head_thr)
        tail_bin = first_bin_ge_scaled_grouped(gcum, blk.w_group,
                                               tail_thr) + 1
    w_load, w_unload = window_values_from_factors(
        head_bin, tail_bin, blk.w_bin_f32, blk.w_range_f32, blk.w_margin_lo,
        blk.w_margin_hi)

    terms = cv_gate_terms(gcv_sum, gcv_sum_sq, blk.g_n_bins)
    sel_t = (lambda x: x) if ids.t else (lambda x: x[blk.t_group])
    use_hist = use_histogram_gate_from_terms(
        sel_t(gtotal), sel_t(goob), tuple(sel_t(x) for x in terms),
        blk.t_min_samples, blk.t_cv_factor, blk.t_oob_threshold)
    return w_load, w_unload, (use_hist if ids.c_gate
                              else use_hist[blk.c_gate])


def hybrid_sweep_decide(gcum, goob, gcv_sum, gcv_sum_sq,
                        blk: HybridSweepBlock,
                        ids: SweepIdentities = SweepIdentities()):
    """Per-config residency bounds from the current group state.

    Every decision input (cumulative counts, OOB, Welford accumulators)
    only changes when an app sees an event, so the windows an app carries
    between events are a *pure function* of group state — which is what
    lets the sweep step carry them (refreshed only at events) and still
    match a fresh decide from the same state. Returns float32
    (load_at, unload_at), each [S, n_apps] (decision-layer dtype; widening
    to the engine's time dtype is exact).
    """
    w_load, w_unload, use_c = _sweep_decision_layers(
        gcum, goob, gcv_sum, gcv_sum_sq, blk, ids)
    std_load, std_unload = standard_window_bounds(
        blk.d_standard_keep if ids.c_std
        else blk.d_standard_keep[blk.c_std])
    load_c = jnp.where(use_c, w_load if ids.c_window
                       else w_load[blk.c_window], std_load)
    unload_c = jnp.where(use_c, w_unload if ids.c_window
                         else w_unload[blk.c_window], std_unload)
    return load_c, unload_c


def fused_hybrid_sweep_step_math(t_now, prev_t, gcum, goob, gcv_sum,
                                 gcv_sum_sq, load_c, unload_c, cold,
                                 waste, *, blk: HybridSweepBlock,
                                 ids: SweepIdentities = SweepIdentities()):
    """One sweep step: S configurations advance together over one trace
    column, sharing the time layer and the per-group histogram update.

    Shapes: ``t_now``/``prev_t`` [n]; group state [G, n(, n_bins)];
    per-config state [S, n] — cold counts, waste, and the carried
    residency bounds ``(load_c, unload_c)`` in the engine's time dtype.
    The bounds are CARRIED, not recomputed at step start: the step
    verdicts the closing gap under them, updates the group state, then
    re-decides from the post-update state — the same carried-windows
    dataflow as the single-config :func:`hybrid_gap_step_math`, which
    lets XLA fuse the decision into the step that produced its state
    instead of stranding it on the next verdict's critical path (this is
    what restores the pre-sweep engine's S=1 step throughput, ROADMAP's
    fused-run regression).

    The carry is bit-identical to re-deriving ``hybrid_sweep_decide`` from
    the pre-update state each step: group state only changes at an app's
    events (the carry is refreshed exactly then, per app), and the init
    carry must equal decide(zero state) — ``(0, standard_keep)``, the
    ``use_histogram_gate`` total>0 fallback arm (float32 decision values
    widen to the time dtype exactly). Every value each config sees is,
    element for element, the same primitive sequence the single-config
    step computes — the layers only deduplicate and gather, so sweep rows
    are bit-identical to single-config runs (asserted by
    ``tests/test_experiment_api.py``).
    """
    wdtype = t_now.dtype
    valid = jnp.isfinite(t_now)        # [n] — shared across the whole grid
    first = ~jnp.isfinite(prev_t)
    it = t_now - prev_t
    account = valid & ~first           # gaps that actually closed

    # Verdict for the gap that just closed, under the carried windows. The
    # verdict math itself stays per-config [S, n]: on CPU the alternative
    # (verdicts per variant + per-config gathers) loses — XLA gathers cost
    # more than the elementwise compare/min/max they would save.
    is_cold = valid & (first | ~warm_from_bounds(it, load_c, unload_c))
    gap_waste = jnp.where(account,
                          idle_from_bounds(it, load_c, unload_c),
                          jnp.zeros((), wdtype))

    # Group layer: one histogram + CV update per distinct histogram shape.
    safe, in_b, oob_hit = classify_idle_time(it, account, blk.g_bin_minutes,
                                             blk.g_n_bins)
    old = raw_count_at(gcum, safe, gather=True)
    new_gcum = suffix_add(gcum, safe, in_b)
    new_goob = goob + oob_hit.astype(jnp.int32)
    gcv_sum, gcv_sum_sq = welford_update(gcv_sum, gcv_sum_sq, in_b, old)

    # Windows governing the next gap, from the post-update state. Apps
    # without an event this step keep their carried bounds — the state
    # they would decide from is unchanged.
    new_load, new_unload = hybrid_sweep_decide(new_gcum, new_goob, gcv_sum,
                                               gcv_sum_sq, blk, ids)
    load_c = jnp.where(valid, new_load.astype(wdtype), load_c)
    unload_c = jnp.where(valid, new_unload.astype(wdtype), unload_c)

    prev_t = jnp.where(valid, t_now, prev_t)
    return (prev_t, new_gcum, new_goob, gcv_sum, gcv_sum_sq, load_c,
            unload_c, cold + is_cold, waste + gap_waste)
