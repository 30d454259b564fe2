"""Plain reference of the paper's hybrid histogram keep-alive policy.

Written from the paper (Shahrad et al., ATC'20, §4.2, Fig. 10) and the
rounding rules the configuration files state, in straightforward numpy:
one loop over each app's events, all sampled apps and configurations
side by side. It imports nothing of the program under test.

Per app and configuration, with execution time 0 (idle time = gap
between invocations):

* the first invocation is cold; an invocation after a gap ``it`` is warm
  iff ``load <= it <= unload`` under the windows decided at the previous
  invocation, and the image sat loaded but idle for
  ``max(min(it, unload) - load, 0)`` minutes of that gap;
* each gap is binned by ``floor(it / bin_minutes)``; bins past the range
  count as out of bounds (OOB);
* after each invocation the next windows are decided: the standard
  keep-alive ``(0, range)`` while fewer than ``min_samples`` gaps were
  seen, no gap was in bounds, more than ``oob_threshold`` of the gaps
  were OOB, or the bin counts' coefficient of variation is below the
  threshold (``n_bins * sum(c^2) < (1 + t^2) * sum(c)^2``, exact);
  otherwise the head and tail percentile bins (the first bin whose
  cumulative count reaches ``pct``% of the in-bounds gaps, at least one)
  give ``load = head * bin * (1 - margin)`` and
  ``unload = max(min((tail + 1) * bin, range) * (1 + margin), load)``,
  rounded to float32 as the policy states its windows;
* after the last invocation the gap to the end of the trace adds its
  loaded-idle time to the waste.

An app's histogram depends only on its gaps and the bin layout (bin
width and count), so configurations that share a layout share it.

Times, gaps and waste are float64, as the configuration states for the
clock. ``dtype=np.float32`` is the control, the precision below it: the
clock is rounded to float32, and every gap is differenced, judged, binned
and added to the waste in float32.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

PCT_SCALE = 10_000   # percentiles in 1/100 of a percent, compared exactly


def _f32(x):
    return np.asarray(x, np.float32)


def _n_bins(c: dict) -> int:
    return int(round(c["range_minutes"] / c["bin_minutes"]))


def reference(times: np.ndarray, counts: np.ndarray, duration: float,
              configs: Sequence[dict], dtype=np.float64) -> dict:
    """Replay ``times`` [n, W] (sorted, +inf padded, minutes) under each
    config (dicts with ``bin_minutes``, ``range_minutes``,
    ``head_percentile``, ``tail_percentile``, ``margin``, ``cv_threshold``,
    ``min_samples``, ``oob_threshold``). Returns ``cold``, ``waste``,
    ``prewarm`` and ``keep_alive`` [S, n] and ``invocations`` [n]."""
    S, n = len(configs), times.shape[0]
    out = dict(cold=np.zeros((S, n), np.int64), waste=np.zeros((S, n)),
               prewarm=np.zeros((S, n)), keep_alive=np.zeros((S, n)))
    layouts: Dict[tuple, list] = {}
    for s, c in enumerate(configs):
        layouts.setdefault((c["bin_minutes"], _n_bins(c)), []).append(s)
    for rows in layouts.values():
        got = _replay(times, counts, duration, [configs[s] for s in rows],
                      np.dtype(dtype))
        for key, v in got.items():
            out[key][rows] = v
    out["invocations"] = counts.astype(np.int64)
    return out


def _replay(times, counts, duration, configs, clock) -> dict:
    """:func:`reference` for configs of one bin layout."""
    S, (n, W) = len(configs), times.shape
    col = lambda key, dt: np.asarray([c[key] for c in configs], dt)[:, None]
    B = _n_bins(configs[0])
    bin_m = clock.type(configs[0]["bin_minutes"])
    bin_f32 = np.float32(configs[0]["bin_minutes"])
    rng_f32 = col("range_minutes", np.float32)
    pct = lambda key: np.asarray([int(round(c[key] * 100))
                                  for c in configs], np.int64)
    head_num, tail_num = pct("head_percentile"), pct("tail_percentile")
    m_lo = _f32([1.0 - c["margin"] for c in configs])[:, None]
    m_hi = _f32([1.0 + c["margin"] for c in configs])[:, None]
    cv_t = col("cv_threshold", np.float64)
    min_s = col("min_samples", np.int64)
    oob_thr = col("oob_threshold", np.float64)

    t = np.asarray(times, clock)
    hist = np.zeros((n, B), np.int64)      # shared by the layout's configs
    total = np.zeros(n, np.int64)
    sum_sq = np.zeros(n, np.int64)         # sum over bins of count^2
    oob = np.zeros(n, np.int64)
    std = np.broadcast_to(rng_f32, (S, n)).astype(clock)
    load = np.zeros((S, n), clock)
    unload = std.copy()
    cold = np.zeros((S, n), np.int64)
    waste = np.zeros((S, n), clock)
    apps = np.arange(n)

    def pct_bin(cum, numer):
        # the first bin whose cumulative count reaches
        # ceil(max(total * pct%, 1)) gaps: cum is nondecreasing, so that
        # is the number of bins below the threshold; one search per
        # distinct percentile
        values, which = np.unique(numer, return_inverse=True)
        need = -(-np.maximum(total[None] * values[:, None], PCT_SCALE)
                 // PCT_SCALE)
        found = np.sum(cum[None] < need[..., None], axis=-1)
        return np.minimum(found, B)[which]

    for k in range(W):
        valid = k < counts
        if not valid.any():
            break
        if k == 0:
            cold += valid
        else:
            it = (np.where(valid, t[:, k], 0)
                  - np.where(valid, t[:, k - 1], 0)).astype(clock)
            warm = (it >= load) & (it <= unload)
            cold += valid & ~warm
            idle = np.maximum(np.minimum(it, unload) - load, 0).astype(clock)
            waste = np.where(valid, waste + idle, waste).astype(clock)
            b = np.floor(it / bin_m).astype(np.int64)
            in_b = valid & (b < B)
            at = apps[in_b], b[in_b]
            sum_sq[in_b] += 2 * hist[at] + 1
            hist[at] += 1
            total += in_b
            oob += valid & (b >= B)
        # windows for the next gap
        seen = total + oob
        s1 = total.astype(np.float64)
        cv_ok = B * sum_sq >= (1.0 + cv_t * cv_t) * s1 * s1
        use = ((seen >= min_s) & (total > 0) & ~(oob > oob_thr * seen)
               & cv_ok)
        cum = np.cumsum(hist, axis=-1)
        head = pct_bin(cum, head_num)
        tail = pct_bin(cum, tail_num) + 1
        h_load = _f32(head) * bin_f32 * m_lo
        h_unload = np.maximum(np.minimum(_f32(tail) * bin_f32, rng_f32)
                              * m_hi, h_load)
        new_load = np.where(use, h_load, 0).astype(clock)
        new_unload = np.where(use, h_unload, std).astype(clock)
        load = np.where(valid, new_load, load)
        unload = np.where(valid, new_unload, unload)

    has = counts > 0
    last = t[apps, np.maximum(counts - 1, 0)]
    tail_gap = (clock.type(duration) - last).astype(clock)
    idle = np.maximum(np.minimum(tail_gap, unload) - load, 0).astype(clock)
    waste = np.where(has & (tail_gap > 0), waste + idle, waste)
    load64 = load.astype(np.float64)
    return dict(cold=cold, waste=waste.astype(np.float64), prewarm=load64,
                keep_alive=unload.astype(np.float64) - load64)
