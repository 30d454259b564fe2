"""The benchmark's own copy of the fleet generator.

A copy of the ``azure_like`` path of ``repro.core.workload_spec`` (one
default cohort: the paper's §3 populations, Fig. 5(a) rates, the
rate-conditioned pattern mix, Fig. 3(b) triggers, the Fig. 4 diurnal warp
and the dataset's one-event-per-minute cap). The benchmark keeps its own
copy so that a change to the program's generator cannot change the
traffic it is measured on; ``bench/tests/test_bench_fleet.py`` pins the
copy's output to a recorded digest.

  fleet(n_apps, days, seed) -> (times [n, W] float64, counts)
  population(n_apps, days, seed) -> per-app columns

Unlike the program's generator, the copy keeps every app's own rate: the
only cap is the dataset's, at most one invocation per minute, so an app
may have up to one event per minute of the span, and the clock stays in
float64 minutes as drawn. Generation is seed-deterministic and
independent of batching: apps are made in fixed index blocks, each from
its own counter RNG keyed on ``(seed, block_start, cohort)``; within a
block, apps of one pattern are drawn in classes of like event counts so
that a rare app never pays for the widest one.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

MINUTES_PER_DAY = 1440.0

# Fig. 5(a) CDF anchors: (fraction of apps, log10(invocations/day)).
RATE_CDF = np.array([
    (0.00, -1.00),
    (0.10, 0.00),
    (0.45, np.log10(24.0)),
    (0.65, 2.30),
    (0.81, np.log10(1440.0)),
    (0.92, 4.50),
    (0.98, 6.00),
    (1.00, 7.00),
])
# Fig. 1 CDF anchors: (fraction of apps, log10(functions/app)).
FUNC_CDF = np.array([
    (0.54, 0.0),
    (0.80, np.log10(3.0)),
    (0.95, 1.0),
    (0.9996, 2.0),
    (1.0, np.log10(2000.0)),
])
# Pattern mix by rate class (periodic, multi_timer, regular, poisson, bursty).
PATTERN_MATRIX = np.asarray([
    (0.12, 0.06, 0.04, 0.12, 0.66),   # rate <= 1/hour
    (0.20, 0.10, 0.10, 0.15, 0.45),   # 1/hour - 1/minute
    (0.15, 0.05, 0.15, 0.40, 0.25),   # >= 1/minute
], np.float64)
N_PATTERNS = 5
PERIODIC, MULTI_TIMER, REGULAR, POISSON, BURSTY = range(N_PATTERNS)
ROUND_PERIODS = np.array([1., 2., 5., 10., 15., 30., 60., 120., 240., 480.,
                          720., 1440., 2880., 10080.])
# Fig. 3(b) trigger-combination shares (12 combinations).
TRIGGER_PROBS = np.array([43.27, 13.36, 9.47, 4.59, 4.22, 3.01, 2.80, 2.57,
                          2.48, 1.69, 1.05, 1.03])
EXEC_LOG_MEAN, EXEC_LOG_SIGMA = -0.38, 2.36                  # Fig. 7
MEM_BURR_C, MEM_BURR_K, MEM_BURR_LAMBDA = 11.652, 0.221, 107.083  # Fig. 8
DIURNAL_AMPLITUDE = 0.45                                      # Fig. 4

EVENT_BUDGET = 1 << 21
MIN_BLOCK, MAX_BLOCK = 256, 32768
RNG_TAG = 0x57F1


def _block_size(max_ev: int) -> int:
    return int(np.clip(EVENT_BUDGET // max_ev, MIN_BLOCK, MAX_BLOCK))


def _blocks(n_apps: int, seed: int, max_ev: int) -> Iterator[Tuple]:
    """(lo, hi, rng) of every generation block of the one-cohort fleet."""
    block = _block_size(max_ev)
    for blo in range(0, n_apps, block):
        yield blo, min(blo + block, n_apps), np.random.default_rng(
            [RNG_TAG, seed, blo, 0])


def _sample_population(rng, n: int) -> Dict[str, np.ndarray]:
    u_lo = float(np.interp(-1.0, RATE_CDF[:, 1], RATE_CDF[:, 0]))
    u_hi = float(np.interp(7.0, RATE_CDF[:, 1], RATE_CDF[:, 0]))
    rates = 10.0 ** np.interp(rng.uniform(u_lo, u_hi, n), RATE_CDF[:, 0],
                              RATE_CDF[:, 1])
    cls = np.digitize(rates, (24.0, MINUTES_PER_DAY), right=True)
    cdf = np.cumsum(PATTERN_MATRIX, axis=1)[cls]
    u = rng.uniform(0.0, 1.0, n)
    pattern = np.sum(u[:, None] > cdf[:, :-1], axis=1).astype(np.int32)
    timer = pattern <= MULTI_TIMER
    if timer.any():   # timers fire at most 1/minute on round periods
        r = np.minimum(rates, MINUTES_PER_DAY)
        raw = MINUTES_PER_DAY / np.maximum(r, 1e-9)
        j = np.argmin(np.abs(np.log(ROUND_PERIODS)[None, :]
                             - np.log(raw)[:, None]), axis=1)
        rates = np.where(timer, MINUTES_PER_DAY / ROUND_PERIODS[j], rates)
    period = np.maximum(MINUTES_PER_DAY / np.maximum(rates, 1e-9), 1.0)
    u = rng.uniform(0.0, 1.0, n)
    memory = np.clip(MEM_BURR_LAMBDA * ((1.0 - u) ** (-1.0 / MEM_BURR_K)
                                        - 1.0) ** (1.0 / MEM_BURR_C),
                     1.0, 16384.0)
    execs = np.exp(rng.normal(EXEC_LOG_MEAN, EXEC_LOG_SIGMA, n))
    u = rng.uniform(0.0, 1.0, n)
    fv = np.where(u <= FUNC_CDF[0, 0], 0.0,
                  np.interp(u, FUNC_CDF[:, 0], FUNC_CDF[:, 1]))
    nfunc = np.maximum(np.round(10.0 ** fv), 1).astype(np.int64)
    trig = rng.choice(len(TRIGGER_PROBS), n,
                      p=TRIGGER_PROBS / TRIGGER_PROBS.sum())
    return dict(rates=rates, pattern=pattern, period=period, memory=memory,
                execs=execs, nfunc=nfunc, trig=trig)


def _warp_grid(duration: float):
    """Inverse cumulative intensity of the diurnal cycle (Fig. 4)."""
    grid_t = np.linspace(0.0, duration, max(int(np.ceil(duration)) + 1, 2))
    a = DIURNAL_AMPLITUDE
    phase = 2.0 * np.pi * (grid_t % MINUTES_PER_DAY) / MINUTES_PER_DAY
    intensity = np.maximum(
        (1.0 - a) + a * 0.5 * (1.0 + np.sin(phase - 0.5 * np.pi)), 1e-3)
    cum = np.concatenate([[0.0],
                          np.cumsum(0.5 * (intensity[1:] + intensity[:-1]))])
    return cum / cum[-1], grid_t


def _minute_cap(frame: np.ndarray) -> None:
    """Keep an event only if it is >= 1 minute after the last kept one
    (rows whose events are all a minute apart already are left alone)."""
    with np.errstate(invalid="ignore"):          # inf - inf past the end
        rows = np.flatnonzero((np.diff(frame, axis=1) < 1.0).any(axis=1))
    if not len(rows):
        return
    sub = frame[rows]
    width = int(np.isfinite(sub).sum(axis=1).max())
    last = sub[:, 0].copy()
    for j in range(1, width):
        col = sub[:, j]
        keep = col >= last + 1.0
        sub[:, j] = np.where(keep, col, np.inf)
        last = np.where(keep, col, last)
    sub.sort(axis=1)
    frame[rows] = sub


def _width_classes(need: np.ndarray, max_ev: int):
    """Positions of ``need`` grouped by the power of two (>= 64, capped at
    ``max_ev``) that holds each app's event count, with that width."""
    width = np.minimum(2 ** np.ceil(np.log2(np.maximum(need, 64))),
                       max_ev).astype(np.int64)
    for w in np.unique(width):
        yield np.flatnonzero(width == w), int(w)


def _events(rng, pop, duration: float, max_ev: int, warp) -> np.ndarray:
    """One block's sorted, +inf padded event frame [m, max_ev] (float64)."""
    m = len(pop["rates"])
    days = duration / MINUTES_PER_DAY
    frame = np.full((m, max_ev), np.inf, np.float64)
    pattern, period = pop["pattern"], pop["period"]
    warped = np.zeros(m, bool)
    for pid in range(N_PATTERNS):
        idx = np.where(pattern == pid)[0]
        g = len(idx)
        if not g:
            continue
        per = period[idx]
        if pid == PERIODIC:
            per = per * np.maximum(np.ceil((duration / per + 1.0) / max_ev),
                                   1.0)
            t = (rng.uniform(0.0, per)[:, None]
                 + np.arange(max_ev)[None, :] * per[:, None])
            t[t >= duration] = np.inf
            frame[idx] = t
        elif pid == MULTI_TIMER:
            per1 = 2.0 * per
            per2 = per1 * rng.uniform(1.2, 3.0, g)
            half = max_ev // 2 + 1
            need = np.maximum(duration / per1, duration / per2) + 1.0
            stretch = np.maximum(np.ceil(need / half), 1.0)
            per1, per2 = per1 * stretch, per2 * stretch
            j = np.arange(half)[None, :]
            t = np.concatenate(
                [rng.uniform(0.0, per1)[:, None] + j * per1[:, None],
                 rng.uniform(0.0, per2)[:, None] + j * per2[:, None]], axis=1)
            t[t >= duration] = np.inf
            t.sort(axis=1)
            frame[idx] = t[:, :max_ev]
        elif pid == REGULAR:   # Erlang-4 gaps, CV 0.5
            per = np.maximum(per, duration / max_ev)
            for sel, width in _width_classes(
                    np.ceil(duration / per * 1.5) + 8, max_ev):
                t = np.cumsum(rng.gamma(4.0, 1.0, (len(sel), width))
                              * (per[sel, None] / 4.0), axis=1)
                t[t >= duration] = np.inf
                frame[idx[sel], :width] = t
        elif pid == POISSON:
            lam = np.minimum(pop["rates"][idx] * days, float(max_ev))
            cnt = np.minimum(rng.poisson(lam), max_ev).astype(np.int64)
            width = max(int(cnt.max()), 1)
            t = rng.uniform(0.0, duration, (g, width))
            t[np.arange(width)[None, :] >= cnt[:, None]] = np.inf
            t.sort(axis=1)
            frame[idx, :width] = t
            warped[idx] = True
        else:                  # bursty: hyperexponential gaps, CV >> 1
            per = np.maximum(per, duration / max_ev)
            burst_mean = rng.uniform(6.0, 30.0, g)
            intra = rng.uniform(0.8, 2.5, g)
            dense = per <= 2.0
            p_intra = np.where(dense, 0.0, 1.0 - 1.0 / burst_mean)
            gap = np.where(dense, per, (per - p_intra * intra)
                           / np.maximum(1.0 - p_intra, 1e-9))
            gap = np.maximum(gap, per)
            for sel, width in _width_classes(
                    np.ceil(duration / per * 1.6) + 16, max_ev):
                k = len(sel)
                short = rng.uniform(0.0, 1.0, (k, width)) < p_intra[sel, None]
                iats = (rng.exponential(1.0, (k, width))
                        * np.where(short, intra[sel, None], gap[sel, None]))
                t = (rng.uniform(0.0, gap[sel])[:, None]
                     + np.cumsum(iats, axis=1) - iats[:, :1])
                t[t >= duration] = np.inf
                frame[idx[sel], :width] = t
            warped[idx] = True
    rows = np.where(warped)[0]
    if len(rows):
        cnorm, grid_t = warp
        sub = frame[rows]
        finite = np.isfinite(sub)
        x = np.clip(np.where(finite, sub, 0.0) / duration, 0.0, 1.0)
        frame[rows] = np.where(finite, np.interp(x, cnorm, grid_t), np.inf)
    return frame


def max_events(days: float) -> int:
    """The most events an app can have: one per minute of the span."""
    return int(np.ceil(days * MINUTES_PER_DAY)) + 1


def fleet(n_apps: int, days: float, seed: int):
    """The padded fleet: (times [n_apps, W] float64 minutes, sorted and
    +inf padded, W the largest event count; counts [n_apps] int32)."""
    duration = days * MINUTES_PER_DAY
    max_ev = max_events(days)
    warp = _warp_grid(duration)
    padded = np.full((n_apps, max_ev), np.inf, np.float64)
    for lo, hi, rng in _blocks(n_apps, seed, max_ev):
        padded[lo:hi] = _events(rng, _sample_population(rng, hi - lo),
                                duration, max_ev, warp)
    _minute_cap(padded)
    counts = np.isfinite(padded).sum(axis=1).astype(np.int32)
    width = max(int(counts.max()), 1) if n_apps else 1
    return np.ascontiguousarray(padded[:, :width]), counts


def population(n_apps: int, days: float, seed: int) -> Dict[str, np.ndarray]:
    """Per-app population columns (rates, pattern, period, memory MB,
    execution seconds, functions, trigger combination), drawn exactly as
    :func:`fleet` draws them, without generating events."""
    out: Dict[str, np.ndarray] = {}
    for lo, hi, rng in _blocks(n_apps, seed, max_events(days)):
        pop = _sample_population(rng, hi - lo)
        if not out:
            out = {k: np.empty(n_apps, v.dtype) for k, v in pop.items()}
        for k, v in pop.items():
            out[k][lo:hi] = v
    return out
