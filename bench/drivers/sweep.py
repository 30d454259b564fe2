"""Closed-loop policy sweeps: one analyst's policy search over one fleet.

Set-up makes the configuration's fleet with the benchmark's own
generator (from the configuration's own seed, so every run has the same
apps and event counts, and the same compiled shapes) and hands it to the
program as a ``Trace`` in an order drawn from the run's seed. Each timed
call is one whole ``repro.core.experiment.sweep(trace, specs)`` over a
fresh grid of the mix's shape: every margin moves by its own small amount
drawn from the seed and the call's index, so no result can be reused
across calls while the bins, bin width and work per call stay the same.
The warm-up call has its own draw.

Work per call is the configs times the fleet's real invocation events
(padding is not work). A sample of apps drawn from the seed (always with
the app that has the most events, and one without any where there is one)
is kept from every call. After the window the plain reference
(``bench/reference/hybrid.py``) replays the sample under every call's
configs (of at most the mix's ``check_calls`` calls, drawn from the seed
where there were more), and every field is compared: cold counts,
invocations and final windows exactly, waste within the configuration's
stated float32 bound.
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional

import numpy as np

from bench import cost, fleet
from bench.reference.hybrid import reference

METRIC = "sweep_events_per_s"
F32_ULP_EPS = float(np.finfo(np.float32).eps)
# The reference replays apps of like event counts together, so only the
# busiest apps pay for the longest loop.
COUNT_GROUPS = (64, 512, 4096)


def grid(traffic: dict) -> List[dict]:
    """The mix's base grid: cutoffs x CV thresholds x margins per range."""
    g = traffic["grid"]
    return [dict(bin_minutes=g["bin_minutes"], range_minutes=r,
                 head_percentile=h, tail_percentile=t, cv_threshold=cv,
                 margin=m, min_samples=g["min_samples"],
                 oob_threshold=g["oob_threshold"])
            for r in g["range_minutes"] for m in g["margin"]
            for cv in g["cv_threshold"] for h, t in g["cutoffs"]]


def call_grid(base: List[dict], jitter: float, seed: int,
              index: int) -> List[dict]:
    """Call ``index``'s grid (-1: the warm-up): the base grid with every
    margin moved by its own draw from [0, jitter)."""
    rng = np.random.default_rng([seed, index + 1, 0x6D61])
    moves = rng.uniform(0.0, jitter, len(base))
    return [dict(c, margin=c["margin"] + float(d))
            for c, d in zip(base, moves)]


def waste_bound(counts: np.ndarray, ref_waste: np.ndarray,
                duration: float) -> np.ndarray:
    """The configuration's waste guarantee per (config, app): one float32
    ulp of the trace's span per event plus float32 summation (a relative
    eps per event)."""
    ulp = float(np.spacing(np.float32(duration)))
    n = counts.astype(np.float64)[None, :]
    return n * ulp + n * F32_ULP_EPS * np.abs(ref_waste)


def compare(out: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            counts: np.ndarray, duration: float) -> Dict[str, float]:
    """Rows (config, app) whose exact fields differ, and the largest waste
    error as a share of its bound. Invocations are [n] or, per row, [S, n]."""
    bad = ((out["cold"] != ref["cold"])
           | (out["prewarm"] != ref["prewarm"])
           | (out["keep_alive"] != ref["keep_alive"])
           | (out["invocations"] != ref["invocations"]))
    err = np.abs(out["waste"] - ref["waste"])
    bound = waste_bound(counts, ref["waste"], duration)
    ratio = np.where(bound > 0, err / np.where(bound > 0, bound, 1.0),
                     np.where(err > 0, np.inf, 0.0))
    return dict(mismatch_rows=int(bad.sum()),
                waste_err=float(ratio.max()) if ratio.size else 0.0)


class Work:
    """One cell's fleet, grid and the answers kept for the check."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 expect_engine: Optional[str], log=print):
        import time
        from repro.core.workload import Trace
        f = config["fleet"]
        t0 = time.perf_counter()
        times, counts = fleet.fleet(f["n_apps"], f["days"], f["seed"])
        order = np.random.default_rng([seed, 0x6F72]).permutation(len(counts))
        self.times, self.counts = times[order], counts[order]
        del times
        self.duration = f["days"] * fleet.MINUTES_PER_DAY
        self.trace = Trace(specs=None, times=None,
                           duration_minutes=self.duration,
                           _padded=(self.times, self.counts))
        self.setup = dict(materialize_s=time.perf_counter() - t0)
        self.seed = seed
        self.base = grid(traffic)
        self.jitter = traffic["margin_jitter"]
        self.expect_engine = expect_engine
        self.limits = config["limits"]
        self.n_events = int(self.counts.sum())
        self.n_active = int((self.counts > 0).sum())
        self.idx = self.sample(traffic["check_apps"])
        self.check_calls = traffic["check_calls"]
        self.kept: List[tuple] = []
        self.found: Dict[str, float] = {}
        log("fleet", apps=f["n_apps"], days=f["days"],
            events=self.n_events, active_apps=self.n_active,
            width=self.times.shape[1], configs=len(self.base),
            **self.setup)

    # -- calls ---------------------------------------------------------------

    def _sweep(self, cfgs: List[dict]):
        from repro.core.experiment import HybridSpec, sweep
        specs = [HybridSpec(bin_minutes=c["bin_minutes"],
                            range_minutes=c["range_minutes"],
                            head_percentile=c["head_percentile"],
                            tail_percentile=c["tail_percentile"],
                            margin=c["margin"],
                            cv_threshold=c["cv_threshold"],
                            min_samples=c["min_samples"],
                            oob_fraction_threshold=c["oob_threshold"],
                            use_arima=False) for c in cfgs]
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            res = sweep(self.trace, specs)
        if self.expect_engine and res.engine != self.expect_engine:
            raise RuntimeError(f"sweep ran engine {res.engine!r}; this "
                               f"cell measures {self.expect_engine!r}")
        return res

    def warm(self) -> None:
        self._sweep(call_grid(self.base, self.jitter, self.seed, -1))

    def sample(self, k: int) -> np.ndarray:
        """The apps the reference replays: ``k`` drawn from the seed, the
        one with the most events and one without any."""
        rng = np.random.default_rng([self.seed, 0x636B])
        n = len(self.counts)
        pick = rng.choice(n, size=min(k, n), replace=False)
        extra = [int(np.argmax(self.counts))]
        idle = np.flatnonzero(self.counts == 0)
        if len(idle):
            extra.append(int(idle[rng.integers(len(idle))]))
        return np.unique(np.concatenate([pick, extra]))

    def call(self, index: int) -> None:
        cfgs = call_grid(self.base, self.jitter, self.seed, index)
        res = self._sweep(cfgs)
        idx = self.idx
        self.kept.append((cfgs, dict(
            cold=res.cold[:, idx].copy(),
            invocations=np.asarray(res.invocations)[idx].copy(),
            waste=res.wasted_minutes[:, idx].copy(),
            prewarm=res.final_prewarm[:, idx].copy(),
            keep_alive=res.final_keep_alive[:, idx].copy())))

    # -- numbers -------------------------------------------------------------

    def events_per_call(self) -> int:
        return len(self.base) * self.n_events

    def attempted(self, calls: int) -> int:
        return calls

    def rate(self, calls: int, elapsed: float) -> Dict[str, float]:
        return {METRIC: self.events_per_call() * calls / elapsed}

    def facts(self, calls: int, elapsed: float) -> dict:
        return dict(config_events_per_call=self.events_per_call(),
                    configs=len(self.base), events=self.n_events,
                    s_per_call=elapsed / max(calls, 1))

    def trace_context(self, calls: int) -> dict:
        n_bins = max(int(round(c["range_minutes"] / c["bin_minutes"]))
                     for c in self.base)
        return dict(
            least_bytes=calls * cost.sweep_least_bytes(
                self.n_events, self.n_active, len(self.base)),
            search_ops=calls * cost.sweep_search_ops(
                self.n_events, len(self.base), n_bins))

    # -- the check -----------------------------------------------------------

    def _replay(self, cfgs: List[dict], dtype) -> Dict[str, np.ndarray]:
        """The reference over the sample under ``cfgs``, group by group of
        like event counts; fields [len(cfgs), sample] in sample order."""
        counts = self.counts[self.idx]
        group = np.searchsorted(COUNT_GROUPS, counts)
        out: Dict[str, np.ndarray] = {}
        for g in np.unique(group):
            at = np.flatnonzero(group == g)
            width = max(int(counts[at].max()), 1)
            got = reference(self.times[self.idx[at], :width], counts[at],
                            self.duration, cfgs, dtype=dtype)
            for key, v in got.items():
                if key not in out:
                    out[key] = np.zeros(v.shape[:-1] + (len(counts),),
                                        v.dtype)
                out[key][..., at] = v
        return out

    def checked_calls(self, calls: int) -> List[int]:
        """The calls whose answers are compared: all of them, or where
        there are more than the mix's ``check_calls``, that many drawn
        from the seed, the last call always among them."""
        if calls <= self.check_calls:
            return list(range(calls))
        rng = np.random.default_rng([self.seed, calls, 0x6363])
        pick = rng.choice(calls - 1, self.check_calls - 1, replace=False)
        return sorted(int(i) for i in pick) + [calls - 1]

    def check(self, control=None) -> Dict[str, dict]:
        """Replay the sample under every kept call's grid (all calls side
        by side) and compare. ``control`` puts the reference, in that
        float type, in the program's place."""
        kept = [self.kept[i] for i in self.checked_calls(len(self.kept))]
        cfgs = [c for call_cfgs, _ in kept for c in call_cfgs]
        worst = dict(mismatch_rows=0, waste_err=0.0)
        if cfgs:
            ref = self._replay(cfgs, np.float64)
            if control is not None:
                out = self._replay(cfgs, control)
            else:
                out = {k: np.concatenate([o[k] for _, o in kept])
                       for k in ("cold", "waste", "prewarm", "keep_alive")}
                out["invocations"] = np.concatenate(
                    [np.broadcast_to(o["invocations"], (len(c), len(o[
                        "invocations"]))) for c, o in kept])
            worst = compare(out, ref, self.counts[self.idx], self.duration)
        self.found = dict(checked_calls=len(kept),
                          checked_apps=len(self.idx),
                          checked_rows=len(cfgs) * len(self.idx))
        return {k: dict(value=v, limit=self.limits[k])
                for k, v in worst.items()}

    def control(self, calls: int) -> Dict[str, dict]:
        """The check with the reference in float32 (the control) in the
        program's place, over the grids of ``calls`` calls."""
        self.kept = [(call_grid(self.base, self.jitter, self.seed, i), {})
                     for i in range(calls)]
        return self.check(control=np.float32)

    def check_facts(self) -> dict:
        return self.found
