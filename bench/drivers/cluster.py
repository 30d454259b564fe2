"""Closed-loop invoker-cluster replays: one operator's §5.3 what-if.

Set-up makes the configuration's fleet and its population columns
(execution seconds, memory) with the benchmark's own generator, from the
configuration's seed, and builds the program's ``AppTable`` from them
once (image bytes = memory MiB x 2**20, the program's rule). Each timed
call is one ``repro.core.experiment.run(table, spec,
cluster=ClusterSpec(...))`` with ``engine="auto"`` and one hybrid config
whose margin moves by its own draw from the seed and the call's index,
so no result can be reused across calls. Calls run with every
``RuntimeWarning`` an error: the engine's fallback to the scalar oracle
warns, so it fails the run, as does any numpy warning.

Work per call is the fleet's real invocation events (one config), the
quantity the sweep cells count.

After the window the plain reference (``bench/reference/cluster.py``)
replays, for at most the mix's ``check_calls`` calls (the last, and
others drawn from the seed), ``check_invokers`` invokers each: the one
with the most evictions in that call and others drawn from the seed.
Compared: per app, the cold share and every latency exactly; per
invoker, its counters exactly (together ``mismatch_rows``, with one more
row if the result does not cover the fleet's events and invokers); and
its resident byte-seconds against the float64 accumulation bound
(``resident_err``, the largest error over its bound).
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional

import numpy as np

from bench import cluster_cost, fleet
from bench.reference import cluster as ref

METRIC = "sweep_events_per_s"
BYTES_PER_MIB = 2 ** 20
COUNTERS = ("cold_starts", "warm_starts", "prewarms", "unloads",
            "evictions", "budget_overflows", "bytes_moved")


def call_config(base: dict, jitter: float, seed: int, index: int) -> dict:
    """Call ``index``'s config (-1: the warm-up): the base config with the
    margin moved by a draw from [0, jitter)."""
    rng = np.random.default_rng([seed, index + 1, 0x6D61])
    return dict(base, margin=base["margin"] + float(rng.uniform(0.0,
                                                                jitter)))


class Work:
    """One cell's fleet, cluster and the answers kept for the check."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 expect_engine: Optional[str], log=print):
        import time
        from repro.core.workload import Trace
        from repro.serving.apptable import AppTable
        from repro.serving.cluster_vector import ClusterSpec
        f, c = config["fleet"], config["cluster"]
        t0 = time.perf_counter()
        self.times, self.counts = fleet.fleet(f["n_apps"], f["days"],
                                              f["seed"])
        pop = fleet.population(f["n_apps"], f["days"], f["seed"])
        self.exec_s, memory_mb = pop["execs"], pop["memory"]
        self.weight_bytes = np.round(memory_mb * BYTES_PER_MIB).astype(
            np.int64)
        self.duration = f["days"] * fleet.MINUTES_PER_DAY
        t1 = time.perf_counter()
        self.table = AppTable.from_trace(
            Trace(specs=None, times=None, duration_minutes=self.duration,
                  _padded=(self.times, self.counts)),
            exec_s=self.exec_s, memory_mb=memory_mb)
        self.cluster = ClusterSpec(
            n_workers=c["invokers"], hbm_budget_bytes=c["hbm_budget_bytes"],
            balancing=c["balancing"], hedge=c["hedge"],
            checkpoint_at_minute=c["checkpoint_at_minute"])
        t2 = time.perf_counter()
        self.n_workers, self.budget = c["invokers"], c["hbm_budget_bytes"]
        self.costs = config["costs"]
        self.where = ref.placement(self.times, self.counts, self.n_workers,
                                   c["balancing"])
        self.ranks = ref.arrival_ranks(self.times, self.counts)
        self.first = np.cumsum(self.counts) - self.counts
        self.setup = dict(materialize_s=t1 - t0, table_s=t2 - t1,
                          reference_prep_s=time.perf_counter() - t2)
        self.seed = seed
        self.base = traffic["policy"]
        self.jitter = traffic["margin_jitter"]
        self.check_calls = traffic["check_calls"]
        self.check_invokers = traffic["check_invokers"]
        self.limits = config["limits"]
        self.n_events = int(self.counts.sum())
        self.n_active = int((self.counts > 0).sum())
        self.kept: List[tuple] = []
        self.found: Dict[str, float] = {}
        self.assigned = np.bincount(self.where, weights=self.weight_bytes
                                    * (self.counts > 0),
                                    minlength=self.n_workers)
        log("fleet", apps=f["n_apps"], days=f["days"],
            events=self.n_events, active_apps=self.n_active,
            width=self.times.shape[1], invokers=self.n_workers,
            budget=self.budget, most_assigned_bytes=int(self.assigned.max()),
            **self.setup)

    # -- calls ---------------------------------------------------------------

    def _run(self, cfg: dict):
        from repro.core.experiment import HybridSpec, run
        spec = HybridSpec(bin_minutes=cfg["bin_minutes"],
                          range_minutes=cfg["range_minutes"],
                          head_percentile=cfg["head_percentile"],
                          tail_percentile=cfg["tail_percentile"],
                          margin=cfg["margin"],
                          cv_threshold=cfg["cv_threshold"],
                          min_samples=cfg["min_samples"],
                          oob_fraction_threshold=cfg["oob_threshold"],
                          use_arima=False)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            return run(self.table, spec, cluster=self.cluster,
                       engine="auto")

    def warm(self) -> None:
        self._run(call_config(self.base, self.jitter, self.seed, -1))

    def call(self, index: int) -> None:
        cfg = call_config(self.base, self.jitter, self.seed, index)
        self.kept.append((cfg, self._run(cfg)))

    # -- numbers -------------------------------------------------------------

    def attempted(self, calls: int) -> int:
        return calls

    def rate(self, calls: int, elapsed: float) -> Dict[str, float]:
        return {METRIC: self.n_events * calls / elapsed}

    def facts(self, calls: int, elapsed: float) -> dict:
        got = [r.evictions for _, r in self.kept]
        return dict(events=self.n_events, s_per_call=elapsed / max(calls, 1),
                    evictions_per_call=",".join(map(str, got)))

    def trace_context(self, calls: int) -> dict:
        return dict(least_bytes=calls * cluster_cost.windows_least_bytes(
            self.n_events, self.n_active))

    # -- the check -----------------------------------------------------------

    def checked_calls(self, calls: int) -> List[int]:
        """All calls, or where there are more than the mix's
        ``check_calls``, that many drawn from the seed, the last always
        among them."""
        if calls <= self.check_calls:
            return list(range(calls))
        rng = np.random.default_rng([self.seed, calls, 0x6363])
        pick = rng.choice(calls - 1, self.check_calls - 1, replace=False)
        return sorted(int(i) for i in pick) + [calls - 1]

    def invokers(self, index: int, result) -> List[int]:
        """The invokers replayed for call ``index``: the one with the most
        evictions in its result (without a result, the one with the most
        image bytes assigned), and others drawn from the seed."""
        if result is not None:
            first = int(np.argmax([s["evictions"]
                                   for s in result.stats_per_worker]))
        else:
            first = int(np.argmax(self.assigned))
        rest = np.delete(np.arange(self.n_workers), first)
        rng = np.random.default_rng([self.seed, index, 0x6976])
        k = min(self.check_invokers - 1, len(rest))
        return [first] + sorted(int(w) for w in rng.choice(rest, k,
                                                           replace=False))

    def _replay(self, apps: np.ndarray, cfg: dict, clock) -> dict:
        got = ref.replay_invoker(apps, self.times, self.counts, self.exec_s,
                                 self.weight_bytes, self.duration, cfg,
                                 self.budget, self.costs, clock)
        got["cold_pct"] = 100.0 * got["cold"] / np.maximum(
            self.counts[apps], 1)
        return got

    def _program(self, apps: np.ndarray, w: int, result) -> dict:
        """The program's answers for invoker ``w``'s ``apps`` in the
        reference's layout."""
        at = np.concatenate([self.ranks[self.first[a]:self.first[a]
                                        + self.counts[a]] for a in apps])
        st = result.stats_per_worker[w]
        return dict(cold_pct=result.cold_pct_per_app[apps],
                    latency=result.latencies_s[at],
                    stats={k: st[k] for k in COUNTERS},
                    resident_byte_seconds=st["resident_byte_seconds"])

    def _compare(self, apps: np.ndarray, out: dict, want: dict):
        """(mismatched rows, resident error over its bound)."""
        cnt = self.counts[apps]
        bad_lat = np.zeros(len(apps), bool)
        if len(want["latency"]):
            diff = (out["latency"] != want["latency"]).astype(np.int64)
            edges = np.cumsum(cnt) - cnt
            has = cnt > 0
            bad_lat[has] = np.add.reduceat(diff, edges[has]) > 0
        rows = int(((out["cold_pct"] != want["cold_pct"]) | bad_lat).sum())
        rows += int(any(out["stats"][k] != want["stats"][k]
                        for k in COUNTERS))
        err = abs(out["resident_byte_seconds"]
                  - want["resident_byte_seconds"])
        bound = want["resident_bound"]
        return rows, (err / bound if bound > 0 else
                      (np.inf if err > 0 else 0.0))

    def check(self, control=None) -> Dict[str, dict]:
        """Replay the chosen invokers of the checked calls and compare.
        ``control`` puts the reference, on that float clock, in the
        program's place."""
        worst = dict(mismatch_rows=0, resident_err=0.0)
        facts = dict(checked_calls=0, checked_invokers=0, checked_apps=0,
                     checked_events=0, checked_evictions=0)
        for i in self.checked_calls(len(self.kept)):
            cfg, result = self.kept[i]
            if result is not None and (
                    result.latencies_s.size != self.n_events
                    or len(result.stats_per_worker) != self.n_workers
                    or result.cold_pct_per_app.size != len(self.counts)):
                worst["mismatch_rows"] += 1 + int((self.counts > 0).sum())
                continue
            facts["checked_calls"] += 1
            for w in self.invokers(i, result):
                apps = np.flatnonzero(self.where == w)
                want = self._replay(apps, cfg, np.float64)
                out = (self._program(apps, w, result) if control is None
                       else self._replay(apps, cfg, control))
                rows, err = self._compare(apps, out, want)
                worst["mismatch_rows"] += rows
                worst["resident_err"] = max(worst["resident_err"], err)
                facts["checked_invokers"] += 1
                facts["checked_apps"] += len(apps)
                facts["checked_events"] += int(self.counts[apps].sum())
                facts["checked_evictions"] += want["stats"]["evictions"]
        self.found = facts
        return {k: dict(value=v, limit=self.limits[k])
                for k, v in worst.items()}

    def control(self, calls: int) -> Dict[str, dict]:
        """The check with the reference on a float32 clock (the control)
        in the program's place, over the configs of ``calls`` calls."""
        self.kept = [(call_config(self.base, self.jitter, self.seed, i), None)
                     for i in range(calls)]
        return self.check(control=np.float32)

    def check_facts(self) -> dict:
        return self.found
