"""The experiment front door: declarative policy specs, one ``run()``, and
a vectorized ``sweep()`` that computes a whole policy grid in one pass.

The paper's core results (Figs. 14-18) are *sweeps* — fixed keep-alive x
{10..240}m, histogram range x {60..480}m, CV-threshold and cutoff
ablations. This module makes a configuration grid a first-class input:

    from repro.core.experiment import FixedSpec, HybridSpec, sweep

    grid = [FixedSpec(ka) for ka in (10, 20, 30, 60, 120, 240)]
    result = sweep(trace, grid)               # Fig. 14 in one call
    for spec, row in zip(result.specs, result):
        print(spec.name, row.cold_pct_percentile(75), row.total_wasted)

Workloads are specs too: everywhere a :class:`~repro.core.workload.Trace`
is accepted, a declarative :class:`~repro.core.workload_spec.WorkloadSpec`
(scenario) is accepted and materialized on entry — and ``sweep`` has a
*trace axis*, making "Fig. 14 across five workload regimes" one call:

    from repro.core.workload_spec import azure_like, bursty, timer_heavy

    grid_2d = sweep(traces=[azure_like(10_000), bursty(10_000),
                            timer_heavy(10_000)], specs=grid)
    for t, res in enumerate(grid_2d):          # (T, S) SweepGrid
        print(grid_2d.trace_name(t), res.row(0).cold_pct_percentile(75))

Each trace is bucketed/chunked ONCE (``to_padded`` hoisted out of
the per-family engines) and reused across every policy configuration; rows
of the (T, S) grid are bit-identical to the corresponding single-trace
``run()`` calls on every engine.

Specs are frozen dataclasses registered as JAX pytrees (they flatten into
their numeric knobs), each ``.build()``-able into the stateful
:class:`repro.core.policy.Policy` objects the scalar oracle and the serving
layer consume. ``sweep`` stacks same-family specs into a traced config axis
and drives the factored sweep engines in :mod:`repro.core.simulator`: the
trace is bucketed, chunked, and scanned ONCE for all S configs
instead of S times, with histogram sufficient statistics shared across
configs that agree on the histogram shape (see
:class:`repro.core.policy_math.HybridSweepBlock`).

Engines (``engine=`` on both ``run`` and ``sweep``):

  * ``"auto"``      — Pallas sweep kernel on TPU, float64 fused sweep
    elsewhere (the default).
  * ``"scalar"``    — the float64 event-driven oracle, one config at a
    time (handles everything, including exotic ``Policy`` subclasses via
    ``spec.build()``).
  * ``"fused"``     — the float64 ``lax.scan`` sweep engine.
  * ``"pallas"``    — the float32 TPU sweep kernel (compiled on TPU,
    interpreted elsewhere — decided from the backend at call time),
    gaps differenced and binned on the host in float64, SMEM config block
    via scalar prefetch.

The fixed/no-unload and SPES families have no histogram state; their
``"pallas"`` engine aliases the (already exact) float64 fused sweep.

Every engine's rows are bit-identical on cold counts, invocations, and
final windows to single-config ``run()`` and to the float64 scalar oracle
— ``tests/test_experiment_api.py`` and the conformance/golden suites
enforce it.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Union

import jax
import numpy as np

from .. import telemetry
from .histogram import HistogramConfig
from .policy import (FixedKeepAlivePolicy, HybridConfig, HybridHistogramPolicy,
                     NoUnloadingPolicy, Policy, SpesConfig, SpesPolicy)
from .simulator import (SimResult, _run_fixed_sweep, _run_hybrid_sweep,
                        _run_spes_sweep, simulate_scalar)
from .workload import Trace
from .workload_spec import WorkloadSpec, _register_pytree

__all__ = [
    "ENGINES", "PolicySpec", "FixedSpec", "NoUnloadSpec", "HybridSpec",
    "SpesSpec", "EngineOptions", "SweepResult", "SweepGrid", "as_spec",
    "as_trace", "run", "sweep",
]

ENGINES = ("auto", "scalar", "fused", "pallas")


# PolicySpec and WorkloadSpec families share one pytree-registration
# contract — the helper lives in workload_spec (the import direction).

@dataclasses.dataclass(frozen=True)
class FixedSpec:
    """The provider state of practice: ``prewarm=0``, constant keep-alive
    (AWS 10 min / Azure 20 min / OpenWhisk 10 min)."""
    keep_alive: float = 10.0
    label: Optional[str] = None

    @property
    def name(self) -> str:
        return self.label or f"fixed-{self.keep_alive:g}m"

    def build(self) -> FixedKeepAlivePolicy:
        return FixedKeepAlivePolicy(float(self.keep_alive))


@dataclasses.dataclass(frozen=True)
class NoUnloadSpec:
    """Infinite keep-alive: lower bound on cold starts, upper bound on
    waste (Fig. 14's right edge)."""
    label: Optional[str] = None

    @property
    def keep_alive(self) -> float:
        return float("inf")

    @property
    def name(self) -> str:
        return self.label or "no-unloading"

    def build(self) -> NoUnloadingPolicy:
        return NoUnloadingPolicy()


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """The paper's hybrid histogram policy, flattened to its knobs.

    Mirrors :class:`repro.core.policy.HybridConfig` /
    :class:`repro.core.histogram.HistogramConfig` field-for-field (same
    defaults, including ``use_arima=True``), but as a flat pytree whose
    leaves are exactly the axes the paper sweeps.
    """
    bin_minutes: float = 1.0          # paper: 1-minute bins
    range_minutes: float = 240.0      # paper: 4-hour default range
    head_percentile: float = 5.0      # paper: 5th percentile -> pre-warm
    tail_percentile: float = 99.0     # paper: 99th percentile -> keep-alive
    margin: float = 0.10              # paper: 10% margin both sides
    cv_threshold: float = 2.0         # paper: CV=2 default (Fig. 17)
    min_samples: int = 5              # too few ITs -> standard keep-alive
    oob_fraction_threshold: float = 0.5   # most ITs OOB -> ARIMA
    arima_min_samples: int = 4
    arima_margin: float = 0.15        # paper: 15% margin
    use_arima: bool = True
    label: Optional[str] = None

    @property
    def name(self) -> str:
        return self.label or f"hybrid-{self.range_minutes:g}m"

    def to_config(self) -> HybridConfig:
        return HybridConfig(
            histogram=HistogramConfig(
                bin_minutes=float(self.bin_minutes),
                range_minutes=float(self.range_minutes),
                head_percentile=float(self.head_percentile),
                tail_percentile=float(self.tail_percentile),
                margin=float(self.margin)),
            cv_threshold=float(self.cv_threshold),
            min_samples=int(self.min_samples),
            oob_fraction_threshold=float(self.oob_fraction_threshold),
            arima_min_samples=int(self.arima_min_samples),
            arima_margin=float(self.arima_margin),
            use_arima=bool(self.use_arima))

    @classmethod
    def from_config(cls, cfg: HybridConfig,
                    label: Optional[str] = None) -> "HybridSpec":
        h = cfg.histogram
        return cls(bin_minutes=h.bin_minutes, range_minutes=h.range_minutes,
                   head_percentile=h.head_percentile,
                   tail_percentile=h.tail_percentile, margin=h.margin,
                   cv_threshold=cfg.cv_threshold,
                   min_samples=cfg.min_samples,
                   oob_fraction_threshold=cfg.oob_fraction_threshold,
                   arima_min_samples=cfg.arima_min_samples,
                   arima_margin=cfg.arima_margin, use_arima=cfg.use_arima,
                   label=label)

    def build(self) -> HybridHistogramPolicy:
        return HybridHistogramPolicy(self.to_config())


@dataclasses.dataclass(frozen=True)
class SpesSpec:
    """SPES-style next-idle predictor policy, flattened to its knobs.

    A pure forecast policy (no histogram): a streaming exponentially-
    weighted point forecast of each app's next idle interval, with a
    confidence band that widens with the forecast residual variance —
    mapped to (prewarm, keep-alive) windows through the same
    ``policy_math`` bound helpers as every other family. Mirrors
    :class:`repro.core.policy.SpesConfig` field-for-field.
    """
    alpha: float = 0.3               # EW smoothing weight per observation
    band_margin: float = 0.10        # relative half-band around the forecast
    band_sigma: float = 1.0          # residual-std multiplier for the band
    min_samples: int = 4             # ITs before the forecast governs
    standard_keep_alive: float = 240.0   # fallback until warmed up
    label: Optional[str] = None

    @property
    def name(self) -> str:
        return self.label or f"spes-{self.alpha:g}"

    def to_config(self) -> SpesConfig:
        return SpesConfig(
            alpha=float(self.alpha), band_margin=float(self.band_margin),
            band_sigma=float(self.band_sigma),
            min_samples=int(self.min_samples),
            standard_keep_alive=float(self.standard_keep_alive))

    @classmethod
    def from_config(cls, cfg: SpesConfig,
                    label: Optional[str] = None) -> "SpesSpec":
        return cls(alpha=cfg.alpha, band_margin=cfg.band_margin,
                   band_sigma=cfg.band_sigma, min_samples=cfg.min_samples,
                   standard_keep_alive=cfg.standard_keep_alive, label=label)

    def build(self) -> SpesPolicy:
        return SpesPolicy(self.to_config())


_register_pytree(FixedSpec, meta=("label",))
_register_pytree(NoUnloadSpec, meta=("label",))
_register_pytree(HybridSpec, meta=("use_arima", "label"))
_register_pytree(SpesSpec, meta=("label",))

PolicySpec = Union[FixedSpec, NoUnloadSpec, HybridSpec, SpesSpec]
_SPEC_TYPES = (FixedSpec, NoUnloadSpec, HybridSpec, SpesSpec)


def as_spec(obj) -> PolicySpec:
    """Coerce legacy policy objects/configs to the declarative spec form.

    Accepts a ``PolicySpec`` (returned as-is), a ``HybridConfig``, or one of
    the three built-in ``Policy`` classes. Raises ``TypeError`` for
    arbitrary policies — those stay on the scalar oracle via
    ``simulate_scalar(trace, policy)``.
    """
    if isinstance(obj, _SPEC_TYPES):
        return obj
    if isinstance(obj, HybridConfig):
        return HybridSpec.from_config(obj)
    if isinstance(obj, HybridHistogramPolicy):
        return HybridSpec.from_config(obj.cfg)
    if isinstance(obj, SpesConfig):
        return SpesSpec.from_config(obj)
    if isinstance(obj, SpesPolicy):
        return SpesSpec.from_config(obj.cfg)
    if isinstance(obj, FixedKeepAlivePolicy):
        return FixedSpec(obj.keep_alive)
    if isinstance(obj, NoUnloadingPolicy):
        return NoUnloadSpec()
    raise TypeError(
        f"cannot express {type(obj).__name__} as a PolicySpec; build a "
        f"FixedSpec/NoUnloadSpec/HybridSpec/SpesSpec, or use "
        f"simulate_scalar for arbitrary Policy objects")


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Execution knobs shared by ``run`` and ``sweep`` (engine-semantic
    knobs live on the spec; these only shape *how* the engines execute)."""
    include_trailing: bool = True     # account waste after the last event
    app_chunk: Optional[int] = None   # apps per device chunk (None: auto,
    #                                   scaled down by the config-axis size)
    tile_apps: int = 1024             # Pallas kernel app-tile (one vreg
    #                                   of apps: 8 sublanes x 128 lanes)
    devices: Union[None, int, str] = None   # shard the app axis: None (off),
    #                                   an int device count (1 exercises the
    #                                   sharded path), or "auto" (every
    #                                   local device). Results stay
    #                                   bit-identical — see
    #                                   repro.distributed.scaleout. Applies
    #                                   to the vectorized sweep engines and
    #                                   the cluster policy-window scan;
    #                                   "scalar" ignores it.
    max_eviction_rounds: Optional[int] = None   # cluster cells only: cap
    #                                   the HBM-eviction fixed point; past
    #                                   it the cell falls back to the
    #                                   scalar oracle with a warning


@dataclasses.dataclass
class SweepResult:
    """S policy configurations evaluated over one trace.

    Row-major over the input spec order; ``row(s)`` materializes the
    familiar :class:`~repro.core.simulator.SimResult` view of config ``s``
    (the arrays are shared, not copied).
    """
    specs: List[PolicySpec]
    engine: str                    # the engine that ran ("auto" resolved)
    cold: np.ndarray               # [S, n_apps] int64
    invocations: np.ndarray        # [n_apps] int64 (trace property)
    wasted_minutes: np.ndarray     # [S, n_apps] float64
    final_prewarm: np.ndarray      # [S, n_apps] float64
    final_keep_alive: np.ndarray   # [S, n_apps] float64

    def __len__(self) -> int:
        return len(self.specs)

    def row(self, s: int) -> SimResult:
        return SimResult(self.cold[s], self.invocations,
                         self.wasted_minutes[s], self.final_prewarm[s],
                         self.final_keep_alive[s])

    def __iter__(self) -> Iterator[SimResult]:
        return (self.row(s) for s in range(len(self)))

    def points(self):
        """One :class:`~repro.core.metrics.PolicyPoint` per spec (named by
        ``spec.name``/``label``) — plug straight into ``pareto_frontier``."""
        from .metrics import evaluate
        return [evaluate(spec.name, self.row(s))
                for s, spec in enumerate(self.specs)]


@dataclasses.dataclass
class SweepGrid:
    """A (T, S) grid: S policy configurations over T workloads.

    ``results[t]`` is the full :class:`SweepResult` of trace ``t`` (rows
    bit-identical to single-trace ``sweep``/``run``); ``row(t, s)`` is the
    (t, s) cell as a :class:`~repro.core.simulator.SimResult`. ``traces``
    keeps the inputs as given (``Trace`` or ``WorkloadSpec``)."""
    traces: List[object]
    results: List[SweepResult]

    @property
    def shape(self):
        return (len(self.results),
                len(self.results[0]) if self.results else 0)

    @property
    def specs(self) -> List[PolicySpec]:
        return self.results[0].specs if self.results else []

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, t: int) -> SweepResult:
        return self.results[t]

    def __iter__(self) -> Iterator[SweepResult]:
        return iter(self.results)

    def row(self, t: int, s: int) -> SimResult:
        return self.results[t].row(s)

    def trace_name(self, t: int) -> str:
        obj = self.traces[t]
        return obj.name if isinstance(obj, WorkloadSpec) else f"trace-{t}"

    def points(self):
        """``points()[t]`` — the per-trace PolicyPoint lists."""
        return [res.points() for res in self.results]


def _resolve_engine(engine: str) -> str:
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of "
                         f"{ENGINES}")
    if engine == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "fused"
    return engine


def as_trace(obj) -> Trace:
    """Coerce the workload argument: a ``Trace`` passes through, a
    declarative ``WorkloadSpec`` is materialized by the vectorized engine."""
    if isinstance(obj, Trace):
        return obj
    if isinstance(obj, WorkloadSpec):
        return obj.materialize()
    raise TypeError(
        f"expected a Trace or WorkloadSpec, got {type(obj).__name__}")


def _sweep_one(trace: Trace, specs: Sequence, eng: str,
               opts: EngineOptions) -> SweepResult:
    with telemetry.span("sweep"):
        return _sweep_families(trace, specs, eng, opts)


def _sweep_families(trace: Trace, specs: Sequence, eng: str,
                    opts: EngineOptions) -> SweepResult:
    n = trace.n_apps
    S = len(specs)
    cold = np.zeros((S, n), np.int64)
    waste = np.zeros((S, n), np.float64)
    pre = np.zeros((S, n), np.float64)
    keep = np.zeros((S, n), np.float64)
    inv: Optional[np.ndarray] = None

    def fill(rows, out):
        nonlocal inv
        if isinstance(out, SimResult):
            out = dict(cold=out.cold, wasted_minutes=out.wasted_minutes,
                       final_prewarm=out.final_prewarm,
                       final_keep_alive=out.final_keep_alive,
                       invocations=out.invocations)
        with telemetry.span("sweep.fill"):
            cold[rows] = out["cold"]
            waste[rows] = out["wasted_minutes"]
            pre[rows] = out["final_prewarm"]
            keep[rows] = out["final_keep_alive"]
        inv = out["invocations"]

    if eng == "scalar":
        for s, spec in enumerate(specs):
            fill([s], simulate_scalar(trace, spec.build(),
                                      opts.include_trailing))
        return SweepResult(specs, eng, cold, inv, waste, pre, keep)

    window_idx = [s for s, sp in enumerate(specs)
                  if isinstance(sp, (FixedSpec, NoUnloadSpec))]
    hybrid_idx = [s for s, sp in enumerate(specs)
                  if isinstance(sp, HybridSpec)]
    spes_idx = [s for s, sp in enumerate(specs)
                if isinstance(sp, SpesSpec)]

    # The trace is padded ONCE for every family and config (list-backed
    # traces rebuild the padded arrays on each to_padded call).
    with telemetry.span("sweep.pad"):
        padded = trace.to_padded()
    if window_idx:
        # No histogram state in this family — the float64 fused sweep is
        # already oracle-exact, so "pallas" aliases it.
        out = _run_fixed_sweep(trace, [specs[s].keep_alive
                                       for s in window_idx],
                               opts.include_trailing, padded=padded,
                               devices=opts.devices)
        fill(window_idx, out)
    if hybrid_idx:
        cfgs = [specs[s].to_config() for s in hybrid_idx]
        out = _run_hybrid_sweep(
            trace, cfgs, opts.include_trailing,
            app_chunk=opts.app_chunk, use_pallas=(eng == "pallas"),
            tile_apps=opts.tile_apps,
            padded=padded, devices=opts.devices)
        fill(hybrid_idx, out)
    if spes_idx:
        # Like the fixed family: no per-bin state, and the float64 fused
        # scan is already oracle-exact, so "pallas" aliases it.
        out = _run_spes_sweep(
            trace, [specs[s].to_config() for s in spes_idx],
            opts.include_trailing, app_chunk=opts.app_chunk,
            padded=padded, devices=opts.devices)
        fill(spes_idx, out)
    assert inv is not None  # every spec belongs to one of the families
    return SweepResult(specs, eng, cold, inv, waste, pre, keep)


def sweep(trace=None, specs: Sequence = None, *, traces=None, clusters=None,
          engine: str = "auto", options: Optional[EngineOptions] = None):
    """Evaluate a policy grid over one workload — or a (T, S) grid.

    ``sweep(trace, specs)`` evaluates S policy configurations over one
    workload (a ``Trace`` or a ``WorkloadSpec``) in one device pass:
    ``specs`` may mix families (fixed / no-unload / hybrid); each family is
    stacked into its own traced config axis and the trace is prepared once.
    Rows come back in input order and are bit-identical (cold counts,
    invocations, final windows) to the corresponding single-config
    :func:`run`. Returns a :class:`SweepResult`.

    ``sweep(traces=[...], specs=[...])`` adds the trace axis: every
    workload (again ``Trace`` or ``WorkloadSpec``, freely mixed) is
    materialized and prepared once, swept over the whole policy grid, and
    the T :class:`SweepResult` rows come back as a :class:`SweepGrid`.

    ``sweep(..., clusters=[ClusterSpec(...), ...])`` adds the *cluster*
    axis: instead of the single-pool simulators, every cell runs the
    fleet engine (:mod:`repro.serving.cluster_vector`) and the
    trace x policy x cluster grid comes back as a
    :class:`~repro.serving.cluster_vector.ClusterSweep`. Cluster engines
    are ``"auto"``/``"vector"``/``"scalar"``.
    """
    if specs is None:
        raise TypeError("sweep() requires specs (a list of PolicySpec)")
    specs = [as_spec(s) for s in specs]
    if not specs:
        raise ValueError("sweep() needs at least one PolicySpec")
    if (trace is None) == (traces is None):
        raise TypeError("pass exactly one of trace= or traces=")
    if clusters is not None:
        from ..serving.cluster_vector import sweep_cluster
        return sweep_cluster(traces if traces is not None else trace,
                             specs, clusters, engine=engine,
                             app_chunk=(options.app_chunk
                                        if options is not None else None),
                             devices=(options.devices
                                      if options is not None else None),
                             max_eviction_rounds=(
                                 options.max_eviction_rounds
                                 if options is not None else None))
    opts = options or EngineOptions()
    eng = _resolve_engine(engine)
    if traces is None:
        return _sweep_one(as_trace(trace), specs, eng, opts)
    traces = list(traces)
    if not traces:
        raise ValueError("sweep() needs at least one trace")
    return SweepGrid(traces=traces,
                     results=[_sweep_one(as_trace(t), specs, eng, opts)
                              for t in traces])


def run(trace, spec, *, engine: str = "auto", cluster=None,
        options: Optional[EngineOptions] = None):
    """Evaluate one policy configuration (the S=1 sweep) over one workload
    (``Trace`` or ``WorkloadSpec``). With ``cluster=`` (a
    :class:`~repro.serving.cluster_vector.ClusterSpec`), the cell runs the
    fleet simulator instead and returns a
    :class:`~repro.serving.cluster_sim.ClusterResult`."""
    if cluster is not None:
        from ..serving.cluster_vector import run_cluster
        return run_cluster(trace, spec, cluster, engine=engine,
                           app_chunk=(options.app_chunk
                                      if options is not None else None),
                           devices=(options.devices
                                    if options is not None else None),
                           max_eviction_rounds=(
                               options.max_eviction_rounds
                               if options is not None else None))
    return sweep(trace, [spec], engine=engine, options=options).row(0)
