"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``, the
deployment: fleet size, span, cluster) and a traffic mix
(``bench/traffic/<traffic>.json``), whose ``driver`` key names the module
under ``bench/drivers/`` that sets the cell up, makes each timed call and
checks the answers against the plain reference. Per-layer metrics are
readers under ``bench/metrics/<name>.py``. Everything is found by name,
so a new cell, mix or metric is a new file and an entry in
``BENCHMARK.json``, never an edit.

A run: checks for the chips the cell asks for (no TPU: exit 2, no
result), points JAX's persistent compilation cache at the checkout, sets
the cell up (fleet, one whole warm-up call), calls back to back for
``--seconds``, reads the device's peak memory, then compares the answers
with the reference. With ``--trace 1`` the window runs under the profiler
and the per-layer metrics replace the end-to-end ones. The last line of
standard output is the result; the compared numbers and their limits are
the last lines of standard error and the last key of the result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
import traceback
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (metric readers' names carry dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell ``workload`` with its configuration, traffic mix, driver
    module, end-to-end metrics and per-layer metric readers."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root, configs[cell["config"]]["file"])
    traffic = load_json(root, "bench", "traffic", cell["traffic"] + ".json")
    driver = load_module(os.path.join(root, "bench", "drivers",
                                      traffic["driver"] + ".py"),
                         "bench_driver_" + traffic["driver"])
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if (workload in m["workloads"] if "workloads" in m
                  else m["moves"] in reported)]
    readers = {m["name"]: load_module(
        os.path.join(root, "bench", "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in layers}
    return dict(cell=cell, config=config, traffic=traffic, driver=driver,
                end_to_end=e2e, per_layer=layers, readers=readers)


class CompileCounter:
    """Counts XLA compilations (and their seconds) from JAX's monitoring
    events; a program found in the persistent cache is not compiled."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def use_compile_cache() -> str:
    """JAX's persistent cache at the checkout's fixed path (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program however
    quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def say(*parts, **kw) -> None:
    print(" ".join(str(p) for p in parts),
          " ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


def is_correct(checks: Dict[str, dict]) -> bool:
    """Every compared number within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def memory_peak(devices) -> Optional[int]:
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in devices if d.memory_stats()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             log: Callable = say, save_trace: Optional[str] = None
             ) -> Optional[dict]:
    """One run; returns the result line's object, or None (after printing
    why) when the chips the cell needs are not there. ``save_trace``
    writes a small excerpt of a traced run's trace there as JSON."""
    import jax
    cell, driver = resolved["cell"], resolved["driver"]
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"bench: {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"found {len(devices)} {platform!r} device(s)",
              file=sys.stderr)
        return None
    used = devices[:cell["chips"]]
    counter = CompileCounter()
    cache = use_compile_cache()
    log("device", platform=platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__, cache=cache)

    work = driver.Work(resolved["config"], resolved["traffic"], seed,
                       expect_engine="pallas" if platform == "tpu" else None,
                       log=log)
    t_warm = time.perf_counter()
    work.warm()
    setup_s = time.perf_counter() - t_start
    log("setup", setup_s=setup_s, warm_s=time.perf_counter() - t_warm,
        compiles=counter.count, compile_s=counter.seconds, **work.setup)

    capture = None
    if trace:
        from bench.trace import Capture
        capture = Capture()
    c0 = counter.count
    ends = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            work.call(len(ends))
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= seconds:
            break
    calls, elapsed = len(ends), ends[-1]
    traced = capture.stop() if capture is not None else None
    peak = memory_peak(used)
    log("window", calls=calls, window_s=elapsed,
        window_compiles=counter.count - c0, memory_peak_bytes=peak,
        call_s=",".join(f"{b - a:.4f}" for a, b in zip([0.0] + ends, ends)),
        **work.facts(calls, elapsed))

    t_check = time.perf_counter()
    checks = work.check()
    log("check", check_s=time.perf_counter() - t_check,
        **work.check_facts())
    correct = is_correct(checks)

    device = dict(platform=platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=peak)
    metrics: Dict[str, dict] = {}
    breakdown = None
    if traced is None:
        rate = work.rate(calls, elapsed)
        for m in resolved["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else rate.get(m["name"])
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
    else:
        from bench import trace as tr
        from bench.peaks import peaks
        log(tr.summary(traced))
        if save_trace:
            with open(save_trace, "w") as f:
                json.dump(tr.excerpt(traced), f)
        busy = tr.busy_seconds(traced)
        span = tr.window(traced)
        if not busy or span is None:
            raise RuntimeError("the trace holds no device operation inside "
                               "the timed calls: " + tr.summary(traced))
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = (span[1] - span[0]) / 1e9
        log("busy", per_device=",".join(f"{b:.6f}" for b in busy))
        ctx = dict(work.trace_context(calls), chips=len(used),
                   peaks=peaks(devices[0].device_kind) if platform == "tpu"
                   else None, log=log)
        for m in resolved["per_layer"]:
            value = resolved["readers"][m["name"]].reduce(traced, ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        breakdown = tr.breakdown(traced)
    result = dict(correct=correct, attempted=work.attempted(calls),
                  failed=0, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: dict(value=c["value"], limit=c["limit"])
                        for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv: List[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="with --trace 1: write a small excerpt of the "
                    "trace to this JSON file (a test input)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    bench = load_json(ROOT, "BENCHMARK.json")
    resolved = resolve(bench, args.workload)
    try:
        result = run_cell(resolved, args.seed, args.seconds,
                          bool(args.trace), t_start,
                          save_trace=args.save_trace)
    except Exception:
        traceback.print_exc()
        return 1
    if result is None:
        return 2
    print(json.dumps(result))
    return 0
