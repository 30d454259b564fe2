"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,paper_reference`` CSV rows (paper_reference empty when
the paper gives no number for that quantity).

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run --only fig_policy
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names to run")
    ap.add_argument("--apps", type=int, default=800,
                    help="trace size for the policy figures")
    args = ap.parse_args()
    use_compile_cache()

    from . import (cluster_sim, fig_cluster, fig_exec_mem, fig_policy,
                   fig_workload, forecast, kernel_bench, policy_sweep,
                   roofline, scaleout, trace_gen)
    modules = {
        "fig_workload": lambda: fig_workload.run(),
        "fig_exec_mem": lambda: fig_exec_mem.run(),
        "fig_policy": lambda: fig_policy.run(n_apps=args.apps),
        "fig_cluster": lambda: fig_cluster.run(),
        "cluster_sim": lambda: cluster_sim.run(),
        "policy_sweep": lambda: policy_sweep.run(),
        "scaleout": lambda: scaleout.run(),
        "trace_gen": lambda: trace_gen.run(),
        "forecast": lambda: forecast.run(),
        "kernel_bench": lambda: kernel_bench.run(),
        "roofline": lambda: roofline.run(),
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,value,paper_reference")
    failures = 0
    for name, fn in modules.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                key, value, ref = row
                v = f"{value:.6g}" if isinstance(value, float) else value
                print(f"{key},{v},{ref}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
