"""The control of a cell's check, at the cell's own size.

  python3 bench/control.py --workload <cell> --seeds <a,b,c> --calls <n>

Puts the reference, computed one precision below what the configuration
states, in the program's place for the first ``n`` calls' grids over each
seed's sample of apps, and prints the numbers the check compares, each
with its limit, and ``correct`` as a run decides it. The sweep cells'
control replays in float32 (the configuration states a float64 clock). A
sound control reads ``correct: false``; the benchmark's own runs never
run it. It needs no chip.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description="Read a cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--calls", type=int, required=True)
    args = ap.parse_args(argv)
    r = harness.resolve(harness.load_json(ROOT, "BENCHMARK.json"),
                        args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        work = r["driver"].Work(r["config"], r["traffic"], seed, None,
                                log=lambda *a, **k: None)
        got = work.control(args.calls)
        print(json.dumps(dict(control=args.workload, seed=seed,
                              correct=harness.is_correct(got), checks=got,
                              seconds=time.perf_counter() - t0,
                              **work.check_facts())), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
