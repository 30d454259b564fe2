"""Entry point of the benchmark: one run of one cell.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py``. Puts the checkout's ``src`` (the program under
test) and the checkout itself (the ``bench`` package) on the path.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
