"""Benchmark entry point.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this machine holds and prints
its result as the last line of standard output (see ``bench/harness.py``).
Exits non-zero, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], os.path.dirname(BENCH), T_START))
