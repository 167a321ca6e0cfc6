"""Runs a benchmark cell on the CPU at whatever size its files give: the
harness's look for a TPU is replaced by JAX's CPU devices, and a fault from
``plants.py`` may be planted first. For the self-tests only; the numbers it
prints are not device numbers.

  python bench/tests/cpu_run.py <checkout root> [--plant <fault>] \
      --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    root = os.path.abspath(sys.argv[1])
    argv = sys.argv[2:]
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, os.path.join(root, "bench"))
    import jax  # noqa: E402

    import harness  # noqa: E402
    harness.find_devices = lambda chips: jax.devices()[:chips]
    if argv[:1] == ["--plant"]:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from plants import plant  # noqa: E402
        plant(argv[1])
        argv = argv[2:]
    sys.exit(harness.main(argv, root, T_START))
