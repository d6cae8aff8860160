"""Run one benchmark cell and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (configuration, traffic, metrics) is looked up by name in
``BENCHMARK.json``. It needs the accelerator the cell asks for: without
one it exits non-zero and prints no result. See ``bench/harness.py``.
"""
import time

T0 = time.monotonic()   # set-up is timed from here: the process's start

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        sys.exit(2)
    from bench.harness import main
    sys.exit(main(t0=T0))
