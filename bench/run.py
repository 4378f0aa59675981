#!/usr/bin/env python3
"""Run one benchmark cell once (see ``BENCHMARK.json``):

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
checks' numbers, each beside its limit, are the last lines of standard
error. Exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

T0 = time.time()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
