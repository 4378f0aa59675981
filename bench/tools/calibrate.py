#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: one process, one
set-up, then for each seed new weights and engines, a window of the
cell's own traffic and the checks, with the control (the reference one
precision lower put in the system's place) read on the same prompts and
tokens. Prints one JSON line per seed.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

from harness import cellrun, spec  # noqa: E402
from harness.device import require_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    info = require_chips(cell.chips)
    cellrun.configure_cache()
    counter = cellrun.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    stack = None
    for i, seed in enumerate(seeds):
        t0 = time.time()
        if stack is None:
            stack = cellrun.setup(cell, seed, T0)
        else:
            stack.make_weights(seed)
            stack.build()
        win, rec, mark, tracer = cellrun.serve_window(
            stack, cell, seed, args.seconds, False, t0, counter)
        out = cellrun.finish(stack, cell, seed, args.seconds, win, rec, mark,
                             tracer, info, False, controls=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "controls": out["controls"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
