"""``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: run one cell once on the chip this process finds, and
print the result as the last line of standard output."""
from __future__ import annotations

import argparse
import json
import sys

from harness import spec
from harness.device import NoChip, require_chips


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t0: float) -> int:
    args = parse(argv)
    try:
        cell = spec.resolve(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"[bench] FAIL: the system under test is not importable "
              f"from {spec.ROOT / 'src'} ({e})", file=sys.stderr)
        return 2
    try:
        info = require_chips(cell.chips)
    except (NoChip, KeyError) as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 3
    from harness.cellrun import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0,
                      info)
    print(json.dumps(result), flush=True)
    return 0
