#!/usr/bin/env python3
"""Cut the recorded trace that the trace-reduction tests read
(``bench/tests/trace_slice.json``) from a profiler trace, and print the
trace's planes, lines and first events as text.

    python3 bench/tools/trace_slice.py <trace.xplane.pb> <out.json> [--seconds 0.25]

The trace has to hold a ``bench.window`` host span: one that
``harness.trace.Tracer.run`` wrote (its ``dir`` holds the ``.xplane.pb``
until ``reduce`` runs).
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness.trace import read_events  # noqa: E402


def dump_slice(path: str, out_path: str, seconds: float):
    """Write the first ``seconds`` of the trace's window, as
    ``reduce_events`` takes it, to a JSON file."""
    (lo, hi), dev_ops, mods, spans = read_events(path)
    hi = min(hi, lo + seconds * 1e9)

    def keep(evs):
        return [list(e) for e in evs if e[1] > lo and e[0] < hi]

    with open(out_path, "w") as f:
        json.dump({"window": [lo, hi],
                   "device_ops": {d: keep(v) for d, v in dev_ops.items()},
                   "modules": {d: keep(v) for d, v in mods.items()},
                   "host_spans": keep(spans)}, f)


def describe(path: str, per_line: int = 25):
    """Print the planes, lines and first events of a trace."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:per_line]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} "
                      f"stats={str(list(ev.stats))[:400]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.25)
    args = ap.parse_args(argv)
    dump_slice(args.trace, args.out, args.seconds)
    describe(args.trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
