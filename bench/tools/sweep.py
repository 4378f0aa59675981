#!/usr/bin/env python3
"""Find the knee of a configuration: in one process and on one served
stack, open-loop windows of the Poisson RAG mix at rising rates, each
followed by a drain, stopping at the first rate that leaves queries
failed or unfinished; then, if the stack is still sound, one closed-loop
window of ``--clients`` callers. Prints per window the queries due and
answered, the answers per second, the median and 90th percentile
latency, the backlog at the close, generated tokens per second and the
mean decode batch.

    python3 bench/tools/sweep.py --workload <poisson cell> --rates 1.0,1.4 \
        --seconds 40 --clients 16
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

from harness import cellrun, readers, spec  # noqa: E402
from harness.device import require_chips  # noqa: E402
from harness.load import run_closed, run_open  # noqa: E402

DRAIN_S = 120.0


def report(win, **extra) -> dict:
    secs = win.t_close - win.t_open
    due = [r for r in win.queries if r.in_window]
    lat = [r.t_done - r.t_sched for r in due if r.ok]
    done_in = sum(1 for r in win.queries if r.ok
                  and win.t_open <= r.t_done < win.t_close)
    backlog = sum(1 for r in win.queries if r.t_submit < win.t_close
                  and (r.t_done is None or r.t_done >= win.t_close))
    dt = win.close_stats["decode_tokens"] - win.open_stats["decode_tokens"]
    di = win.close_stats["decode_iters"] - win.open_stats["decode_iters"]
    out = dict(extra, window_s=secs, due=len(due), ok=len(lat),
               answered_qps=done_in / secs, backlog_at_close=backlog,
               p50_s=readers.percentile(lat, 50),
               p90_s=readers.percentile(lat, 90),
               gen_tokens_per_s=dt / secs, decode_batch=dt / max(di, 1))
    print(json.dumps(out), flush=True)
    return out


def drained(win) -> bool:
    end = time.time() + DRAIN_S
    while time.time() < end:
        if all(r.ctx.done.is_set() for r in win.queries):
            return True
        time.sleep(0.2)
    return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    require_chips(cell.chips)
    cellrun.configure_cache()
    stack = cellrun.setup(cell, args.seed, T0)
    sound = True
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_qps=rate, ramp_s=10, tail_s=0)
        win = run_open(stack.orch, stack.llm, mix, args.seed, args.seconds)
        out = report(win, rate_qps=rate)
        sound = drained(win) and out["ok"] == out["due"]
        if not sound:
            print(json.dumps({"rate_qps": rate, "stopped": "failed or "
                              "unfinished queries"}), flush=True)
            break
    if sound:
        mix = dict(cell.traffic, loop="closed", clients=args.clients)
        win = run_closed(stack.orch, stack.llm, mix, args.seed,
                         args.seconds)
        report(win, clients=args.clients)
    stack.teardown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
