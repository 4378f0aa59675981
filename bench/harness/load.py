"""The measured window: queries sent to the orchestrator as the mix says,
with the window's clock, the engine's counters at its edges and one
record per query.

Open loop: one sender thread submits each query at its scheduled time
(``traffic.open_schedule``); the window's queries are exactly those due
inside it, each timed from its scheduled send time to its completion.
Sends go on after the close while those queries finish (at most
``tail_s``), so they finish under the same load.

Closed loop: ``clients`` threads, each submitting its next query when
the last one is answered. The window opens once every client has had
one answer, and after the close each client finishes the query it has
in flight and stops.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from harness import traffic as tr

QUERY_WAIT = 120.0       # seconds a closed-loop client waits for one answer
FINISH_GRACE = 60.0      # seconds past the close for the window's queries


@dataclass
class QueryRecord:
    idx: int
    t_sched: float
    t_submit: float
    ctx: object = None
    in_window: bool = False

    @property
    def t_done(self) -> Optional[float]:
        return self.ctx.t_done if self.ctx is not None and \
            self.ctx.done.is_set() else None

    @property
    def ok(self) -> bool:
        return self.t_done is not None and self.ctx.error is None


@dataclass
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    open_stats: dict = field(default_factory=dict)
    close_stats: dict = field(default_factory=dict)
    queries: List[QueryRecord] = field(default_factory=list)
    sender_lag_s: float = 0.0


def stats(eng) -> dict:
    with eng._stats_lock:
        return dict(eng.stats)


def _sleep_until(t: float, stop: threading.Event = None):
    d = t - time.time()
    if d > 0:
        if stop is None:
            time.sleep(d)
        else:
            stop.wait(d)


def run_open(orch, eng, mix: dict, seed: int, seconds: float,
             on_open=None, on_close=None, log=print) -> Window:
    times, sizes, (w0, w1) = tr.open_schedule(mix, seed, seconds)
    win = Window()
    stop = threading.Event()
    base = time.time() + 0.05
    lag = [0.0]

    def sender():
        for i, t in enumerate(times):
            _sleep_until(base + t, stop)
            if stop.is_set():
                return
            q = tr.make_query(seed, i, sizes[i])
            now = time.time()
            lag[0] = max(lag[0], now - (base + t))
            rec = QueryRecord(i, base + t, now,
                              in_window=w0 <= t < w1)
            rec.ctx = orch.submit(q)
            win.queries.append(rec)

    th = threading.Thread(target=sender, name="bench-sender", daemon=True)
    th.start()
    _sleep_until(base + w0)
    win.t_open = time.time()
    win.open_stats = stats(eng)
    if on_open:
        on_open()
    _sleep_until(base + w1)
    win.t_close = time.time()
    win.close_stats = stats(eng)
    if on_close:
        on_close()
    deadline = win.t_close + FINISH_GRACE
    while time.time() < deadline:
        due = [r for r in win.queries if r.in_window]
        if len(due) >= round(float(mix["rate_qps"]) * seconds) and \
                all(r.ctx.done.is_set() for r in due):
            break
        time.sleep(0.05)
    stop.set()
    th.join()
    win.sender_lag_s = lag[0]
    return win


def run_closed(orch, eng, mix: dict, seed: int, seconds: float,
               on_open=None, on_close=None, log=print) -> Window:
    n = int(mix["clients"])
    win = Window()
    stop = threading.Event()
    answered = [threading.Event() for _ in range(n)]
    lock = threading.Lock()

    def client(c):
        k = 0
        while not stop.is_set():
            idx = tr.closed_queries(mix, c, k)
            q = tr.make_query(seed, idx, tr.closed_sizes(mix, seed, idx))
            now = time.time()
            rec = QueryRecord(idx, now, now)
            rec.ctx = orch.submit(q)
            with lock:
                win.queries.append(rec)
            rec.ctx.done.wait(QUERY_WAIT)
            if not rec.ctx.done.is_set() or rec.ctx.error is not None:
                return
            answered[c].set()
            k += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client{c}") for c in range(n)]
    for th in threads:
        th.start()
    deadline = time.time() + QUERY_WAIT
    for ev in answered:
        ev.wait(max(0.0, deadline - time.time()))
    win.t_open = time.time()
    win.open_stats = stats(eng)
    if on_open:
        on_open()
    time.sleep(max(0.0, win.t_open + seconds - time.time()))
    win.t_close = time.time()
    win.close_stats = stats(eng)
    if on_close:
        on_close()
    stop.set()
    end = win.t_close + FINISH_GRACE
    for th in threads:
        th.join(max(0.0, end - time.time()))
    with lock:
        for r in win.queries:
            r.in_window = r.t_submit < win.t_close and (
                r.t_done is None or r.t_done > win.t_open)
    return win


RUNNERS = {"open": run_open, "closed": run_closed}
