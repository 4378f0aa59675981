"""Share of the window's answered queries' latency (submit to done) in
which some primitive of the query was ready but not yet begun by an
engine and none was running, summed over the queries (%). A primitive is
ready from its ``node_spans`` start and running from its ``node_start``
to its ``node_spans`` end. None where the queries carry no
``node_start``."""
from harness.trace import union_length


def _wait(ctx) -> float:
    lo, hi = ctx.t_submit, ctx.t_done
    ready, running = [], []
    for pid, (a, b) in ctx.node_spans.items():
        s = ctx.node_start.get(pid)
        if b is None or s is None:
            continue
        ready.append((max(a, lo), min(s, hi)))
        running.append((max(s, lo), min(b, hi)))
    ready = [(a, b) for a, b in ready if b > a]
    running = [(a, b) for a, b in running if b > a]
    return union_length(ready + running) - union_length(running)


def read(run):
    waited = total = 0.0
    for r in run.window_queries():
        if not r.ok:
            continue
        if not hasattr(r.ctx, "node_start"):
            return None
        waited += _wait(r.ctx)
        total += r.ctx.t_done - r.ctx.t_submit
    return 100.0 * waited / total if total > 0 else None
