"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader takes a ``CellRun`` and returns a number, or None where the
run holds nothing to read (the harness then leaves the metric out).
"""
from __future__ import annotations

import statistics
from typing import List, Optional


def latencies(run) -> List[float]:
    """Seconds from scheduled send to completion of every query of the
    window that finished without error."""
    return [r.t_done - r.t_sched for r in run.window_queries() if r.ok]


def percentile(values, q: int) -> Optional[float]:
    """The q-th percentile (q in 1..99) of all ``values``, interpolated
    between order statistics (``statistics.quantiles``, inclusive)."""
    values = list(values)
    if not values:
        return None
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[q - 1])


def counter_delta(run, key: str) -> float:
    w = run.window
    return float(w.close_stats[key] - w.open_stats[key])


def gen_tokens_per_s(run) -> Optional[float]:
    """Tokens the core LLM generated in the window over its seconds."""
    w = run.window
    secs = w.t_close - w.t_open
    if secs <= 0:
        return None
    return counter_delta(run, "decode_tokens") / secs


def decode_batch(run) -> Optional[float]:
    """Mean sequences per decode step in the window."""
    iters = counter_delta(run, "decode_iters")
    if iters <= 0:
        return None
    return counter_delta(run, "decode_tokens") / iters


def llm_share(run) -> Optional[float]:
    """Percent of the window's query latency (submit to done) covered by
    the query's core-LLM primitives' spans, summed over the queries."""
    covered = total = 0.0
    for r in run.window_queries():
        if not r.ok:
            continue
        ctx = r.ctx
        lo, hi = ctx.t_submit, ctx.t_done
        iv = []
        for pid, (a, b) in ctx.node_spans.items():
            node = ctx.graph.nodes.get(pid)
            if node is None or node.engine != "core_llm" or b is None:
                continue
            a, b = max(a, lo), min(b, hi)
            if b > a:
                iv.append((a, b))
        from harness.trace import union_length
        covered += union_length(iv)
        total += hi - lo
    return 100.0 * covered / total if total > 0 else None


def idle_share(run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def _in(steps, lo, hi):
    return [s for s in steps if lo <= s[0] < hi]


def step_flops(run, step) -> float:
    """Model operations of one recorded LLM step."""
    if len(step) == 4:                       # decode: t0, t1, batch, ctx
        _, _, n, ctx = step
        return run.cost(n, ctx / n, n)["flops"]
    _, _, chunks, toks, ctx = step
    return run.cost(toks, ctx / max(toks, 1), chunks)["flops"]


def mfu(run) -> Optional[float]:
    """Percent of the chip's bf16 peak that the model operations of every
    token the core LLM processed in the window (prefill and decode)
    amount to, over the window's seconds."""
    w = run.window
    secs = w.t_close - w.t_open
    steps = _in(run.recorder.decode_steps, w.t_open, w.t_close) + \
        _in(run.recorder.prefill_steps, w.t_open, w.t_close)
    if secs <= 0 or not steps or run.peaks is None:
        return None
    flops = sum(step_flops(run, s) for s in steps)
    return 100.0 * flops / secs / run.peaks["bf16_flops"] / run.chips


def step_roofline(run) -> Optional[float]:
    """Percent: the least time of the traced decode steps (each the
    larger of its operations over the bf16 peak and its bytes, weights
    and KV cache, over the HBM bandwidth) over their device time."""
    if run.trace is None or run.peaks is None:
        return None
    dev = [t for _, t in run.trace.decode_steps()]
    lo, hi = run.trace_wall
    host = _in(run.recorder.decode_steps, lo, hi)
    if not dev or not host:
        return None
    least = 0.0
    for _, _, n, ctx in host:
        c = run.cost(n, ctx / n, n)
        nbytes = c["weight_bytes"] + c["kv_bytes_per_token"] * (ctx + n)
        least += max(c["flops"] / run.peaks["bf16_flops"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    # the host saw len(host) steps in the slice, the trace len(dev):
    # compare per step
    return 100.0 * (least / len(host)) / (sum(dev) / len(dev))
