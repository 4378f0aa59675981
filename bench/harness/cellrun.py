"""One run of one cell: set-up, the measured window, the correctness
check, the metrics and the result line."""
from __future__ import annotations

import math
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional

from harness import check, device
from harness.load import RUNNERS
from harness.system import Stack
from harness.trace import Tracer
from harness.warm import warm_encoders, warm_llm

TRACE_SECONDS = 8.0      # profiled slice of a --trace 1 run's window
TRACE_AFTER = 2.0        # seconds after the window opens that it starts


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.hits = 0
        self.traces = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.compiles, self.hits, self.traces)


@dataclass
class CellRun:
    """What the metric readers read."""
    cell: object
    chips: int
    setup_s: float
    window: object
    recorder: object
    peaks: dict
    trace: Optional[object] = None
    trace_wall: tuple = (0.0, 0.0)

    def window_queries(self):
        return [r for r in self.window.queries if r.in_window]

    def cost(self, tokens, context, logits):
        return self.cell.arch.step_cost(self.cell.config, tokens, context,
                                        logits)


def configure_cache():
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    from repro.launch.device import startup
    startup("bench")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def setup(cell, seed: int, t0: float, stack: Stack = None):
    """Weights, engines, orchestrator and warm-up; returns the stack."""
    t = time.time()
    if stack is None:
        stack = Stack(cell, seed, log=log)
    log(f"set-up: weights made in {time.time() - t:.2f}s "
        f"(at {time.time() - t0:.2f}s)")
    t = time.time()
    stack.build()
    log(f"set-up: engines built in {time.time() - t:.2f}s")
    t = time.time()
    n = warm_encoders(stack.engines)
    log(f"set-up: encoders warmed ({n} shapes) in {time.time() - t:.2f}s")
    t = time.time()
    n = warm_llm(stack.llm, cell.traffic["context_blocks"])
    log(f"set-up: LLM warmed ({n} calls) in {time.time() - t:.2f}s")
    return stack


def serve_window(stack, cell, seed: int, seconds: float, trace: bool,
                 t0: float, counter: CompileCounter):
    """Drive the window; returns (window, recorder, marks, tracer)."""
    rec = check.Recorder(stack.llm)
    mark = {}
    tracer = Tracer(TRACE_SECONDS, cell.arch.dims(cell.config)["d"]) \
        if trace else None

    def traced():
        time.sleep(TRACE_AFTER)
        rec.annotate = True
        mark["trace0"] = time.time()
        try:
            tracer.run()
        finally:
            mark["trace1"] = time.time()
            rec.annotate = False

    def on_open():
        mark["setup_s"] = time.time() - t0
        mark["open"] = counter.snapshot()
        if tracer is not None:
            mark["tracer"] = threading.Thread(target=traced,
                                              name="bench-tracer")
            mark["tracer"].start()

    def on_close():
        mark["close"] = counter.snapshot()

    run = RUNNERS[cell.traffic["loop"]]
    win = run(stack.orch, stack.llm, cell.traffic, seed, seconds,
              on_open=on_open, on_close=on_close, log=log)
    if "tracer" in mark:
        mark.pop("tracer").join()
    o, c = mark["open"], mark["close"]
    log(f"window: {win.t_close - win.t_open:.3f}s, compiles inside "
        f"{c[0] - o[0]}, cache loads {c[1] - o[1]}, traces {c[2] - o[2]}; "
        f"sender lag {win.sender_lag_s:.3f}s")
    return win, rec, mark, tracer


def correctness(stack, cell, seed, win, rec):
    """The checks' numbers (after the engines are gone)."""
    ok_q = [r for r in win.queries if r.in_window and r.ok]
    qids = {r.ctx.qid for r in ok_q}
    rows = {sid: v for sid, v in check.sequence_rows(rec.seqs).items()
            if sid.split(":")[0] in qids}
    sids = check.sample_sequences(rows, seed)
    stores = [dict(r.ctx.store) for r in check.sample_queries(ok_q, seed)]
    return rows, sids, stores


def finish(stack, cell, seed: int, seconds: float, win, rec, mark, tracer,
           info: dict, trace: bool, controls: bool = False) -> dict:
    """Read the device's memory peak, drop the engines, reduce the
    trace, run the checks and the metric readers; the result line."""
    mem = device.memory()
    peak = int(mem.get("peak_bytes_in_use", 0))
    rows, sids, stores = correctness(stack, cell, seed, win, rec)
    rec.detach()
    stack.teardown()
    run = CellRun(cell=cell, chips=cell.chips,
                  setup_s=mark["setup_s"], window=win, recorder=rec,
                  peaks=device.PEAKS.get(info["kind"]))
    if tracer is not None:
        run.trace = tracer.reduce()
        run.trace_wall = (mark["trace0"], mark["trace1"])
        log(f"trace: {run.trace.window_s:.3f}s window, busy "
            f"{run.trace.busy_s:.3f}s, {len(run.trace.steps)} LLM steps, "
            f"reduced in {tracer.reduce_s:.1f}s")
    t = time.time()
    ref = cell.arch.Reference(cell.config)
    ctl = (cell.arch.Reference(cell.config, quant="fp8"),) if controls \
        else ()
    nums = check.llm_gaps(ref, stack.weights, rows, sids, ctl)
    nums.update(check.retrieval_gaps(stack.enc_mod, cell.config["encoders"],
                                     stack.enc_weights, stores,
                                     control=controls))
    log(f"check: {len(sids)} sequences, {nums['positions']} served "
        f"positions, {len(stores)} queries, in {time.time() - t:.1f}s")
    due = run.window_queries()
    n_due = len(due)
    if cell.traffic["loop"] == "open":
        n_due = max(n_due, round(float(cell.traffic["rate_qps"]) * seconds))
    failed = n_due - sum(1 for r in due if r.ok)
    ends = [r for r in due if r.ok and win.t_open <= r.t_done < win.t_close]
    log(f"queries: {n_due} in the window, {failed} failed, "
        f"{len(ends)} completed inside it; "
        f"{len(rows)} LLM sequences logged")
    for r in due:
        if not r.ok:
            err = None if r.ctx is None else r.ctx.error
            log(f"query {r.idx} failed: "
                f"{'did not finish' if err is None else repr(err)}")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(run)
        if v is None or not math.isfinite(v):
            log(f"metric {m.name}: nothing to read")
            continue
        metrics[m.name] = {"value": v, "unit": m.unit}
    limits = cell.config["limits"]
    log(f"retrieval_gap {nums['retrieval_gap']} (not compared: see "
        f"PERF.md)")
    checks = {name: {"value": nums[name], "limit": limits[name]}
              for name in ("llm_gap", "retrieval_err", "chunks_differ")}
    checks["failed_queries"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = {"platform": info["platform"], "kind": info["kind"],
           "count": info["count"], "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": n_due, "failed": failed,
           "metrics": metrics, "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    if controls:
        out["controls"] = {k: v for k, v in nums.items() if "." in k}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             info: dict) -> dict:
    """One run: set-up, window, checks, metrics."""
    configure_cache()
    counter = CompileCounter()
    stack = setup(cell, seed, t0)
    win, rec, mark, tracer = serve_window(stack, cell, seed, seconds, trace,
                                          t0, counter)
    return finish(stack, cell, seed, seconds, win, rec, mark, tracer, info,
                  trace)
