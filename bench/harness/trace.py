"""Profiler trace of a slice of the window, reduced to device metrics.

``Tracer`` starts JAX's profiler (no Python tracing) and marks the
traced slice with a host span ``bench.window``; the benchmark's own
recorder puts each LLM step call in a host span too. ``reduce`` reads
the ``.xplane.pb`` back with ``jax.profiler.ProfileData`` and returns:

- the traced window (the ``bench.window`` span) and, per device, the
  union of its operation intervals inside it (busy seconds);
- the LLM steps it ran: each module execution whose largest
  ``while`` loop carries activations of shape ``[batch, tokens,
  d_model]``, with its batch, tokens and device time (decode steps have
  one token);
- the ``breakdown``: the operations that took most device time, and
  the longest idle gaps, each named by the host span that covered most
  of it.

The reduction itself (``reduce_events``) works on plain tuples, so that
it can be checked on a small recorded trace.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

_SHAPE = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


@dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over the devices traced
    steps: List[Tuple[int, int, float]] = field(default_factory=list)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def decode_steps(self):
        return [(b, t) for b, s, t in self.steps if s == 1]


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo: float, hi: float):
    """Uncovered ``(start, end)`` stretches of ``[lo, hi]``."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


_OP = re.compile(r"^%?([\w.\-]+)\s*=\s*")
_ANY_SHAPE = re.compile(r"\w+\[\d[\d,]*\]")
TEXT = 300               # characters of an op's HLO text that are kept


def split_op(text: str):
    """``(name, rest)`` of a trace event named by its HLO instruction
    (``%while.4 = (s32[], bf16[16,1,4096], ...) while(...)``)."""
    m = _OP.match(text)
    if m is None:
        return text, ""
    return m.group(1), text[m.end():m.end() + TEXT]


def op_label(name: str, rest: str) -> str:
    """A short stable label: the op's name and the first non-scalar
    shape it produces."""
    m = _ANY_SHAPE.search(rest or "")
    return f"{name} {m.group(0)}" if m else name


def reduce_events(window, device_ops: Dict[int, list], modules: Dict[int, list],
                  host_spans: list, d_model: int) -> Summary:
    """``window``: (start_ns, end_ns). ``device_ops``/``modules``: per
    device, lists of ``(start_ns, end_ns, name, hlo_text)``.
    ``host_spans``: ``(start_ns, end_ns, name)``."""
    lo, hi = window
    win_s = (hi - lo) / 1e9
    busy = []
    by_name: Dict[str, float] = {}
    all_gaps = []
    for dev, ops in device_ops.items():
        iv = _clip([(s, e) for s, e, _, _ in ops], lo, hi)
        busy.append(union_length(iv) / 1e9)
        for s, e, name, rest in ops:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                label = op_label(name, rest)
                by_name[label] = by_name.get(label, 0.0) + (b - a) / 1e9
        all_gaps.extend(gaps(iv, lo, hi))
    steps = []
    d0 = min(device_ops) if device_ops else None
    if d0 is not None:
        ops0 = device_ops[d0]
        loops = sorted((s, e, ln) for s, e, n, ln in ops0
                       if n.startswith("while"))
        for s, e, _, _ in modules.get(d0, []) or []:
            if s < lo or e > hi:
                continue
            best = None
            for ls, le, ln in loops:
                if ls >= s and le <= e:
                    for m in _SHAPE.finditer(ln or ""):
                        if int(m.group(3)) == d_model:
                            if best is None or le - ls > best[0]:
                                best = (le - ls, int(m.group(1)),
                                        int(m.group(2)))
                            break
            if best is not None:
                steps.append((best[1], best[2], (e - s) / 1e9))
    named = []
    for a, b in sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]:
        cover: Dict[str, float] = {}
        for s, e, n in host_spans:
            ov = min(b, e) - max(a, s)
            if ov > 0 and n != WINDOW_SPAN:
                cover[n] = cover.get(n, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "no llm step in flight"
        named.append((label, (b - a) / 1e9))
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(window_s=win_s,
                   busy_s=sum(busy) / len(busy) if busy else 0.0,
                   steps=steps, device_ops=ops, idle_gaps=named)


def read_events(path: str):
    """``(window, device_ops, modules, host_spans)`` of one
    ``.xplane.pb``, as ``reduce_events`` takes them."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    host_spans = []
    device_ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            if m is None:
                if plane.name.startswith("/host"):
                    for ev in line.events:
                        if ev.name == WINDOW_SPAN:
                            window = (ev.start_ns, ev.end_ns)
                        elif ev.name.startswith("llm."):
                            host_spans.append((ev.start_ns, ev.end_ns,
                                               ev.name))
                continue
            dev = int(m.group(1))
            if line.name == OPS_LINE:
                device_ops[dev] = [(ev.start_ns, ev.end_ns,
                                    *split_op(ev.name))
                                   for ev in line.events]
            elif line.name == MODULES_LINE:
                modules[dev] = [(ev.start_ns, ev.end_ns, ev.name, "")
                                for ev in line.events]
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    if not device_ops:
        raise ValueError(f"{path}: no TPU operations in the trace")
    return window, device_ops, modules, host_spans


def reduce_file(path: str, d_model: int) -> Summary:
    """Read one ``.xplane.pb`` and reduce it."""
    return reduce_events(*read_events(path), d_model)


class Tracer:
    """Profile ``seconds`` of the window; ``summary`` after ``stop``."""

    def __init__(self, seconds: float, d_model: int):
        self.seconds = seconds
        self.d_model = d_model
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.summary: Optional[Summary] = None
        self.reduce_s = 0.0

    def run(self):
        """Trace ``seconds`` from now (blocks the calling thread)."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                time.sleep(self.seconds)
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> Summary:
        t0 = time.time()
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise ValueError("the profiler wrote no trace")
            self.summary = reduce_file(files[0], self.d_model)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.reduce_s = time.time() - t0
        return self.summary
