"""The readers of the program's own spans and counters, on synthetic runs:
each computes what it says, and reads nothing (None, no error) where the
program keeps no such counter or stamp."""
import os
import sys
from pathlib import Path
from threading import Event
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from harness import spec  # noqa: E402
from harness.load import QueryRecord, Window  # noqa: E402

PHASES = {"plan_s": 1.0, "prepare_s": 2.0, "launch_s": 0.5,
          "wait_s": 30.0, "readback_s": 1.5, "emit_s": 0.5}


def _metric(name):
    return spec.load_module(spec.metric_path(name),
                            "t_" + name.replace(".", "_")).read


def _run(open_stats=None, close_stats=None, ctxs=(), window=(100.0, 150.0)):
    qs = [QueryRecord(i, t_sched=c.t_submit, t_submit=c.t_submit, ctx=c,
                      in_window=True) for i, c in enumerate(ctxs)]
    w = Window(t_open=window[0], t_close=window[1], queries=qs,
               open_stats=dict(open_stats or {}),
               close_stats=dict(close_stats or {}))
    return SimpleNamespace(window=w, window_queries=lambda: qs)


def _ctx(t_submit, t_done, spans, starts=None, error=None):
    done = Event()
    done.set()
    c = SimpleNamespace(t_submit=t_submit, t_done=t_done, done=done,
                        error=error, node_spans=dict(spans))
    if starts is not None:
        c.node_start = dict(starts)
    return c


@pytest.mark.parametrize("cell", ["saturated", "poisson"])
def test_loop_host_share_leaves_out_the_wait_for_the_device(cell):
    read = _metric(f"llm.loop_host_share.{cell}")
    zero = {k: 0.0 for k in PHASES}
    run = _run(zero, PHASES)
    assert read(run) == pytest.approx(100.0 * 5.5 / 50.0)
    half = {k: v / 2 for k, v in PHASES.items()}
    assert read(_run(half, PHASES)) == pytest.approx(100.0 * 2.75 / 50.0)


@pytest.mark.parametrize("cell", ["saturated", "poisson"])
def test_new_shapes_is_a_counter_delta(cell):
    read = _metric(f"llm.new_shapes.{cell}")
    assert read(_run({"new_shapes": 126}, {"new_shapes": 126})) == 0.0
    assert read(_run({"new_shapes": 126}, {"new_shapes": 128})) == 2.0


def test_prefix_hit_share_is_hits_over_prompt_tokens():
    read = _metric("kv.prefix_hit_share.poisson")
    run = _run({"radix_hit_tokens": 100, "prompt_tokens": 1000},
               {"radix_hit_tokens": 400, "prompt_tokens": 2000})
    assert read(run) == pytest.approx(30.0)
    idle = _run({"radix_hit_tokens": 5, "prompt_tokens": 10},
                {"radix_hit_tokens": 5, "prompt_tokens": 10})
    assert read(idle) is None


def test_wait_share_counts_ready_time_with_nothing_running():
    read = _metric("sched.wait_share.poisson")
    # a: ready 0, begun 1, done 4; b: ready 4, begun 6, done 8;
    # c: ready 2, begun 3 while a runs (no wait); d: not finished
    ctx = _ctx(0.0, 8.0,
               {"a": (0.0, 4.0), "b": (4.0, 8.0), "c": (2.0, 3.5),
                "d": (5.0, None)},
               {"a": 1.0, "b": 6.0, "c": 3.0})
    assert read(_run(ctxs=[ctx])) == pytest.approx(100.0 * 3.0 / 8.0)
    # a second query with no waiting halves nothing but the sum
    busy = _ctx(10.0, 12.0, {"x": (10.0, 12.0)}, {"x": 10.0})
    assert read(_run(ctxs=[ctx, busy])) == pytest.approx(100.0 * 3.0 / 10.0)
    # failed queries are left out
    bad = _ctx(0.0, 1.0, {"x": (0.0, 1.0)}, {"x": 1.0},
               error=RuntimeError("x"))
    assert read(_run(ctxs=[busy, bad])) == pytest.approx(0.0)


@pytest.mark.parametrize("name", [
    "llm.loop_host_share.saturated", "llm.loop_host_share.poisson",
    "llm.new_shapes.saturated", "llm.new_shapes.poisson",
    "kv.prefix_hit_share.poisson", "sched.wait_share.poisson"])
def test_a_program_without_the_counters_reads_nothing(name):
    """What the parent commit's engine keeps: the counters and stamps
    the readers need are absent, and each reader returns None."""
    old = {"prefill_tokens": 10, "decode_tokens": 20, "calls": 3,
           "decode_iters": 2, "busy_s": 1.0}
    ctx = _ctx(0.0, 2.0, {"a": (0.0, 2.0)})
    assert _metric(name)(_run(old, old, ctxs=[ctx])) is None
