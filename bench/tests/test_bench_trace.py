"""The trace reduction on a small recorded trace (a quarter second of a
``chatglm3-6b.rag_saturated`` window on one TPU v5e, kept in
``bench/tests/trace_slice.json``), and the operation and byte counts
the roofline and mfu metrics divide by, at both configurations' shapes."""
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import device, readers, spec, trace  # noqa: E402

SLICE = BENCH / "tests" / "trace_slice.json"
ARCH = spec.load_module(BENCH / "configs" / "dense_gqa.py", "t_dense_gqa")
CFG = {n: spec.load_json(BENCH / "configs" / f"{n}.json")
       for n in ("chatglm3-6b", "deepseek-67b-6l")}
V5E = device.peaks("TPU v5 lite")


def _slice():
    d = json.loads(SLICE.read_text())
    ops = {int(k): [tuple(e) for e in v] for k, v in d["device_ops"].items()}
    mods = {int(k): [tuple(e) for e in v] for k, v in d["modules"].items()}
    return tuple(d["window"]), ops, mods, [tuple(e) for e in d["host_spans"]]


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    window, ops, mods, spans = _slice()
    s = trace.reduce_events(window, ops, mods, spans, 4096)
    lo, hi = window
    # brute force: mark every microsecond some operation covers
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for a, b, _, _ in ops[min(ops)]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.busy_s / s.window_s == pytest.approx(grid.mean(), abs=0.01)
    assert 0.0 < s.idle_share < 1.0


def test_breakdown_lists_top_ops_and_longest_gaps():
    window, ops, mods, spans = _slice()
    s = trace.reduce_events(window, ops, mods, spans, 4096)
    assert 0 < len(s.device_ops) <= 10 and 0 < len(s.idle_gaps) <= 10
    secs = [t for _, t in s.device_ops]
    assert secs == sorted(secs, reverse=True)
    gaps = [t for _, t in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= s.window_s - s.busy_s + 1e-9
    assert all(isinstance(n, str) and n for n, _ in s.device_ops)


def test_decode_steps_are_found_with_their_batch():
    window, ops, mods, spans = _slice()
    s = trace.reduce_events(window, ops, mods, spans, 4096)
    dec = s.decode_steps()
    assert dec, "the saturated window runs decode steps"
    assert all(b in (1, 2, 4, 8, 16) and t > 0 for b, t in dec)


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7)]
    assert trace.union_length(iv) == 4
    assert trace.gaps(iv, 0, 10) == [(3, 5), (6, 10)]
    assert trace.gaps([], 2, 3) == [(2, 3)]


@pytest.mark.parametrize("name,params,nbytes", [
    ("chatglm3-6b", 6_243_584_000, 12_487_168_000),
    ("deepseek-67b-6l", 5_830_189_056, 11_660_378_112),
])
def test_parameter_counts_match_the_published_shapes(name, params, nbytes):
    p = ARCH.param_count(CFG[name])
    assert p["total"] == params
    assert 2 * p["total"] == nbytes


@pytest.mark.parametrize("name", ["chatglm3-6b", "deepseek-67b-6l"])
def test_step_cost_counts(name):
    D = ARCH.dims(CFG[name])
    p = ARCH.param_count(CFG[name])
    one = ARCH.step_cost(CFG[name], 1, 1, 1)
    att = 4 * D["heads"] * D["head_dim"] * D["layers"]
    assert one["flops"] == 2 * p["matmul"] + att
    c = ARCH.step_cost(CFG[name], 16, 100, 16)
    assert c["flops"] == 16 * 2 * p["matmul"] + 16 * 100 * att
    assert c["kv_bytes_per_token"] == \
        2 * D["layers"] * D["kv_heads"] * D["head_dim"] * 2
    # a decode step at batch 16 is bound by its bytes on a v5e
    nbytes = c["weight_bytes"] + c["kv_bytes_per_token"] * 1600
    assert nbytes / V5E["hbm_bytes_per_s"] > c["flops"] / V5E["bf16_flops"]


def _run_with(name, decode_steps, prefill_steps, dev_steps, window):
    cell = SimpleNamespace(arch=ARCH, config=CFG[name])
    w = SimpleNamespace(t_open=window[0], t_close=window[1])
    rec = SimpleNamespace(decode_steps=decode_steps,
                          prefill_steps=prefill_steps)
    tr = SimpleNamespace(decode_steps=lambda: dev_steps)
    return SimpleNamespace(
        window=w, recorder=rec, trace=tr, trace_wall=window, peaks=V5E,
        chips=1,
        cost=lambda t, c, lg: ARCH.step_cost(CFG[name], t, c, lg))


@pytest.mark.parametrize("name", ["chatglm3-6b", "deepseek-67b-6l"])
def test_roofline_and_mfu_from_the_counts(name):
    # 100 decode steps of 16 sequences with 150 positions each, each
    # taking 40 ms on the device, in a 10 s window
    steps = [(float(i) / 10, float(i) / 10 + 0.04, 16, 16 * 150)
             for i in range(100)]
    run = _run_with(name, steps, [], [(16, 0.04)] * 100, (0.0, 10.0))
    c = ARCH.step_cost(CFG[name], 16, 150, 16)
    least = (c["weight_bytes"] + c["kv_bytes_per_token"] * (16 * 150 + 16)
             ) / V5E["hbm_bytes_per_s"]
    assert readers.step_roofline(run) == pytest.approx(100 * least / 0.04)
    mfu = 100 * 100 * c["flops"] / 10.0 / V5E["bf16_flops"]
    assert readers.mfu(run) == pytest.approx(mfu)
    assert 0 < readers.mfu(run) < readers.step_roofline(run) < 100
