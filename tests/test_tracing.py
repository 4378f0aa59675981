"""Spans and counters inside the program: the continuous loop's phase
counters, the prompt-token counters of the radix cache, the new-shape
counter, the graph scheduler's ``node_start`` stamps, and the host spans
on the profiler's clock."""
import glob
import time

import pytest

from repro.configs.base import get_config
from repro.core.apps import advanced_rag, build_engines
from repro.core.teola import Teola
from repro.engines.decode_loop import DecodeSeq, PrefillJob, phase
from repro.engines.llm_engine import LLMEngine
from repro.engines.sim_engines import build_sim_engines
from repro.training.data import doc_corpus

CFG = get_config("tiny-lite-llm")
PHASES = ("plan_s", "prepare_s", "launch_s", "wait_s", "readback_s",
          "emit_s")
SPANS = ("llm.loop.plan", "llm.loop.emit", "llm.step.prepare",
         "llm.step.launch", "llm.step.wait", "llm.step.readback")
SHARED = " ".join(f"instr{i}" for i in range(40))


def _engine(**kw):
    kw.setdefault("max_len", 256)
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 32)
    return LLMEngine("t", CFG, **kw)


def _serve(eng, texts, max_new=6):
    """Prefill ``texts`` through the loop, then decode each; returns the
    loop's wall seconds."""
    t0 = time.perf_counter()
    eng.op_prefill([{"sid": f"s{i}", "text": t}
                    for i, t in enumerate(texts)])
    seqs = [eng.submit_decode(f"s{i}", max_new) for i in range(len(texts))]
    for s in seqs:
        s.wait(60)
    eng.stop_decode_loop()
    return time.perf_counter() - t0


@pytest.mark.parametrize("paged", [False, True])
def test_loop_phase_counters_fit_inside_the_loop_wall_time(paged):
    eng = _engine(paged=paged, chunked_prefill=True)
    wall = _serve(eng, [SHARED + " alpha", "beta gamma delta"])
    c = eng.counters()
    assert all(c[k] >= 0.0 for k in PHASES)
    assert all(c[k] > 0.0 for k in ("plan_s", "prepare_s", "launch_s",
                                    "wait_s", "emit_s"))
    assert sum(c[k] for k in PHASES) <= wall


def test_counters_is_a_copy_taken_under_the_lock():
    eng = _engine()
    c = eng.counters()
    c["decode_tokens"] = 123
    assert eng.counters()["decode_tokens"] == 0
    assert set(PHASES) <= set(c)


@pytest.mark.parametrize("chunked", [False, True])
def test_prompt_tokens_are_radix_hits_plus_prefill_plus_clamp(chunked):
    eng = _engine(paged=True, block_size=8, chunked_prefill=chunked,
                  prefix_cache="radix", max_len=128)
    long_text = " ".join(f"w{i}" for i in range(150))   # past max_len
    eng.op_prefill([{"sid": "a", "text": SHARED + " first"}])
    eng.op_prefill([{"sid": "b", "text": SHARED + " second question"}])
    eng.op_prefill([{"sid": "c", "text": long_text}])
    eng.stop_decode_loop()
    c = eng.counters()
    clamped = len(eng.tok.encode(long_text)) - (eng.max_len - 8)
    assert clamped > 0 and c["radix_hit_tokens"] >= 40
    assert c["prompt_tokens"] == \
        c["radix_hit_tokens"] + c["prefill_tokens"] + clamped


@pytest.mark.parametrize("paged", [False, True])
def test_new_shapes_counts_a_signature_once(paged):
    eng = _engine(paged=paged)
    eng.op_prefill([{"sid": "a", "text": "one two three"},
                    {"sid": "b", "text": "four five six"}])

    def step(sids):
        before = eng.counters()["new_shapes"]
        eng.decode_iteration([DecodeSeq(s, eng.states[s], 1,
                                        text_fn=lambda q: "")
                              for s in sids])
        return eng.counters()["new_shapes"] - before

    assert step(["a"]) == 1              # a new batch bucket
    assert step(["a"]) == 0              # the same signature again
    assert step(["b"]) == 0
    assert step(["a", "b"]) == 1         # batch bucket 2
    assert step(["b", "a"]) == 0


@pytest.mark.parametrize("paged", [False, True])
def test_token_reads_count_step_calls_not_sequences(paged):
    """Each step brings its sampled ids to the host in one read: loop
    passes of ten resident decodes plus a prefill chunk, and a
    run-to-completion batch, count one ``token_reads`` per step call."""
    eng = _engine(paged=paged, max_batch=16)
    n = 10
    sids = [f"s{i}" for i in range(n)]
    eng.op_prefill([{"sid": s, "text": f"question {s} about optics"}
                    for s in sids])
    calls = []                           # (step name, batch rows)
    for k in ("_step", "_pstep", "_paged_step", "_paged_pstep"):
        real = getattr(eng, k, None)
        if real is None:
            continue

        def counted(*args, real=real):
            calls.append((real.__name__, args[1].shape[0]))
            return real(*args)
        counted.__name__ = real.__name__
        setattr(eng, k, counted)
    seqs = [DecodeSeq(s, eng.states[s], 3, text_fn=lambda q: "")
            for s in sids]
    eng.states["p"] = eng.new_state()
    job = PrefillJob("p", eng.states["p"], eng.tok.encode(SHARED))
    before = eng.counters()
    for _ in range(3):
        eng.mixed_iteration(seqs, [(job, 8)])
    mid = eng.counters()
    assert mid["token_reads"] - before["token_reads"] == len(calls) == 6
    assert mid["decode_tokens"] - before["decode_tokens"] == 3 * n
    assert sorted(set(b for _, b in calls)) == [1, 16]
    assert job.cursor == 24 and all(len(r.tokens) == 3 for r in seqs)
    eng.op_decode([{"sid": s, "max_new": 4} for s in sids])
    after = eng.counters()
    assert after["token_reads"] - mid["token_reads"] == len(calls) - 6 == 4
    assert after["decode_tokens"] - mid["decode_tokens"] == 4 * n


def _query(teola):
    ctx = teola.submit({"question": "what is fact 3 about optics",
                        "docs": doc_corpus(2)})
    assert ctx.done.wait(300) and ctx.error is None
    return ctx


@pytest.mark.parametrize("mode", ["loop", "batched", "sim_pool"])
def test_node_start_lies_inside_each_primitives_span(mode):
    if mode == "sim_pool":
        engines = build_sim_engines(llm_instances=2, paged_kv=True,
                                    chunked_prefill=True)
    else:
        loop = mode == "loop"
        engines = build_engines(paged_kv=loop, chunked_prefill=loop,
                                prefix_cache="radix" if loop else "none")
    teola = Teola(advanced_rag(engines), engines,
                  continuous_batching=mode != "batched")
    try:
        ctx = _query(teola)
    finally:
        teola.shutdown()
    assert set(ctx.node_start) == set(ctx.graph.nodes)
    for pid, (ready, done) in ctx.node_spans.items():
        assert ready <= ctx.node_start[pid] <= done, pid


def test_spans_reach_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    eng = _engine(paged=True, chunked_prefill=True)
    _serve(eng, ["warm the programs up"], max_new=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, ["tracing one", "tracing two"], max_new=3)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names, args = set(), {}
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("llm."):
                    names.add(ev.name)
                    args.setdefault(ev.name, {k: v for k, v in ev.stats})
    assert set(SPANS) <= names
    assert args["llm.step.launch"]["kind"] in ("decode", "prefill")
    assert args["llm.step.launch"]["batch"] >= 1


def test_spans_cost_microseconds_without_a_profiler_session():
    acc = {}
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with phase(acc, "llm.step.prepare", kind="decode", batch=16):
            pass
    per = (time.perf_counter() - t0) / n
    assert 0.0 < acc["prepare_s"] <= time.perf_counter() - t0
    assert per < 50e-6
