"""Chunked prefill with stall-free mixed prefill/decode iterations:
chunked == monolithic token identity (dense + paged, legacy + continuous
loop), mid-prefill decode admission/eviction interleaving, token-budget
admission (never exceeded), flag-off identity, prefix-fork and
speculative-decode composition, and the post-chunk admission re-check."""
import itertools
import threading
import time

import pytest

import repro.core.passes as passes_mod
import repro.core.pgraph as pgraph_mod
import repro.core.primitives as prims_mod
import repro.core.runtime as runtime_mod
from repro.configs.base import get_config
from repro.engines.decode_loop import ContinuousDecodeLoop, PrefillJob
from repro.engines.llm_engine import LLMEngine
from repro.engines.sim_engines import SimLLMEngine, build_sim_engines

CFG = get_config("tiny-lite-llm")
LONG = " ".join(f"tok{i}" for i in range(90))


def _engine(*, paged=False, chunked=False, **kw):
    kw.setdefault("max_len", 256)
    kw.setdefault("max_batch", 4)
    return LLMEngine("t", CFG, paged=paged, chunked_prefill=chunked,
                     **kw)


def _wait(pred, timeout=30.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.002)
    return False


# ---------------------------------------------------------------------------
# Token identity: chunked == monolithic by construction

@pytest.mark.parametrize("paged", [False, True])
def test_prefill_chunked_matches_monolithic(paged):
    """The resumable-cursor path must land the sequence in exactly the
    monolithic prefill state: same pos, same next-token prediction, and
    an identical greedy continuation."""
    a = _engine(paged=paged)
    sa, toks, _ = a._prepare_prefill_task({"sid": "x", "text": LONG})
    a.prefill_batch([(sa, toks)])

    b = _engine(paged=paged)
    sb, toks_b, _ = b._prepare_prefill_task({"sid": "x", "text": LONG})
    assert toks_b == toks
    b.prefill_chunked([(sb, toks_b)], chunk=32)

    assert (sa.pos, sa.last_token) == (sb.pos, sb.last_token)
    assert a.op_decode([{"sid": "x", "max_new": 8}]) == \
        b.op_decode([{"sid": "x", "max_new": 8}])


@pytest.mark.parametrize("paged", [False, True])
def test_loop_chunked_prefill_token_identity(paged):
    """op_prefill with chunked_prefill on streams the prompt through the
    continuous loop's mixed passes; the decoded continuation must equal
    the flag-off monolithic path token for token."""
    def run(chunked):
        eng = _engine(paged=paged, chunked=chunked, prefill_chunk=32)
        eng.op_prefill([{"sid": "a", "text": LONG}])
        out = eng.op_decode([{"sid": "a", "max_new": 8}])[0]
        eng.stop_decode_loop()
        return out

    assert run(True) == run(False)


# The KV cache holds bfloat16 keys and values, so the served float32
# logits differ from the cache-free reference by up to about 2% of their
# RMS on the tiny model; a near-tie within that may flip.
TIE = 0.05          # of the reference logits' RMS at the position


def test_loop_tokens_are_the_reference_greedy_picks():
    """Tokens the continuous loop serves over the paged pool, with
    chunked prefill and a radix-forked prompt, are the greedy argmax of
    the cache-free float32 reference over the same weights: the step
    programs pick on the device what the reference picks. Only at a
    near-tie (two of these 26 positions) may the served token be
    another, whose reference logit lies less than ``TIE`` below the
    best."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import reference_logits
    eng = LLMEngine("t", get_config("tiny-core-llm"), max_len=256,
                    paged=True, chunked_prefill=True, block_size=8,
                    prefill_chunk=16, prefix_cache="radix")
    texts = {"a": LONG + " first question", "b": LONG + " second one"}
    first = {}                           # the prefill's own pick
    for sid, text in texts.items():        # b forks a's cached blocks
        eng.op_prefill([{"sid": sid, "text": text}])
        first[sid] = eng.states[sid].last_token
    assert eng.counters()["radix_hit_tokens"] >= 64
    seqs = {sid: eng.submit_decode(sid, 12) for sid in texts}
    for seq in seqs.values():
        seq.wait(120)
    eng.stop_decode_loop()
    gaps = []
    for sid, text in texts.items():
        prompt = eng.tok.encode(text)
        served = [first[sid]] + list(seqs[sid].tokens)
        assert len(served) == 13
        ref = reference_logits(eng.cfg, eng.params, jnp.asarray(
            [prompt + served[:-1]], jnp.int32))
        ref = np.asarray(ref[0, len(prompt) - 1:])
        rms = np.sqrt(np.mean(ref ** 2, axis=-1))
        gap = (ref.max(axis=-1) - ref[np.arange(len(served)), served]) / rms
        assert gap.max() <= TIE, (sid, gap.tolist())
        gaps.extend(gap.tolist())
    assert sum(g == 0.0 for g in gaps) >= len(gaps) - 2


@pytest.mark.parametrize("paged", [False, True])
def test_mixed_iterations_token_identity_with_resident_decodes(paged):
    """A long prompt arriving while decodes are resident advances in
    chunks BETWEEN their iterations; every sequence — the co-resident
    decodes and the chunked prompt's own continuation — must match the
    sequential monolithic run exactly."""
    def run(chunked):
        eng = _engine(paged=paged, chunked=chunked, prefill_chunk=16,
                      token_budget=24)
        # warm the hash tokenizer's id->word table up front: decoded
        # TEXT renders an id as a word only once that word has been
        # encoded, and the two runs encode LONG at different times
        # (token ids are what identity is asserted over)
        eng.tok.encode(LONG)
        eng.op_prefill([{"sid": "d1", "text": "short prompt one"},
                        {"sid": "d2", "text": "another short prompt"}])
        # same co-resident decode batch in both runs — only the LONG
        # prompt's prefill mode differs (loop chunks vs one monolithic
        # forward once the decodes are done)
        s1 = eng.submit_decode("d1", 20)
        s2 = eng.submit_decode("d2", 20)
        assert _wait(lambda: s1.steps >= 2)
        if chunked:
            job = eng.submit_prefill({"sid": "long", "text": LONG})
            job.wait(120)
            assert job.chunks > 1        # genuinely chunked
            outs = [s1.wait(120), s2.wait(120)]
        else:
            outs = [s1.wait(120), s2.wait(120)]
            eng.op_prefill([{"sid": "long", "text": LONG}])
        outs.append(eng.op_decode([{"sid": "long", "max_new": 8}])[0])
        eng.stop_decode_loop()
        return outs

    assert run(True) == run(False)


def test_mid_prefill_decode_admission_and_eviction():
    """Decode admissions and evictions must interleave with a long
    prompt's chunks: a decode submitted mid-prefill is admitted before
    the prefill finishes, and a short decode finishes (is evicted) while
    the prompt is still chunking."""
    eng = _engine(paged=True, chunked=True, prefill_chunk=8,
                  token_budget=12, max_len=384)
    eng.op_prefill([{"sid": "d1", "text": "short prompt one"}])
    s1 = eng.submit_decode("d1", 6)          # evicted mid-prefill
    job = eng.submit_prefill({"sid": "long", "text": LONG})
    assert _wait(lambda: job.chunks >= 1)
    eng.op_prefill([{"sid": "d2", "text": "another short prompt"}])
    s2 = eng.submit_decode("d2", 6)          # admitted mid-prefill
    job.wait(120)
    s1.wait(120)
    s2.wait(120)
    loop = eng._decode_loop
    first_chunk = min(i for _, i, _ in loop.prefill_chunks)
    last_chunk = max(i for _, i, _ in loop.prefill_chunks)
    evict_d1 = next(i for sid, i, _ in loop.evictions if sid == "d1")
    admit_d2 = next(i for sid, i in loop.admissions if sid == "d2")
    assert first_chunk < evict_d1 <= last_chunk + 1
    assert first_chunk < admit_d2 <= last_chunk
    eng.stop_decode_loop()


# ---------------------------------------------------------------------------
# Token-budget admission

def _budget_holds(loop: ContinuousDecodeLoop):
    for dcost, planned, landed in loop.mixed_log:
        assert landed <= planned
        assert planned <= max(0, loop.token_budget - dcost), \
            (dcost, planned, loop.token_budget)


def test_token_budget_never_exceeded_sim():
    """Every mixed pass: decode query tokens are packed first and
    prefill chunks only ever take the leftover budget."""
    eng = SimLLMEngine("s", max_batch=4, decode_ms_per_step=2.0,
                       prefill_ms_per_tok=0.05, prefill_setup=1.0,
                       chunked_prefill=True, prefill_chunk=16,
                       token_budget=20)
    seqs = [eng.submit_decode(f"d{i}", 30) for i in range(3)]
    jobs = [eng.submit_prefill({"sid": f"p{i}", "text": _words(64)})
            for i in range(3)]
    for j in jobs:
        j.wait(60)
    for s in seqs:
        s.wait(60)
    loop = eng._decode_loop
    assert loop.mixed_log, "no mixed passes ran"
    _budget_holds(loop)
    eng.stop_decode_loop()


def _words(n):
    return " ".join(f"w{i}" for i in range(n))


def test_token_budget_property():
    """Property sweep over budget/chunk/decode-load combinations: the
    per-pass budget is never exceeded by planned prefill tokens, and
    decodes always advance even when the budget is below the resident
    decode cost (prefill is simply starved, never the decodes)."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    given, settings = hypothesis.given, hypothesis.settings

    @settings(max_examples=10, deadline=None)
    @given(budget=st.integers(1, 40), chunk=st.integers(1, 32),
           ndec=st.integers(0, 4), nprompts=st.integers(1, 3))
    def check(budget, chunk, ndec, nprompts):
        eng = SimLLMEngine("s", max_batch=4, decode_ms_per_step=1.0,
                           prefill_ms_per_tok=0.02, prefill_setup=0.5,
                           chunked_prefill=True, prefill_chunk=chunk,
                           token_budget=budget)
        seqs = [eng.submit_decode(f"d{i}", 8) for i in range(ndec)]
        jobs = [eng.submit_prefill({"sid": f"p{i}", "text": _words(40)})
                for i in range(nprompts)]
        for s in seqs:
            s.wait(60)
        for j in jobs:
            j.wait(60)
        _budget_holds(eng._decode_loop)
        eng.stop_decode_loop()

    check()


# ---------------------------------------------------------------------------
# Flag-off identity

def test_flag_off_monolithic_path_untouched():
    """chunked_prefill=False must keep op_prefill the monolithic
    whole-prompt forward: no decode loop is started, exactly one engine
    call per op_prefill, and the loop built later has no prefill queue
    armed (submit_prefill refuses)."""
    eng = _engine(paged=False, chunked=False)
    eng.op_prefill([{"sid": "a", "text": LONG}])
    assert eng._decode_loop is None          # never touched the loop
    assert eng.stats["calls"] == 1           # one monolithic forward
    with pytest.raises(RuntimeError, match="chunked_prefill is disabled"):
        eng.submit_prefill({"sid": "b", "text": "x"})
    loop = eng.start_decode_loop()
    assert loop.prefill_chunk == 0 and loop.token_budget == 0
    with pytest.raises(RuntimeError, match="chunked prefill disabled"):
        loop.submit_prefill(PrefillJob("b", None, [1]))
    eng.stop_decode_loop()


def test_runtime_flag_off_scheduler_keeps_batch_path():
    """With chunked prefill off, the continuous scheduler must NOT pull
    prefill primitives out of batch formation."""
    engines = build_sim_engines()
    rt = runtime_mod.Runtime(engines, continuous_batching=True)
    try:
        for s in rt.scheds.values():
            assert not s.chunked
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# Composition: COW prefix forks and speculative decode

def test_chunked_prefill_with_prefix_fork_identity():
    """Chunked prefill over a copy-on-write forked instruction prefix
    (paged pool) must match the monolithic cold path token for token —
    only the suffix is chunked, against the shared prefix blocks."""
    instr = "system instruction used for every query"
    suffix = " ".join(f"q{i}" for i in range(70))

    def run(chunked):
        eng = _engine(paged=True, chunked=chunked, prefill_chunk=16)
        eng.get_prefix_state(instr)
        eng.use_prefix_cache = True
        eng.op_prefill([{"sid": "a", "text": f"{instr} {suffix}"}])
        out = eng.op_decode([{"sid": "a", "max_new": 8}])[0]
        eng.stop_decode_loop()
        return out, eng.stats["prefill_tokens"]

    (out_c, ntok_c), (out_m, ntok_m) = run(True), run(False)
    assert out_c == out_m
    assert ntok_c == ntok_m            # both prefilled only the suffix


def test_chunked_prefill_with_speculative_decode():
    """Mixed passes compose with speculative decoding: spec verify
    chunks advance resident decodes while a prompt chunks through, and
    outputs stay token-identical to the plain engine."""
    def run(spec):
        eng = _engine(paged=True, chunked=True, prefill_chunk=16,
                      token_budget=48, max_len=384)
        if spec:
            eng.enable_speculative(draft=None, k=3)
        eng.op_prefill([{"sid": "d", "text": "repeat repeat repeat"}])
        s = eng.submit_decode("d", 24)
        assert _wait(lambda: s.steps >= 1)
        job = eng.submit_prefill({"sid": "long", "text": LONG})
        job.wait(120)
        out = [s.wait(120), eng.op_decode([{"sid": "long",
                                            "max_new": 8}])[0]]
        eng.stop_decode_loop()
        return out

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# Paged backpressure and capacity

def test_submit_prefill_impossible_capacity_fails_loudly():
    eng = LLMEngine("t", CFG, max_len=256, max_batch=2, paged=True,
                    block_size=16, num_blocks=4, chunked_prefill=True,
                    prefill_chunk=16)
    with pytest.raises(ValueError, match="never fit"):
        eng.submit_prefill({"sid": "big", "text": LONG})


def test_chunk_declined_under_reservation_then_retried():
    """A planned chunk that cannot take unreserved free blocks is
    DECLINED (the loop never sleeps on prefill backpressure) and lands
    later once decodes finish and release their reservations."""
    eng = LLMEngine("t", CFG, max_len=256, max_batch=2, paged=True,
                    block_size=16, num_blocks=12, chunked_prefill=True,
                    prefill_chunk=32)
    eng.op_prefill([{"sid": "d", "text": "short prompt"}])
    s = eng.submit_decode("d", 40)           # reserves most of the pool
    assert _wait(lambda: s.steps >= 1)
    job = eng.submit_prefill({"sid": "p", "text": _words(60)})
    job.wait(120)
    s.wait(120)
    assert job.cursor == len(job.tokens)
    eng.stop_decode_loop()


# ---------------------------------------------------------------------------
# Bugfix: admission re-check after a prefill chunk lands

class _RecheckEngine(SimLLMEngine):
    """try_admit defers every decode until the first prefill chunk has
    landed — models a paged pool whose free blocks only materialize
    mid-pass. The loop must re-run try_admit in the SAME pass the chunk
    lands instead of reusing its pre-chunk admission decision."""

    def __init__(self):
        super().__init__("recheck", max_batch=2, decode_ms_per_step=1.0,
                         prefill_ms_per_tok=0.02, prefill_setup=0.5,
                         chunked_prefill=True, prefill_chunk=8,
                         token_budget=16)
        self.chunk_landed = False

    def try_admit(self, seq):
        return self.chunk_landed

    def mixed_iteration(self, seqs, pitems):
        super().mixed_iteration(seqs, pitems)
        if pitems:
            self.chunk_landed = True


def test_admit_rechecked_after_prefill_chunk_lands():
    eng = _RecheckEngine()
    seq = eng.submit_decode("d", 4)
    time.sleep(0.05)
    assert seq.t_admit is None               # deferred: no chunk yet
    job = eng.submit_prefill({"sid": "p", "text": _words(24)})
    job.wait(60)
    seq.wait(60)
    loop = eng._decode_loop
    first_chunk = min(i for _, i, _ in loop.prefill_chunks)
    admit_iter = next(i for sid, i in loop.admissions if sid == "d")
    # admitted by the post-chunk re-check of the SAME pass the chunk
    # landed in (both log the same post-increment iteration number) —
    # without the re-check the admission would land a pass later
    assert admit_iter == first_chunk
    eng.stop_decode_loop()


# ---------------------------------------------------------------------------
# Runtime end-to-end (sim): chunked == monolithic answers

def test_runtime_sim_chunked_identity():
    from repro.core.apps import ALL_APPS
    from repro.core.teola import Teola
    from repro.training.data import doc_corpus

    def run(chunked):
        runtime_mod._qid = itertools.count()
        prims_mod._counter = itertools.count()
        pgraph_mod._sid = itertools.count()
        passes_mod._uid = itertools.count()
        engines = build_sim_engines(chunked_prefill=chunked,
                                    prefill_chunk=32, token_budget=48)
        app = ALL_APPS["advanced_rag"](engines)
        orch = Teola(app, engines, policy="topo",
                     continuous_batching=True)
        docs = doc_corpus(2)
        outs = [orch.query({"question": f"what is fact {i} about optics",
                            "docs": docs}, timeout=300)[0]
                for i in range(2)]
        loop = engines["core_llm"]._decode_loop
        chunks = len(loop.prefill_chunks) if loop else 0
        for name in ("core_llm", "lite_llm"):
            assert orch.runtime.scheds[name].chunked == chunked
        orch.shutdown()
        return outs, chunks

    base, _ = run(False)
    got, nchunks = run(True)
    assert got == base
    assert nchunks > 0                 # prompts really went through the loop


def test_concurrent_submitters_fifo_progress():
    """Several scheduler threads queueing prompts concurrently while
    decodes run: all jobs and decodes complete, budget holds."""
    eng = SimLLMEngine("s", max_batch=4, decode_ms_per_step=1.0,
                       prefill_ms_per_tok=0.02, prefill_setup=0.5,
                       chunked_prefill=True, prefill_chunk=8,
                       token_budget=16)
    seqs = [eng.submit_decode(f"d{i}", 12) for i in range(2)]
    jobs, threads = [], []

    def submit(i):
        jobs.append(eng.submit_prefill({"sid": f"p{i}",
                                        "text": _words(30)}))

    for i in range(4):
        t = threading.Thread(target=submit, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    for j in jobs:
        j.wait(60)
    for s in seqs:
        s.wait(60)
    _budget_holds(eng._decode_loop)
    eng.stop_decode_loop()
