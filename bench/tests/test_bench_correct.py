"""What decides ``correct``, on the CPU at a toy size: the rest of a run
(set-up, the served window, the checks) with the look for a chip
skipped. A sound run is correct; the control (the reference one
precision lower in the system's place) reads past every limit it should;
and each fault a serving cell can have, planted in the timed path, makes
``correct`` come out false."""
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from harness import cellrun, spec  # noqa: E402

SEED = 2**31 + 77
SECONDS = 3.0
INFO = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mix")
    mix = {"app": "advanced_rag", "loop": "open", "rate_qps": 1.0,
           "ramp_s": 2, "tail_s": 30, "docs_per_query": 10,
           "question_words": {"mean": 17.61, "sigma": 0.4},
           "doc_words": {"mean": 46.3, "sigma": 0.5},
           "context_blocks": [4, 16]}
    (tmp / "tiny.json").write_text(json.dumps(mix))
    s = json.loads((ROOT / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "tiny-gqa",
                         "file": "bench/tests/tiny-gqa.json"})
    s["workloads"] = [{"name": "tiny-gqa.tiny", "config": "tiny-gqa",
                       "traffic": "tiny", "chips": 1}]
    for m in s["end_to_end"] + s["per_layer"]:
        m.pop("workloads", None)
    return spec.resolve("tiny-gqa.tiny", s, traffic_dir=tmp)


@pytest.fixture(scope="module")
def stack(cell):
    st = cellrun.setup(cell, SEED, time.time())
    yield st
    st.teardown()


def _run(cell, stack, plant=None, controls=False):
    if stack.orch is None:
        stack.build()
    if plant is not None:
        plant(stack)
    counter = cellrun.CompileCounter()
    t0 = time.time()
    win, rec, mark, tracer = cellrun.serve_window(
        stack, cell, SEED, SECONDS, False, t0, counter)
    return cellrun.finish(stack, cell, SEED, SECONDS, win, rec, mark, tracer,
                          INFO, False, controls=controls)


def test_sound_run_is_correct_and_the_control_is_not(cell, stack):
    out = _run(cell, stack, controls=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 3
    assert list(out)[-1] == "checks"
    limits = cell.config["limits"]
    ctl = out["controls"]
    assert ctl["llm_gap.0"] > limits["llm_gap"]
    assert ctl["retrieval_err.0"] > limits["retrieval_err"]
    assert set(out["metrics"]) == {"query_p50_s", "gen_tokens_per_s",
                                   "setup_s"}


def _token_altered(stack):
    """The decode step's chosen token is replaced where it is produced."""
    eng = stack.llm
    real = eng._decode_iteration_base
    V = eng.cfg.vocab_size

    def altered(seqs):
        out = real(seqs)
        r = seqs[0]
        tok = (r.state.last_token + 1) % V
        r.state.last_token = tok
        r.tokens[-1] = tok
        return out
    eng._decode_iteration_base = altered


def _state_unchanged(stack):
    """The decode step returns the KV pool it was given, unwritten."""
    eng = stack.llm
    real = eng._paged_step

    def stale(params, tokens, pool, tables, pos):
        logits, _ = real(params, tokens, jax.tree.map(jnp.copy, pool),
                         tables, pos)
        return logits, pool
    eng._paged_step = stale


def _answer_altered(stack):
    """The reranker's top answer is replaced by a candidate it did not
    pick."""
    eng = stack.engines["rerank"]
    real = eng.op_rerank

    def altered(tasks):
        out = real(tasks)
        for t, res in zip(tasks, out):
            picked = {c["text"] for c in res}
            other = [c for c in t["candidates"] if c["text"] not in picked]
            if res and other:
                res[0] = {**other[-1], "rerank_score":
                          res[0]["rerank_score"]}
        return out
    eng.op_rerank = altered


@pytest.mark.parametrize("plant,number", [
    (_token_altered, "llm_gap"),
    (_state_unchanged, "llm_gap"),
    (_answer_altered, "retrieval_err"),
])
def test_a_planted_fault_makes_the_run_incorrect(cell, stack, plant, number):
    out = _run(cell, stack, plant)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]
