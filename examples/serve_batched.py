"""End-to-end serving driver: serve the advanced-RAG app on REAL JAX
engines with a stream of batched concurrent requests (the paper-kind e2e
deliverable — serving a small model with batched requests).

  PYTHONPATH=src python examples/serve_batched.py [n_queries]
"""
import sys
import time

import numpy as np

from repro.core.apps import build_engines, advanced_rag
from repro.core.teola import Teola
from repro.training.data import doc_corpus

QUESTIONS = [
    "what is fact 3 about optics",
    "tell me fact 7 about finance",
    "what is fact 5 about biology",
    "explain fact 9 about chess",
]


def main(n=6):
    engines = build_engines()
    app = advanced_rag(engines)
    # decodes run in the core LLM's continuous loop, whose phases are
    # printed at the end
    teola = Teola(app, engines, continuous_batching=True)
    docs = doc_corpus(2)

    print("warmup...")
    teola.query({"question": QUESTIONS[0], "docs": docs}, timeout=300)

    print(f"submitting {n} concurrent queries (Poisson arrivals)...")
    rng = np.random.default_rng(0)
    ctxs = []
    t0 = time.time()
    for i in range(n):
        q = {"question": QUESTIONS[i % len(QUESTIONS)], "docs": docs}
        ctxs.append(teola.submit(q))
        time.sleep(float(rng.exponential(0.3)))
    for c in ctxs:
        c.done.wait(600)
    wall = time.time() - t0

    lats = [c.latency for c in ctxs]
    print(f"\nserved {n} queries in {wall:.1f}s "
          f"(throughput {n / wall:.2f} q/s)")
    print(f"latency avg={np.mean(lats) * 1000:.0f}ms "
          f"p50={np.percentile(lats, 50) * 1000:.0f}ms "
          f"max={np.max(lats) * 1000:.0f}ms")
    llm = engines["core_llm"]
    c = llm.counters()
    print(f"core LLM engine: {c['calls']} batched calls, "
          f"{c['prefill_tokens']} prefill tokens, "
          f"{c['decode_tokens']} decoded tokens")
    print("continuous-loop phases (s): " + ", ".join(
        f"{k[:-2]} {c[k]:.2f}" for k in ("plan_s", "prepare_s", "launch_s",
                                        "wait_s", "readback_s", "emit_s")))
    sched = teola.runtime.scheds["core_llm"]
    sizes = [s for s, _ in sched.batches]
    print(f"LLM batch sizes formed by topology-aware batching: "
          f"avg={np.mean(sizes):.2f} max={max(sizes)}")
    teola.shutdown()


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 6)
