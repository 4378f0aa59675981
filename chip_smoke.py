#!/usr/bin/env python3
"""Smoke check of the served Teola RAG path on a TPU.

    python3 chip_smoke.py             # one chip: phases 1-6 below
    python3 chip_smoke.py --chips 4   # replicas on four chips only

One chip, one process, in order:
  1. device: name it; stop unless JAX's platform is a TPU;
  2. engines: ``build_engines`` with ChatGLM3-6B (published widths, all
     28 layers, random weights from a seed) as ``core_llm`` in bfloat16;
  3. serve, default path: ``advanced_rag`` queries through ``Teola``
     with the launcher's own set-up and query code (``launch/serve.py``);
  4. serve, paged path: the same queries with ``--paged-kv
     --continuous-batching --chunked-prefill --prefix-cache radix``, each
     answer compared with phase 3's;
  5. logits: the paged engine's prefill and 4 decode steps against a
     cache-free float32 forward at the highest matmul precision;
  6. kernels: the main-path Pallas kernels compiled (``interpret=False``)
     at ChatGLM3 shapes against their ``kernels/ref.py`` oracles.

``--chips 4`` serves the same ``advanced_rag`` queries on a ``core_llm``
pool of four replicas, one per chip, and on one replica, then moves one
sequence's paged KV between two chips and decodes it there.

Findings go to earlier lines; the last line of standard output is one
JSON object naming the device, printed only when every phase passed.
Times printed here are smoke timings of one cold run (compilation
included), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

MODEL = "chatglm3-6b"
DTYPE = "bfloat16"
QUERIES = 3
PAGED_FLAGS = ["--paged-kv", "--continuous-batching", "--chunked-prefill",
               "--prefix-cache", "radix"]
LOGITS_PROMPT = ("answer the question using the retrieved context : the "
                 "lens focuses light onto the sensor and the mirror folds "
                 "the optical path so that the camera stays short")
DECODE_STEPS = 4
# Logits tolerances, relative to the reference logits' RMS at each
# position. Serving runs in bfloat16 (8-bit mantissa, f32 accumulation):
# every layer rounds its activations to bf16 (relative error 2^-9 each),
# which over 28 layers leaves an RMS logit error near 2% of the logits'
# RMS (about 1.6% measured at reduced width on the CPU) and a largest
# single-logit error about four times that. The bounds keep 2-3x headroom
# for the wider model and vocabulary; an 8-bit float (3-bit mantissa)
# would be 16x worse and fail both.
REL_RMS_TOL = 0.05
MAX_REL_TOL = 0.25
# Kernel tolerances: kernel and oracle both accumulate in float32 from
# the same bf16 inputs and round the output to bf16 (half an ulp, 2^-9
# relative, each side) in a different summation order.
KERNEL_TOL = dict(atol=3e-2, rtol=2e-2)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Counts XLA backend compilations (cache hits do not compile)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.cache_hits


def bytes_in_use(device=None) -> int:
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))


def serve_args(model, dtype, extra, queries=QUERIES):
    from repro.launch.serve import build_parser, validate_args
    ap = build_parser()
    args = ap.parse_args(["--app", "advanced_rag", "--scheme", "Teola",
                          "--core-llm", model, "--dtype", dtype,
                          "--queries", str(queries), "--rate", "4"] + extra)
    validate_args(ap, args)
    return args


def build_phase(label, args):
    """Stand up the launcher's serving stack for ``args``."""
    from repro.launch import serve
    t0 = time.time()
    served = serve.build_serving(args)
    log(f"{label}: engines built in {time.time() - t0:.1f}s; "
        f"bytes_in_use={bytes_in_use()}")
    return served


def serve_phase(label, served, args, counter):
    """Run the launcher's warm-up and queries on ``served``; returns
    (answers, failures)."""
    from repro.launch import serve
    from repro.training.data import doc_corpus
    c0 = counter.snapshot()
    docs = doc_corpus(2)
    t1 = time.time()
    warm = serve.warmup(served["orch"], docs)
    c1 = counter.snapshot()
    log(f"{label}: warm-up query {time.time() - t1:.1f}s, "
        f"{c1[0] - c0[0]} compiles, {c1[1] - c0[1]} compile-cache hits "
        f"(smoke timing)")
    failures = [("warmup", r) for _, r in serve.query_failures([warm])]
    t2 = time.time()
    ctxs = serve.run_queries(served["orch"], args, docs)
    c2 = counter.snapshot()
    failures += serve.query_failures(ctxs)
    answers = [c.store.get(c.output_key) for c in ctxs]
    for i, c in enumerate(ctxs):
        log(f"{label}: query {i} done={c.done.is_set()} "
            f"error={None if c.error is None else type(c.error).__name__} "
            f"latency={c.latency:.2f}s words={len(str(answers[i]).split())}")
    log(f"{label}: {len(ctxs) - len(failures)}/{len(ctxs)} queries ok in "
        f"{time.time() - t2:.1f}s, {c2[0] - c1[0]} compiles after warm-up "
        f"(smoke timing)")
    for i, reason in failures:
        log(f"{label}: query {i} FAILED: {reason}")
    return answers, failures


def teardown(served):
    """Stop the orchestrator and drop every engine, so that the next
    phase's weights fit on the chip."""
    served["orch"].shutdown()
    served.clear()
    gc.collect()


def compare_answers(label, ref, got):
    """Print, per query, whether ``got`` matches ``ref`` word for word
    (the tokenizer is word-level) and the first differing word."""
    same = 0
    for i, (a, b) in enumerate(zip(ref, got)):
        wa, wb = str(a).split(), str(b).split()
        if wa == wb:
            same += 1
            log(f"{label}: query {i} text matches")
            continue
        at = next((j for j, (x, y) in enumerate(zip(wa, wb)) if x != y),
                  min(len(wa), len(wb)))
        log(f"{label}: query {i} text differs at word {at} "
            f"(lengths {len(wa)} vs {len(wb)})")
    log(f"{label}: {same}/{len(ref)} answers identical")


def engine_summary(eng):
    import jax
    leaves = jax.tree.leaves(eng.params)
    nbytes = sum(x.nbytes for x in leaves)
    layers = sum(elems[0]["ln1"].shape[0] for elems in
                 eng.params["stages"])
    cfg = eng.cfg
    log(f"core_llm {cfg.name}: d_model={cfg.d_model} heads={cfg.num_heads} "
        f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} qkv_bias={cfg.qkv_bias} "
        f"rope={cfg.rope_kind} dtype={leaves[0].dtype}")
    log(f"core_llm params: {sum(x.size for x in leaves)} parameters, "
        f"{nbytes} bytes; layers held {layers} of {cfg.num_layers}; "
        f"device {eng.device}")


def logits_check(eng, prompt=LOGITS_PROMPT, steps=DECODE_STEPS):
    """Prefill ``prompt`` and take ``steps`` decode steps through the
    engine's own paths, then compare every step's logits with
    ``reference_logits`` over the same tokens. Returns
    (ok, worst_rel_rms, worst_max_rel)."""
    import numpy as np
    from repro.engines.decode_loop import DecodeSeq
    from repro.models.transformer import reference_logits
    toks = eng.tok.encode(prompt)
    sid = "smoke-logits"
    st = eng.new_state()
    eng.states[sid] = st
    try:
        got = [np.asarray(eng.prefill_batch([(st, toks)])[0], np.float32)]
        fed = [st.last_token]
        seq = DecodeSeq(sid, st, steps, text_fn=eng.tok.decode)
        for _ in range(steps):
            got.append(np.asarray(eng.decode_iteration([seq])[0],
                                  np.float32))
            fed.append(st.last_token)
        tokens = toks + fed[:steps]
        ref = reference_logits(eng.cfg, eng.params,
                               eng._put(np.asarray([tokens], np.int32)))
        ref = np.asarray(ref[0, len(toks) - 1:], np.float32)
    finally:
        eng.release(sid)
    worst_rms = worst_max = 0.0
    for i, (g, r) in enumerate(zip(got, ref)):
        scale = float(np.sqrt(np.mean(r * r)))
        rel_rms = float(np.sqrt(np.mean((g - r) ** 2))) / scale
        max_rel = float(np.max(np.abs(g - r))) / scale
        worst_rms, worst_max = max(worst_rms, rel_rms), max(worst_max,
                                                            max_rel)
        log(f"logits {'prefill' if i == 0 else f'decode {i}'} "
            f"(position {len(toks) - 1 + i}): rel_rms_err={rel_rms:.5f} "
            f"max_rel_err={max_rel:.5f} ref_rms={scale:.4f} "
            f"argmax engine/ref {int(g.argmax())}/{int(r.argmax())}")
    ok = worst_rms <= REL_RMS_TOL and worst_max <= MAX_REL_TOL
    log(f"logits check {'PASS' if ok else 'FAIL'}: {len(got)} positions, "
        f"worst rel_rms_err {worst_rms:.5f} (tol {REL_RMS_TOL}), worst "
        f"max_rel_err {worst_max:.5f} (tol {MAX_REL_TOL}); prompt "
        f"{len(toks)} tokens, engine dtype {eng.params['embed'].dtype}")
    return ok, worst_rms, worst_max


def kernel_checks() -> bool:
    """Main-path Pallas kernels compiled at ChatGLM3 shapes (H=32, K=2,
    hd=128, block 16) against their oracles, on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    H, K, hd, bs, B, maxblk = 32, 2, 128, 16, 4, 32
    nb = 1 + B * maxblk
    ks = jax.random.split(jax.random.key(7), 8)
    bf = jnp.bfloat16
    kp = jax.random.normal(ks[0], (nb, bs, K, hd), bf)
    vp = jax.random.normal(ks[1], (nb, bs, K, hd), bf)
    perm = np.random.default_rng(0).permutation(np.arange(1, nb))
    tables = jnp.asarray(perm.reshape(B, maxblk), jnp.int32)
    cases = {
        "verify_attention S=1": (
            ops.verify_attention, ref.verify_attention_ref,
            (jax.random.normal(ks[2], (B, 1, H, hd), bf), kp, vp, tables,
             jnp.asarray([1, 37, 300, 512], jnp.int32)), {}),
        "verify_attention S=5": (
            ops.verify_attention, ref.verify_attention_ref,
            (jax.random.normal(ks[3], (B, 5, H, hd), bf), kp, vp, tables,
             jnp.asarray([5, 37, 300, 512], jnp.int32)), {}),
        "chunk_prefill_attention": (
            ops.chunk_prefill_attention, ref.chunk_prefill_attention_ref,
            (jax.random.normal(ks[4], (B, 128, H, hd), bf), kp, vp, tables,
             jnp.asarray([0, 16, 200, 384], jnp.int32)), {}),
        "flash_prefill": (
            ops.flash_prefill, ref.flash_prefill_ref,
            (jax.random.normal(ks[5], (1, 128, H, hd), bf),
             jax.random.normal(ks[6], (1, 512, K, hd), bf),
             jax.random.normal(ks[7], (1, 512, K, hd), bf)),
            {"prefix_len": 128}),
    }
    ok = True
    for name, (kern, oracle, args, kw) in cases.items():
        text = kern.lower(*args, interpret=False, **kw).as_text()
        compiled = "tpu_custom_call" in text
        out = np.asarray(kern(*args, interpret=False, **kw), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(*args, **kw), np.float32)
        err = float(np.max(np.abs(out - want)))
        close = bool(np.allclose(out, want, **KERNEL_TOL))
        ok &= compiled and close and bool(np.isfinite(out).all())
        log(f"kernel {name}: tpu_custom_call={compiled} shape="
            f"{tuple(out.shape)} max_abs_err={err:.5f} "
            f"({'PASS' if compiled and close else 'FAIL'}, atol "
            f"{KERNEL_TOL['atol']} rtol {KERNEL_TOL['rtol']})")
    return ok


def migration_check(src, dst) -> bool:
    """Prefill on ``src``, move the sequence's paged KV to ``dst``
    (another device) and decode there; the tokens must equal decoding
    the same prompt on ``src`` alone."""
    import jax
    from repro.engines.decode_loop import DecodeSeq
    toks = src.tok.encode(LOGITS_PROMPT)

    def decode(eng, sid, st):
        seq = DecodeSeq(sid, st, DECODE_STEPS, text_fn=eng.tok.decode)
        for _ in range(DECODE_STEPS):
            eng.decode_iteration([seq])
        return list(seq.tokens)

    for sid in ("smoke-stay", "smoke-move"):
        st = src.new_state()
        src.states[sid] = st
        src.prefill_batch([(st, toks)])
    stay = decode(src, "smoke-stay", src.states["smoke-stay"])
    src.release("smoke-stay")
    dst.import_seq(src.export_seq("smoke-move"))
    st = dst.states["smoke-move"]
    moved_to = {a.device for a in jax.tree.leaves(dst.pool)}
    moved = decode(dst, "smoke-move", st)
    dst.release("smoke-move")
    ok = moved == stay and moved_to == {dst.device}
    log(f"migration {src.name} ({src.device}) -> {dst.name} "
        f"({dst.device}): {len(st.table)} blocks, destination pool on "
        f"{sorted(str(d) for d in moved_to)}, decoded tokens "
        f"{'identical' if moved == stay else 'DIFFER'} "
        f"({'PASS' if ok else 'FAIL'})")
    return ok


def run_one_chip(counter, model=MODEL, dtype=DTYPE) -> bool:
    from repro.core.engine_pool import primary_of
    ok = True
    # phases 2-3: engines and the default served path
    args = serve_args(model, dtype, [])
    served = build_phase("default", args)
    engine_summary(primary_of(served["engines"]["core_llm"]))
    base, failures = serve_phase("default", served, args, counter)
    ok &= not failures
    teardown(served)
    log(f"after default-path teardown: bytes_in_use={bytes_in_use()}")
    # phase 4: paged path
    args = serve_args(model, dtype, PAGED_FLAGS)
    served = build_phase("paged", args)
    paged, failures = serve_phase("paged", served, args, counter)
    ok &= not failures
    compare_answers("paged vs default", base, paged)
    engines = served["engines"]
    served["orch"].shutdown()
    # phase 5: logits against the float32 reference
    eng = primary_of(engines["core_llm"])
    logits_ok, _, _ = logits_check(eng)
    ok &= logits_ok
    log(f"after logits check: bytes_in_use={bytes_in_use()}")
    del engines, eng, served
    gc.collect()
    # phase 6: kernels
    ok &= kernel_checks()
    return ok


def run_four_chips(counter, model=MODEL, dtype=DTYPE, replicas=4) -> bool:
    """The same queries on one replica and on a pool of ``replicas``, one
    per device (the launcher's default path), then one paged sequence
    moved between the first two devices."""
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.core.engine_pool import replicas_of
    from repro.engines.llm_engine import LLMEngine
    ok = True
    queries = 2 * replicas
    args = serve_args(model, dtype, [], queries=queries)
    served = build_phase("1 replica", args)
    one, failures = serve_phase("1 replica", served, args, counter)
    ok &= not failures
    teardown(served)
    args = serve_args(model, dtype, ["--llm-instances", str(replicas)],
                      queries=queries)
    served = build_phase(f"{replicas} replicas", args)
    many, failures = serve_phase(f"{replicas} replicas", served, args,
                                 counter)
    ok &= not failures
    reps = replicas_of(served["engines"]["core_llm"])
    for r in reps:
        log(f"replica {r.name}: device {r.device} (params on "
            f"{sorted(str(d) for d in r.params['embed'].devices())}) "
            f"prefill_tokens={r.stats['prefill_tokens']} "
            f"decode_tokens={r.stats['decode_tokens']} "
            f"bytes_in_use={bytes_in_use(r.device)}")
    devices = {r.device for r in reps}
    served_all = all(r.stats["prefill_tokens"] + r.stats["decode_tokens"]
                     for r in reps)
    log(f"{len(reps)} replicas on {len(devices)} distinct devices; every "
        f"replica served: {served_all}")
    ok &= len(devices) == len(reps) == replicas and served_all
    compare_answers(f"{replicas} replicas vs 1", one, many)
    del reps
    teardown(served)
    # migration: a paged engine on device 0 and its clone on device 1
    src = LLMEngine("core_llm", get_config(model),
                    dtype=getattr(jnp, dtype), paged=True)
    ok &= migration_check(src, src.clone(1))
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                         "replicas-behind-the-router phase on four")
    args = ap.parse_args(argv)
    try:
        from repro.launch.device import startup
    except ImportError as e:
        print(f"[smoke] FAIL: the repro package is not importable from "
              f"{SRC} ({e})", file=sys.stderr)
        return 2
    info = startup("smoke")
    if info["platform"] != "tpu":
        print(f"[smoke] FAIL: no TPU: JAX reports platform="
              f"{info['platform']} kind={info['kind']}", file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"[smoke] FAIL: --chips {args.chips} but JAX sees "
              f"{info['count']} device(s)", file=sys.stderr)
        return 2
    counter = CompileCounter()
    t0 = time.time()
    run = run_one_chip if args.chips == 1 else run_four_chips
    ok = run(counter)
    log(f"{'all phases passed' if ok else 'FAILED'} in "
        f"{time.time() - t0:.1f}s (smoke timing)")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["kind"],
        "count": info["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
