"""What decides ``correct``: the served path's own outputs, compared with
the plain references once the window has closed.

``Recorder`` sits on the core LLM engine during the run. For every
sequence it keeps the tokens written into the KV cache, by position,
and the tokens the engine chose (greedy) at each position: the tokens
fed through prefill chunks and decode steps, a forked prefix's tokens,
and the token each decode step returned. Nothing in the served path
changes; the recorder only reads arguments and results.

After the window:

- ``llm_gap``: a sample of finished sequences, drawn from the seed and
  holding the longest, runs once through the float32 reference over its
  prompt and served tokens. At each served position the gap is how far
  the served token's reference logit lies below the reference's best,
  over the RMS of the reference logits there. The number compared is
  the widest gap.
- ``retrieval_gap``: for a sample of queries, the chunker's output is
  compared exactly (``chunks_differ``), each expanded query's search
  hits and the reranker's picks against the reference encoders: the
  widest shortfall of a picked item's reference score below the
  reference's k-th best, over the spread (standard deviation) of the
  reference scores it was picked from.

The control (``control=True``) reads the same numbers for the
reference computed one precision lower, fp8 operands in every
projection (the LLM is served in bfloat16; the encoders' float32
matmuls run on bfloat16 operands at the TPU's default precision): the
token or item the lower precision would have picked, judged by the
float32 reference, and the scores it would have reported.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from harness.traffic import rng_for

LLM_SAMPLE = 12          # sequences per run, the longest among them
QUERY_SAMPLE = 4         # queries per run for the retrieval chain
ROW_PAD = 64             # reference rows are padded to a multiple of this
POS_PAD = 128            # and the positions compared, to one of this


@dataclass
class SeqLog:
    fed: Dict[int, int] = field(default_factory=dict)
    served: Dict[int, int] = field(default_factory=dict)


class Recorder:
    """Wraps one engine's decode step and prefill chunk step (instance
    attributes, so the loop calls the wrappers) and logs tokens, step
    times and batch sizes. ``annotate`` (set while tracing) puts each
    call in a host span of the profiler's trace."""

    def __init__(self, eng):
        self.eng = eng
        self.seqs: Dict[str, SeqLog] = {}
        self.decode_steps: List[tuple] = []   # (t0, t1, batch, context)
        # (t0, t1, chunks, tokens, context): context sums, over the
        # tokens, the positions each attends to (its own included)
        self.prefill_steps: List[tuple] = []
        self.annotate = False
        self._lock = threading.Lock()
        self._decode = eng._decode_iteration_base
        self._prefill = eng._prefill_chunk_step
        eng._decode_iteration_base = self._on_decode
        eng._prefill_chunk_step = self._on_prefill

    def detach(self):
        """Give the engine back its own methods and drop every reference
        to it, so that it (and its KV pool) can be freed."""
        for name in ("_decode_iteration_base", "_prefill_chunk_step"):
            self.eng.__dict__.pop(name, None)
        self.eng = self._decode = self._prefill = None

    def _log(self, sid) -> SeqLog:
        with self._lock:
            return self.seqs.setdefault(sid, SeqLog())

    def _span(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _on_decode(self, seqs):
        before = [(r.sid, r.state.pos, r.state.last_token) for r in seqs]
        t0 = time.time()
        with self._span("llm.decode_step"):
            out = self._decode(seqs)
        t1 = time.time()
        for (sid, pos, fed), r in zip(before, seqs):
            log = self._log(sid)
            log.fed[pos] = int(fed)
            log.served.setdefault(pos - 1, int(fed))
            log.served[pos] = int(r.state.last_token)
        self.decode_steps.append((t0, t1, len(seqs),
                                  sum(p + 1 for _, p, _ in before)))
        return out

    def _on_prefill(self, pitems):
        before = [(job, job.cursor, job.state.pos) for job, _ in pitems]
        t0 = time.time()
        with self._span("llm.prefill_chunk"):
            out = self._prefill(pitems)
        t1 = time.time()
        chunks = tokens = context = 0
        for job, c0, p0 in before:
            got = job.tokens[c0:job.cursor]
            if not got:
                continue
            chunks += 1
            tokens += len(got)
            context += len(got) * p0 + len(got) * (len(got) + 1) // 2
            log = self._log(job.sid)
            if c0 == 0 and job.ptoks and p0 == len(job.ptoks):
                for i, t in enumerate(job.ptoks):
                    log.fed.setdefault(i, int(t))
            for i, t in enumerate(got):
                log.fed[p0 + i] = int(t)
        self.prefill_steps.append((t0, t1, chunks, tokens, context))
        return out


def sequence_rows(logs: Dict[str, SeqLog]):
    """``{sid: (tokens, served)}`` for every sequence whose context is
    contiguous from position 0 and which served at least one token;
    ``served`` maps position to the token chosen there."""
    out = {}
    for sid, log in logs.items():
        if not log.fed or not log.served:
            continue
        n = max(log.fed) + 1
        if any(p not in log.fed for p in range(n)):
            continue
        served = {p: t for p, t in log.served.items() if 0 <= p < n}
        if served:
            out[sid] = ([log.fed[p] for p in range(n)], served)
    return out


def sample_sequences(rows: dict, seed: int, k: int = LLM_SAMPLE) -> list:
    """``k`` sequence ids drawn from ``seed``, the one with the most
    served tokens (then the longest context) always among them."""
    if not rows:
        return []
    sids = sorted(rows)
    longest = max(sids, key=lambda s: (len(rows[s][1]), len(rows[s][0]), s))
    rest = [s for s in sids if s != longest]
    pick = rng_for(seed, 3).permutation(len(rest))[:k - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def llm_gaps(ref, params, rows: dict, sids: list, controls=()) -> dict:
    """Widest normalised gap of the served tokens (``llm_gap``) and, for
    each control reference, of the tokens it would pick
    (``llm_gap.<i>``), over the sampled sequences. Also the number of
    positions compared."""
    if not sids:
        return {"llm_gap": float("inf"), "positions": 0}
    S = max(len(rows[s][0]) for s in sids)
    S = -(-S // ROW_PAD) * ROW_PAD
    B = LLM_SAMPLE
    toks = np.zeros((B, S), np.int32)
    r_idx, c_idx, served = [], [], []
    for b, s in enumerate(sids):
        tk, sv = rows[s]
        toks[b, :len(tk)] = tk
        for p in sorted(sv):
            r_idx.append(b)
            c_idx.append(p)
            served.append(sv[p])
    n = len(served)
    P = -(-n // POS_PAD) * POS_PAD       # one program shape per row size
    r_idx = np.asarray(r_idx + [0] * (P - n), np.int32)
    c_idx = np.asarray(c_idx + [0] * (P - n), np.int32)
    served = np.asarray(served)
    ref_logits = np.asarray(ref.logits_at(params, toks, r_idx, c_idx),
                            np.float64)[:n]
    best = ref_logits.max(axis=-1)
    rms = np.sqrt(np.mean(ref_logits ** 2, axis=-1))
    at = np.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    out = {"llm_gap": float(np.max((best - at) / rms)),
           "positions": int(len(served))}
    for i, ctl in enumerate(controls):
        pick = np.asarray(ctl.logits_at(params, toks, r_idx, c_idx)
                          )[:n].argmax(axis=-1)
        at = np.take_along_axis(ref_logits, pick[:, None], axis=-1)[:, 0]
        out[f"llm_gap.{i}"] = float(np.max((best - at) / rms))
    return out


def _text(x):
    return x["text"] if isinstance(x, dict) else str(x)


def _pick_gap(ref_scores: np.ndarray, picked_idx: list, k: int) -> float:
    """Widest shortfall of a picked item's reference score below the
    reference's k-th best, over the scores' standard deviation."""
    if not picked_idx or len(ref_scores) < 2:
        return 0.0
    kth = np.sort(ref_scores)[::-1][min(k, len(ref_scores)) - 1]
    spread = float(np.std(ref_scores)) or 1.0
    return float(max(0.0, max(kth - ref_scores[i] for i in picked_idx))
                 / spread)


def retrieval_gaps(enc_mod, ecfg: dict, weights: dict, stores: list,
                   control: bool = False) -> dict:
    """Chunker, search and rerank of the sampled queries' stores against
    the reference encoders: ``chunks_differ`` (exact), ``retrieval_gap``
    (widest shortfall of a picked item) and ``retrieval_err`` (widest
    error of a score the system reported, over the reference scores'
    spread). With ``control``, the same for the fp8 encoders' picks and
    scores (``.0``)."""
    emb = enc_mod.Encoder(ecfg["embedder"])
    rer = enc_mod.Encoder(ecfg["reranker"])
    ctl = (enc_mod.Encoder(ecfg["embedder"], "fp8"),
           enc_mod.Encoder(ecfg["reranker"], "fp8")) if control else None
    chunks_differ = 0
    gaps, errs, cgaps, cerrs = [0.0], [0.0], [0.0], [0.0]

    def judge(ref, got, picked, k, cscores):
        """ref: reference scores of the candidates; got: the system's
        score of each picked candidate; cscores: the control's scores."""
        spread = float(np.std(ref)) or 1.0
        gaps.append(_pick_gap(ref, picked, k))
        errs.append(max((abs(g - ref[i]) for i, g in zip(picked, got)),
                        default=0.0) / spread)
        if cscores is not None:
            cgaps.append(_pick_gap(ref, list(np.argsort(-cscores)[:k]), k))
            cerrs.append(float(np.max(np.abs(cscores - ref))) / spread)

    for st in stores:
        chunks = enc_mod.chunk(st["docs"])
        got = [_text(c) for c in st.get("chunks", [])]
        chunks_differ += sum(a != b for a, b in zip(chunks, got)) \
            + abs(len(chunks) - len(got))
        where = {}
        for i, c in enumerate(chunks):
            where.setdefault(c, []).append(i)
        cvec = emb(weights["embedder"], chunks)
        cvec_c = ctl[0](weights["embedder"], chunks) if ctl else None
        cands = []
        i = 0
        while f"expanded_queries#{i}" in st:
            hits = st.get(f"retrieved#{i}", [])
            cands.extend(hits)
            q = str(st[f"expanded_queries#{i}"])
            sims = enc_mod.cosine(cvec, emb(weights["embedder"], [q])[0])
            idx, scores = [], []
            for h in hits:
                js = where.get(_text(h))
                if js is None:
                    chunks_differ += 1
                    continue
                idx.append(max(js, key=lambda j: sims[j]))
                scores.append(float(h.get("score", np.nan)))
            csims = enc_mod.cosine(
                cvec_c, ctl[0](weights["embedder"], [q])[0]) if ctl else None
            judge(sims, scores, idx, len(hits), csims)
            i += 1
        uniq = list({_text(c): c for c in cands}.values())
        top = st.get("top_chunks", [])
        if uniq and top:
            texts = [_text(c) for c in uniq]
            pairs = [f"{st['question']} [SEP] {c}" for c in texts]
            ref = rer(weights["reranker"], pairs)
            pos = {c: j for j, c in enumerate(texts)}
            idx = [pos[_text(c)] for c in top if _text(c) in pos]
            chunks_differ += len(top) - len(idx)
            got_s = [float(c.get("rerank_score", np.nan)) for c in top
                     if _text(c) in pos]
            cs = ctl[1](weights["reranker"], pairs) if ctl else None
            judge(ref, got_s, idx, len(top), cs)
    out = {"retrieval_gap": float(max(gaps)),
           "retrieval_err": float(max(errs)),
           "chunks_differ": int(chunks_differ)}
    if control:
        out["retrieval_gap.0"] = float(max(cgaps))
        out["retrieval_err.0"] = float(max(cerrs))
    return out


def sample_queries(done: list, seed: int, k: int = QUERY_SAMPLE) -> list:
    """``k`` of the finished queries, drawn from ``seed``."""
    order = rng_for(seed, 4).permutation(len(done))[:k]
    return [done[i] for i in sorted(order)]
