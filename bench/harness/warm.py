"""Set-up warm-up: every program shape the cell's traffic reaches, run
once through the engines' own methods before the window opens, so that
nothing is traced or compiled inside it.

The shapes are the system's buckets: the LLM engine pads a step's batch
to ``BUCKETS_B``, a prefill chunk's length to ``BUCKETS_S`` and a block
table's width to its width buckets; the encoders pad batch and length to
their own buckets. The mix file's ``context_blocks`` gives the range of
KV blocks one of its sequences spans (prompt and answer), which bounds
the table widths. Warm-up sequences write only into blocks they own and
release them afterwards.
"""
from __future__ import annotations

import itertools

_ids = itertools.count()


def _bucket(n, buckets):
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _seq(eng, pos: int):
    """A sequence holding ``pos`` positions of scratch KV."""
    st = eng.new_state()
    if pos:
        with eng._paged_lock:
            eng._prepare_write(st, pos)
        st.pos = pos
    sid = f"warm{next(_ids)}"
    eng.states[sid] = st
    return sid, st


def llm_shapes(eng, context_blocks):
    """(decode, prefill) shapes to warm: decode ``(batch, width)`` and
    prefill ``(batch, chunk, width)``, widths in blocks. A decode always
    carries a whole prompt, so its widths start at ``context_blocks``'
    lower end; a prefill pass can start a prompt at position 0 with a
    few tokens (when many prompts share the pass's token budget), so its
    widths start at one block."""
    from repro.engines.llm_engine import BUCKETS_B, BUCKETS_S
    lo, hi = context_blocks
    top = _bucket(hi, eng._blk_buckets)
    widths = [w for w in eng._blk_buckets
              if _bucket(lo, eng._blk_buckets) <= w <= top]
    prefill_widths = [w for w in eng._blk_buckets if w <= top]
    batches = [b for b in BUCKETS_B if b <= _bucket(eng.max_batch,
                                                    BUCKETS_B)]
    chunks = [s for s in BUCKETS_S if s <= _bucket(eng.prefill_chunk,
                                                   BUCKETS_S)]
    bs = eng.block_size
    decode = [(b, w) for b in batches for w in widths]
    prefill = [(b, s, w) for b in batches for s in chunks
               for w in prefill_widths if w * bs >= s]
    return decode, prefill


def warm_llm(eng, context_blocks) -> int:
    """Run every decode and prefill-chunk shape once; returns the number
    of engine calls made."""
    from repro.engines.decode_loop import DecodeSeq, PrefillJob
    decode, prefill = llm_shapes(eng, context_blocks)
    bs = eng.block_size
    calls = 0

    def release(sids):
        for sid in sids:
            eng.release(sid)

    def decode_once(n, width):
        pos = min(width * bs, eng.max_len) - 1
        made = [_seq(eng, pos) for _ in range(n)]
        seqs = [DecodeSeq(sid, st, 1, text_fn=lambda s: "")
                for sid, st in made]
        eng.decode_iteration(seqs)
        release([sid for sid, _ in made])

    for b, w in decode:
        decode_once(b, w)
        calls += 1
    # every batch size, for the host's slicing of the logits
    for n in range(1, eng.max_batch + 1):
        decode_once(n, decode[0][1])
        calls += 1
    for b, s, w in prefill:
        made = [_seq(eng, w * bs - s)] + [_seq(eng, 0) for _ in range(b - 1)]
        jobs = [(PrefillJob(sid, st, [5] * (s if i == 0 else 1)),
                 s if i == 0 else 1) for i, (sid, st) in enumerate(made)]
        eng._prefill_chunk_step(jobs)
        release([sid for sid, _ in made])
        calls += 1
    return calls


def warm_encoders(engines, max_batch: int = 32) -> int:
    """Every (batch, length) bucket of the embedding and rerank engines."""
    from repro.engines import encoder_engines as ee
    calls = 0
    for name in ("embedding", "rerank"):
        eng = engines[name]
        for b in ee._BUCKETS_B:
            if b > max_batch:
                break
            for s in ee._BUCKETS_S:
                eng._encode_batch([" ".join(["warm"] * s)] * b)
                calls += 1
    return calls
