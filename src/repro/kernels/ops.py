"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) resolves through
``repro.kernels.resolve_interpret``: the kernels compile for Mosaic on a
TPU and run in interpret mode on any other backend (the CPU tests). An
explicit ``interpret=True`` on a TPU is an error.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.chunk_prefill import \
    chunk_prefill_attention as _chunk_prefill
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.decode_attention import \
    paged_decode_attention as _paged_decode
from repro.kernels.decode_attention import verify_attention as _verify
from repro.kernels.flash_prefill import flash_prefill as _prefill
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv


@partial(jax.jit, static_argnames=("prefix_len", "window", "cap", "scale",
                                   "total_len", "bq", "bk", "interpret"))
def flash_prefill(q, k, v, *, prefix_len=0, window=None, cap=None,
                  scale=None, total_len=None, bq=128, bk=128,
                  interpret=None):
    return _prefill(q, k, v, prefix_len=prefix_len, window=window, cap=cap,
                    scale=scale, total_len=total_len, bq=bq, bk=bk,
                    interpret=interpret)


@partial(jax.jit, static_argnames=("window", "cap", "scale", "bk",
                                   "interpret"))
def decode_attention(q, k, v, length, *, window=None, cap=None, scale=None,
                     bk=128, interpret=None):
    return _decode(q, k, v, length, window=window, cap=cap, scale=scale,
                   bk=bk, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "cap", "scale", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, length, *,
                           window=None, cap=None, scale=None,
                           interpret=None):
    return _paged_decode(q, k_pool, v_pool, block_tables, length,
                         window=window, cap=cap, scale=scale,
                         interpret=interpret)


@partial(jax.jit, static_argnames=("window", "cap", "scale", "interpret"))
def verify_attention(q, k_pool, v_pool, block_tables, length, *,
                     window=None, cap=None, scale=None, interpret=None):
    """Speculative-verification attention (paged, q_len = draft_k + 1);
    q_len == 1 reduces to paged_decode_attention."""
    return _verify(q, k_pool, v_pool, block_tables, length, window=window,
                   cap=cap, scale=scale, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "cap", "scale", "bq",
                                   "interpret"))
def chunk_prefill_attention(q, k_pool, v_pool, block_tables, start, *,
                            window=None, cap=None, scale=None, bq=128,
                            interpret=None):
    """Chunked-prefill attention over the paged pool (q at absolute
    positions start[b] + i); Sq == 1 at start = length - 1 reduces to
    paged_decode_attention."""
    return _chunk_prefill(q, k_pool, v_pool, block_tables, start,
                          window=window, cap=cap, scale=scale, bq=bq,
                          interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, state0, *, chunk=64, interpret=None):
    return _rwkv(r, k, v, w, u, state0, chunk=chunk, interpret=interpret)
