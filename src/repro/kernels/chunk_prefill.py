"""Pallas TPU kernel: chunked prefill attention over a PAGED KV prefix.

This is the paged-pool generalization of ``flash_prefill``: a prompt
chunk of Sq tokens is prefilled against the sequence's existing prefix,
but the prefix (and the chunk's own just-written KV) live in the SHARED
block pool (num_blocks, block_size, K, hd) and are addressed through a
per-sequence block table — the serving layout of the copy-on-write
prefix-sharing cache. The scalar-prefetched table drives the KV
BlockSpec index maps exactly as in ``paged_decode_attention``: grid step
(b, i, j) DMAs physical block ``table[b, j]`` straight from the pool, so
no gathered per-sequence copy of the KV is ever materialized.

Per-sequence chunk-start positions are the second scalar-prefetch
operand: query row i of sequence b sits at absolute position
``start[b] + i``, which yields the causal mask over the prefix AND
inside the chunk from positions alone (the intra-chunk mask that makes
chunked prefill token-identical to monolithic prefill). Sliding windows
and logit softcap are supported like the other serving kernels.

Tiling: grid (B, Sq/bq, maxblk) with j innermost so the online-softmax
scratch accumulates over KV blocks per (b, i). The softmax state lives
in the GQA-grouped (K, bq*G, ·) row layout shared with
``verify_attention`` — row ``r*G + g`` of kv-group ``k`` is query row
``r`` of head ``k*G + g`` — so score/value matmuls batch over the K axis
with no per-block transposes. Causal block skipping: KV block j is
skipped when ``j*bs`` lies beyond the q block's last position (prefix
blocks stream, future blocks never load).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38
_MAX_Q_ROWS = 2048


def _chunk_kernel(tbl_ref, start_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                  m_ref, l_ref, *, scale, window, cap, bs, bq, G):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    start = start_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal block skip: the q block covers absolute positions
    # [start + i*bq, start + i*bq + bq); KV block j holds logical
    # positions [j*bs, (j+1)*bs) — skip blocks entirely past the last
    # query position (the chunk's KV is already scattered into the pool,
    # so every key at k_pos <= q_pos is valid data)
    @pl.when(j * bs <= start + i * bq + bq - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (bq, H, hd)
        kf = k_ref[0].astype(jnp.float32)                 # (K, bs, hd)
        vf = v_ref[0].astype(jnp.float32)
        hd = q.shape[2]
        K = kf.shape[0]
        qg = jnp.moveaxis(q.reshape(bq, K, G, hd), 0, 1)  # (K, bq, G, hd)
        qg = qg.reshape(K, bq * G, hd)
        s = jax.lax.dot_general(
            qg, kf, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # (K, bq*G, bs)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (K, bq * G, bs), 2)
        q_pos = start + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (K, bq * G, bs), 1) // G
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (K, bq*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, vf, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (K, bq*G, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / denom                          # (K, bq*G, hd)
        K, _, hd = o.shape
        o = jnp.moveaxis(o.reshape(K, bq, G, hd), 0, 1)   # (bq, K, G, hd)
        o_ref[0] = o.reshape(bq, K * G, hd).astype(o_ref.dtype)


def chunk_prefill_attention(q, k_pool, v_pool, block_tables, start, *,
                            window=None, cap=None, scale=None, bq: int = 128,
                            interpret=None):
    """Chunked-prefill attention over the paged block pool.

    q (B, Sq, H, hd): the prompt chunk's queries, row i of sequence b at
    absolute position ``start[b] + i``; k_pool, v_pool
    (num_blocks, block_size, K, hd); block_tables (B, maxblk) int32
    physical block per logical block; start (B,) int32 chunk-start
    positions (= tokens already resident before this chunk). The chunk's
    own KV must already be scattered into the pool. Returns
    (B, Sq, H, hd). Sq == 1 with start = length - 1 reduces to
    ``paged_decode_attention``.
    """
    B, Sq, H, hd = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    G = H // K
    maxblk = block_tables.shape[1]
    if scale is None:
        scale = hd ** -0.5
    # VMEM: a grid step holds about eight f32 tiles of bq*H rows by 128
    # lanes (accumulator, lane-padded max and denominator, scores,
    # probabilities, upcast queries) beside the double-buffered q/o
    # blocks; bq*H <= _MAX_Q_ROWS keeps that inside a v5e's 16 MiB of
    # scoped VMEM (32 heads at bq=128 overflow it)
    bq = min(bq, Sq, max(8, _MAX_Q_ROWS // H))
    if Sq % bq != 0:
        bq = Sq                      # engine buckets divide evenly; odd
    #                                  test shapes fall back to one block
    kh = jnp.moveaxis(k_pool, 2, 1)     # (nb, K, bs, hd)
    vh = jnp.moveaxis(v_pool, 2, 1)
    grid = (B, Sq // bq, maxblk)
    kernel = functools.partial(_chunk_kernel, scale=scale, window=window,
                               cap=cap, bs=bs, bq=bq, G=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, H, hd), lambda b, i, j, tbl, s:
                             (b, i, 0, 0)),
                pl.BlockSpec((1, K, bs, hd),
                             lambda b, i, j, tbl, s: (tbl[b, j], 0, 0, 0)),
                pl.BlockSpec((1, K, bs, hd),
                             lambda b, i, j, tbl, s: (tbl[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, H, hd), lambda b, i, j, tbl, s:
                                   (b, i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, bq * G, hd), jnp.float32),
                pltpu.VMEM((K, bq * G, 1), jnp.float32),
                pltpu.VMEM((K, bq * G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), start.astype(jnp.int32), q, kh, vh)
    return out
