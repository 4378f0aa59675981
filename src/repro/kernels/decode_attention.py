"""Pallas TPU kernels: batched GQA decode attention (flash-decode style),
dense and PAGED.

Dense: one new token per sequence attends to a KV cache of up to T tokens
with a *dynamic* per-batch valid length (scalar-prefetched, so block index
maps could skip past-the-end blocks on real hardware). GQA native: all H
query heads for a sequence stay resident in VMEM while KV blocks stream by.
Grid (B, T/bk); scratch: fp32 accumulator (H, hd) + running max/denom.

Paged: K/V live in a SHARED block pool (num_blocks, block_size, K, hd) and
each sequence addresses it through a block table — the scalar-prefetched
table drives the KV BlockSpec index maps, so the j-th grid step DMAs
physical block ``table[b, j]`` straight from the pool (no gathered copy of
the sequence's KV is ever materialized). This is the decode path for the
copy-on-write prefix-sharing cache in serving/kv_cache.py.

Verify: ``verify_attention`` generalizes the paged decode kernel from
q_len=1 to q_len=Sq (the speculative-decoding verification forward: the
target model scores a drafted chunk of k tokens plus the committed last
token in ONE pass). Queries sit at absolute positions
``length - Sq + i``; a causal intra-chunk mask keeps draft token i blind
to drafts > i while every query still streams the sequence's full paged
history via the same scalar-prefetched block-table index maps. Sq == 1
reduces exactly to ``paged_decode_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38


def _flash_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _flash_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                 block_start, length, scale, window, cap, bk, G):
    """One KV block of online-softmax accumulation — the numerically
    delicate core shared by the dense and paged decode kernels. The KV
    refs hold the block's data; ``block_start`` is its LOGICAL position
    (dense: j*bk into the sequence's cache; paged: j*bs, with the
    physical block already resolved by the BlockSpec index map)."""
    q = q_ref[0].astype(jnp.float32)                  # (H, hd)
    kf = k_ref[0].astype(jnp.float32)                 # (K, bk, hd)
    vf = v_ref[0].astype(jnp.float32)
    H, hd = q.shape
    K = kf.shape[0]
    qg = q.reshape(K, G, hd)
    s = jax.lax.dot_general(
        qg, kf, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale    # (K, G, bk)
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    k_pos = block_start + jax.lax.broadcasted_iota(jnp.int32, (K, G, bk), 2)
    mask = k_pos < length
    if window is not None:
        mask &= k_pos > (length - 1 - window)
    s = jnp.where(mask, s, NEG_INF)

    sh = s.reshape(H, bk)
    m_prev = m_ref[...]                               # (H,1)
    m_new = jnp.maximum(m_prev, jnp.max(sh, axis=1, keepdims=True))
    p = jnp.exp(sh - m_new)                           # (H, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(
        p.reshape(K, G, bk), vf, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)           # (K, G, hd)
    acc_ref[...] = acc_ref[...] * alpha + pv.reshape(H, hd)


def _flash_finish(o_ref, acc_ref, l_ref):
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, window, cap, bk, G):
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    @pl.when(j * bk < length)
    def _compute():
        _flash_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                     block_start=j * bk, length=length, scale=scale,
                     window=window, cap=cap, bk=bk, G=G)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _flash_finish(o_ref, acc_ref, l_ref)


def decode_attention(q, k, v, length, *, window=None, cap=None, scale=None,
                     bk: int = 128, interpret=None):
    """q (B,H,hd); k,v (B,T,K,hd); length (B,) int32 valid lengths.
    Returns (B,H,hd)."""
    B, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = hd ** -0.5
    bk = min(bk, T)
    assert T % bk == 0

    kh = jnp.moveaxis(k, 2, 1)      # (B,K,T,hd)
    vh = jnp.moveaxis(v, 2, 1)
    grid = (B, T // bk)
    kernel = functools.partial(_kernel, scale=scale, window=window, cap=cap,
                               bk=bk, G=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, j, L: (b, 0, 0)),
                pl.BlockSpec((1, K, bk, hd), lambda b, j, L: (b, 0, j, 0)),
                pl.BlockSpec((1, K, bk, hd), lambda b, j, L: (b, 0, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, hd), lambda b, j, L: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, hd), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(length.astype(jnp.int32), q, kh, vh)
    return out


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                  m_ref, l_ref, *, scale, window, cap, bs, G):
    """Same flash accumulation as ``_kernel`` (shared ``_flash_block``);
    the KV refs already hold physical block ``tbl[b, j]`` (the BlockSpec
    index maps consumed the prefetched table), so the body only needs
    the LOGICAL position ``j * bs + i`` for masking."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    @pl.when(j * bs < length)
    def _compute():
        _flash_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                     block_start=j * bs, length=length, scale=scale,
                     window=window, cap=cap, bk=bs, G=G)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        _flash_finish(o_ref, acc_ref, l_ref)


def _verify_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                   m_ref, l_ref, *, scale, window, cap, bs, Sq, G):
    """Multi-token (q_len=Sq) paged flash accumulation. The online-softmax
    state lives in the GQA-grouped row layout (K, Sq*G, ·) — row
    ``s*G + g`` of kv-group ``k`` is query position ``s`` of head
    ``k*G + g`` — so score/value matmuls batch over the K axis with no
    per-block transposes; the single relayout to (Sq, H, hd) happens once
    at finish. Query ``s`` sits at absolute position ``length - Sq + s``,
    giving the causal intra-chunk mask for free from positions alone."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        _flash_init(acc_ref, m_ref, l_ref)

    @pl.when(j * bs < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (Sq, H, hd)
        kf = k_ref[0].astype(jnp.float32)                 # (K, bs, hd)
        vf = v_ref[0].astype(jnp.float32)
        hd = q.shape[2]
        K = kf.shape[0]
        qg = jnp.moveaxis(q.reshape(Sq, K, G, hd), 0, 1)  # (K, Sq, G, hd)
        qg = qg.reshape(K, Sq * G, hd)
        s = jax.lax.dot_general(
            qg, kf, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # (K, Sq*G, bs)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (K, Sq * G, bs),
                                                  2)
        q_pos = length - Sq + jax.lax.broadcasted_iota(
            jnp.int32, (K, Sq * G, bs), 1) // G
        mask = k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (K, Sq*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, vf, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (K, Sq*G, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / denom                          # (K, Sq*G, hd)
        K, _, hd = o.shape
        o = jnp.moveaxis(o.reshape(K, Sq, G, hd), 0, 1)   # (Sq, K, G, hd)
        o_ref[0] = o.reshape(Sq, K * G, hd).astype(o_ref.dtype)


def verify_attention(q, k_pool, v_pool, block_tables, length, *,
                     window=None, cap=None, scale=None,
                     interpret=None):
    """Speculative-verification attention over the paged pool.

    q (B, Sq, H, hd): the drafted chunk's queries (Sq = draft_k + 1);
    k_pool, v_pool (num_blocks, block_size, K, hd); block_tables
    (B, maxblk) int32; length (B,) int32 TOTAL valid length including the
    Sq chunk positions (query i sits at ``length - Sq + i``; its KV must
    already be scattered into the pool). Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    G = H // K
    maxblk = block_tables.shape[1]
    if scale is None:
        scale = hd ** -0.5

    kh = jnp.moveaxis(k_pool, 2, 1)     # (nb, K, bs, hd)
    vh = jnp.moveaxis(v_pool, 2, 1)
    grid = (B, maxblk)
    kernel = functools.partial(_verify_kernel, scale=scale, window=window,
                               cap=cap, bs=bs, Sq=Sq, G=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Sq, H, hd), lambda b, j, tbl, L:
                             (b, 0, 0, 0)),
                pl.BlockSpec((1, K, bs, hd),
                             lambda b, j, tbl, L: (tbl[b, j], 0, 0, 0)),
                pl.BlockSpec((1, K, bs, hd),
                             lambda b, j, tbl, L: (tbl[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Sq, H, hd), lambda b, j, tbl, L:
                                   (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, Sq * G, hd), jnp.float32),
                pltpu.VMEM((K, Sq * G, 1), jnp.float32),
                pltpu.VMEM((K, Sq * G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), length.astype(jnp.int32), q, kh, vh)
    return out


def paged_decode_attention(q, k_pool, v_pool, block_tables, length, *,
                           window=None, cap=None, scale=None,
                           interpret=None):
    """q (B,H,hd); k_pool,v_pool (num_blocks, block_size, K, hd) shared
    pools; block_tables (B, maxblk) int32 physical block ids per logical
    block; length (B,) int32 valid lengths. Returns (B,H,hd)."""
    B, H, hd = q.shape
    nb, bs, K = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    G = H // K
    maxblk = block_tables.shape[1]
    if scale is None:
        scale = hd ** -0.5

    kh = jnp.moveaxis(k_pool, 2, 1)     # (nb, K, bs, hd)
    vh = jnp.moveaxis(v_pool, 2, 1)
    grid = (B, maxblk)
    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               cap=cap, bs=bs, G=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, j, tbl, L: (b, 0, 0)),
                pl.BlockSpec((1, K, bs, hd),
                             lambda b, j, tbl, L: (tbl[b, j], 0, 0, 0)),
                pl.BlockSpec((1, K, bs, hd),
                             lambda b, j, tbl, L: (tbl[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, hd), lambda b, j, tbl, L:
                                   (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, hd), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables.astype(jnp.int32), length.astype(jnp.int32), q, kh, vh)
    return out
