"""Pallas TPU kernel: chunked-prefill flash attention with KV prefix.

This is the TPU-native form of Teola's Partial/Full Prefilling (paper §4.2
Pass 3): a prompt chunk of Sq tokens is prefilled *against an existing KV
prefix* of `prefix_len` tokens already resident in the cache, with causal
masking inside the chunk. GQA is handled natively in the index map (no KV
head repetition), sliding windows and Gemma-2-style logit softcap are
supported.

Tiling: grid (B, H, Sq/bq, T/bk), q/o blocks (bq, hd) and kv blocks
(bk, hd) in VMEM; fp32 running-softmax accumulator scratch. bq/bk default
128 to align with the MXU; hd is the lane dim (128/256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, prefix_len, window, cap, bq, bk, total_len):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = prefix_len + i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (k_pos <= q_pos) & (k_pos < total_len)
    if window is not None:
        mask &= k_pos > q_pos - window

    # skip fully-masked kv blocks (causal block skipping)
    block_needed = j * bk <= prefix_len + i * bq + bq - 1

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_prefill(q, k, v, *, prefix_len: int = 0, window=None, cap=None,
                  scale=None, total_len=None, bq: int = 128, bk: int = 128,
                  interpret=None):
    """q (B, Sq, H, hd); k, v (B, T, K, hd) — the cache buffer with the
    chunk already written at [prefix_len, prefix_len+Sq).
    Returns o (B, Sq, H, hd).
    prefix_len is static (serving engines bucket chunk offsets)."""
    B, Sq, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = hd ** -0.5
    if total_len is None:
        total_len = prefix_len + Sq
    bq = min(bq, Sq)
    bk = min(bk, T)
    assert Sq % bq == 0 and T % bk == 0, (Sq, bq, T, bk)

    # head-major layouts so blocks are (rows, lanes) 2-D tiles
    qh = jnp.moveaxis(q, 2, 1).reshape(B, H, Sq, hd)
    kh = jnp.moveaxis(k, 2, 1).reshape(B, K, T, hd)
    vh = jnp.moveaxis(v, 2, 1).reshape(B, K, T, hd)

    grid = (B, H, Sq // bq, T // bk)
    kernel = functools.partial(
        _kernel, scale=scale, prefix_len=prefix_len, window=window, cap=cap,
        bq=bq, bk=bk, total_len=total_len)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qh, kh, vh)
    return jnp.moveaxis(out, 1, 2)  # (B, Sq, H, hd)
