"""Pallas TPU kernel: RWKV-6 chunked WKV scan.

The per-head matrix state S (hd x hd, fp32) stays resident in VMEM scratch
across the sequential chunk grid dimension — the TPU-native adaptation of
the CUDA wkv6 kernel (which keeps state in registers/shared memory): on
TPU the state never round-trips to HBM between timesteps, only r/k/v/w
chunk blocks stream HBM->VMEM.

Grid (B, H, S/chunk); the chunk axis is innermost and TPU grid execution
is sequential, so scratch carries state between chunks of the same (b, h)
— chunk must therefore be the LAST grid dim and (b, h) outer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref,
            st_ref, *, chunk):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _load_state():
        st_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                      # (hd,)

    def step(t, _):
        r_t = r_ref[0, t, 0, :].astype(jnp.float32)       # (hd,)
        k_t = k_ref[0, t, 0, :].astype(jnp.float32)
        v_t = v_ref[0, t, 0, :].astype(jnp.float32)
        w_t = w_ref[0, t, 0, :].astype(jnp.float32)
        st = st_ref[...]
        kv = k_t[:, None] * v_t[None, :]                  # (hd, hd)
        y = jnp.einsum("k,kv->v", r_t, st + u[:, None] * kv)
        st_ref[...] = w_t[:, None] * st + kv
        y_ref[0, t, 0, :] = y.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, chunk, step, ())

    @pl.when(c == pl.num_programs(2) - 1)
    def _store_state():
        sf_ref[0, 0] = st_ref[...]


def rwkv6_scan(r, k, v, w, u, state0, *, chunk: int = 64,
               interpret=None):
    """r,k,v,w (B,S,H,hd); u (H,hd); state0 (B,H,hd,hd) fp32.
    Returns (y (B,S,H,hd) fp32, final_state (B,H,hd,hd) fp32)."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0

    grid = (B, H, S // chunk)
    io_spec = pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0))
    kernel = functools.partial(_kernel, chunk=chunk)
    y, sf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            io_spec, io_spec, io_spec, io_spec,
            pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            io_spec,
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u, state0)
    return y, sf
