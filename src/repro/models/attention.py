"""Attention: GQA (grouped-query) and MLA (multi-head latent), with
position-mask unified handling of train / chunked-prefill / decode and
linear / ring-buffer caches.

The mask is derived purely from absolute positions:
    valid(i, j) = k_pos[j] >= 0  and  k_pos[j] <= q_pos[i]
                  and (window is None or k_pos[j] > q_pos[i] - window)
which makes full causal, prefix-cache chunked prefill (Teola's Partial/Full
Prefilling), sliding windows and ring buffers all the same code path.

Long sequences are processed blockwise over the query axis (lax.map over
checkpointed blocks) so peak memory is O(q_block * Skv), not O(S^2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init, softcap, split_keys
from repro.models.sharding import hint, active_mesh
from repro.serving import kv_cache as kvc

NEG_INF = -2.0e38


def _model_axis_size():
    mesh = active_mesh()
    if mesh is None:
        return 1
    try:
        return mesh.shape["model"]
    except (KeyError, TypeError):
        return 1


def _maybe_model(n: int):
    """'model' if the dim is divisible by the TP axis, else None (avoid
    GSPMD padding waste on awkward head counts like Hymba's 25)."""
    from repro.launch import optflags
    if optflags.has("flat_dp"):            # model axis belongs to batch
        return None
    m = _model_axis_size()
    return "model" if (m > 1 and n % m == 0) else None


def position_mask(q_pos, k_pos, window):
    """q_pos (B,Sq), k_pos (B,Skv) -> (B,Sq,Skv)."""
    kp = k_pos[:, None, :]
    qp = q_pos[:, :, None]
    m = (kp >= 0) & (kp <= qp)
    if window is not None:
        m &= kp > (qp - window)
    return m


# ---------------------------------------------------------------------------
# GQA core

def _gqa_core(q, k, v, q_pos, k_pos, scale, window, cap):
    """q (B,Sq,H,hd); k,v (B,Skv,K,hd); grouped einsum (no KV repeat)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qh = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bskgh,btkh->bkgst", qh, k.astype(jnp.float32)) * scale
    s = softcap(s, cap)
    mask = position_mask(q_pos, k_pos, window)              # (B,Sq,Skv)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def blockwise_over_q(core, q, q_pos, q_block):
    """Run `core(q_blk, q_pos_blk)` over query blocks via lax.map with
    rematerialization, keeping peak memory at one block of scores.
    q_pos: (B, Sq)."""
    B, Sq = q.shape[0], q.shape[1]
    if Sq <= q_block or Sq % q_block != 0:
        return core(q, q_pos)
    nb = Sq // q_block
    qb = jnp.moveaxis(q.reshape(B, nb, q_block, *q.shape[2:]), 1, 0)
    pb = jnp.moveaxis(q_pos.reshape(B, nb, q_block), 1, 0)
    fn = jax.checkpoint(lambda args: core(*args))
    ob = jax.lax.map(fn, (qb, pb))
    return jnp.moveaxis(ob, 0, 1).reshape(B, Sq, *ob.shape[3:])


def gqa_attention(q, k, v, q_pos, k_pos, *, scale, window=None, cap=None,
                  q_block=512, causal_skip=False):
    if causal_skip:
        return _gqa_causal_skip(q, k, v, q_pos, k_pos, scale, window, cap,
                                q_block)
    core = lambda qq, pp: _gqa_core(qq, k, v, pp, k_pos, scale, window, cap)
    return blockwise_over_q(core, q, q_pos, q_block)


def _gqa_causal_skip(q, k, v, q_pos, k_pos, scale, window, cap, q_block):
    """Causal block skipping (perf iteration, optflag 'causal_skip'):
    unrolled q-block loop where block i only attends KV[: (i+1)*q_block]
    — halves attention FLOPs for full causal self-attention. Requires
    q_pos == k_pos == contiguous (training / full prefill)."""
    B, Sq, H, hd = q.shape
    if Sq <= q_block or Sq % q_block != 0:
        return _gqa_core(q, k, v, q_pos, k_pos, scale, window, cap)
    nb = Sq // q_block
    outs = []
    for i in range(nb):
        hi = (i + 1) * q_block
        lo = 0
        if window is not None:            # also clip from the left
            lo = max(0, (i * q_block - window) // q_block * q_block)
        blk = jax.checkpoint(
            lambda qq, pp, kk, vv, kp: _gqa_core(qq, kk, vv, pp, kp, scale,
                                                 window, cap))
        outs.append(blk(q[:, i * q_block:hi], q_pos[:, i * q_block:hi],
                        k[:, lo:hi], v[:, lo:hi], k_pos[:, lo:hi]))
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# GQA layer (projections + cache handling)

def init_gqa_params(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, K * hd), dtype),
        "wv": dense_init(ks[2], (d, K * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def paged_write(pool, chunk, block_tables, positions):
    """Scatter a (B,S,...) chunk into a (nb,bs,...) pool through per-seq
    block tables: token (b,i) at absolute position p = positions[b,i]
    lands in physical block block_tables[b, p // bs] at offset p % bs.
    Distinct sequences own distinct blocks, so batch scatters never
    collide (padding rows all target the reserved pad block — last write
    wins on scratch data)."""
    bs = pool.shape[1]
    with jax.named_scope("kv_write"):
        bid = jnp.take_along_axis(block_tables, positions // bs, axis=1)
        return pool.at[bid, positions % bs].set(chunk.astype(pool.dtype))


def paged_gather(pool, block_tables):
    """Gather a sequence-contiguous (B, maxblk*bs, ...) linear view of the
    pool through the block tables (the XLA `take` path; the Pallas kernel
    streams blocks by table instead of materializing this view)."""
    B, maxblk = block_tables.shape
    g = jnp.take(pool, block_tables, axis=0)      # (B, maxblk, bs, ...)
    return g.reshape(B, maxblk * pool.shape[1], *pool.shape[2:])


def gqa_layer(cfg, spec, p, x, cache, pos, q_block=512, block_tables=None):
    """x (B,S,d). cache: elem dict or None (train). pos: dynamic scalar
    (tokens already in cache; 0 for train). With ``block_tables``
    (B,maxblk), cache elems are PAGED POOLS (nb,bs,K,hd) shared across
    sequences: writes scatter through the table, reads gather a linear
    view, and sliding windows are enforced by the position mask alone
    (no ring buffer). Returns (out, new_cache)."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5

    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)

    pos = kvc.batch_pos(pos, B)
    positions = pos[:, None] + jnp.arange(S)[None, :]      # (B,S)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_kind)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_kind)
    q = hint(q, "batch", None, _maybe_model(H), None)

    if cache is None:
        from repro.launch import optflags
        k_pos = positions
        o = gqa_attention(q, k, v, positions, k_pos, scale=scale,
                          window=spec.window, cap=cfg.attn_logit_softcap,
                          q_block=q_block,
                          causal_skip=optflags.has("causal_skip"))
        new_cache = None
    elif block_tables is not None:
        kp = paged_write(cache["k"], k, block_tables, positions)
        vp = paged_write(cache["v"], v, block_tables, positions)
        new_cache = {"k": kp, "v": vp}
        from repro.launch import optflags
        if S > 1 and optflags.has("pallas_chunk_prefill"):
            # chunked-prefill serving path: the prompt chunk's queries
            # (absolute positions pos + i) attend to the paged prefix and
            # to the chunk itself through the scalar-prefetched
            # block-table index maps, q tiled in bq blocks — no gathered
            # per-sequence KV view. Read at TRACE time like the other
            # kernel flags: set before building jitted steps.
            from repro.kernels import ops as kops
            o = kops.chunk_prefill_attention(
                q, kp, vp, block_tables, pos, window=spec.window,
                cap=cfg.attn_logit_softcap, scale=scale).astype(q.dtype)
        elif optflags.has("pallas_paged_attn"):
            # accelerator serving path: stream physical blocks through the
            # scalar-prefetched table index maps instead of materializing
            # the gathered view. verify_attention covers decode (S=1) and
            # speculative multi-token verification (S=k+1) alike — the
            # chunk's queries sit at positions (pos+S) - S + i. The flag
            # is read at TRACE time: set it before building jitted steps.
            from repro.kernels import ops as kops
            o = kops.verify_attention(
                q, kp, vp, block_tables, pos + S, window=spec.window,
                cap=cfg.attn_logit_softcap, scale=scale).astype(q.dtype)
        else:
            kb = paged_gather(kp, block_tables)
            vb = paged_gather(vp, block_tables)
            k_pos = kvc.slot_positions_linear(kb.shape[1], pos + S)
            o = gqa_attention(q, kb.astype(x.dtype), vb.astype(x.dtype),
                              positions, k_pos, scale=scale,
                              window=spec.window,
                              cap=cfg.attn_logit_softcap, q_block=q_block)
    else:
        kb, vb = cache["k"], cache["v"]
        T = kb.shape[1]
        if spec.window is not None:
            # ring buffer (degenerates to linear while pos+S <= T); the
            # window itself is enforced by the position mask. Correctness
            # needs T >= window+S-1 once the ring wraps — init_cache
            # sizes the buffer accordingly.
            kb = kvc.write_ring(kb, k, pos)
            vb = kvc.write_ring(vb, v, pos)
            k_pos = kvc.slot_positions_ring(T, pos + S)     # (B,T)
        else:
            kb = kvc.write_linear(kb, k, pos)
            vb = kvc.write_linear(vb, v, pos)
            k_pos = kvc.slot_positions_linear(T, pos + S)   # (B,T)
        o = gqa_attention(q, kb.astype(x.dtype), vb.astype(x.dtype),
                          positions, k_pos, scale=scale, window=spec.window,
                          cap=cfg.attn_logit_softcap, q_block=q_block)
        new_cache = {"k": kb, "v": vb}
    out = o.reshape(B, S, H * hd) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): absorbed formulation throughout.
#
# Absorbed attention never materializes per-head expanded K/V over the
# context: scores are computed in the compressed kv_lora space
#   q_eff = q_nope @ W_kv_b[k-part]      (B,S,H,r)
#   s     = q_eff . ckv + q_rope . k_rope
#   ctx   = softmax(s) . ckv             (B,S,H,r)
#   out_h = ctx @ W_kv_b[v-part]
# This is the production decode path (the KV cache stays compressed); we
# use it for prefill/train as well — it trades ~2.7x score FLOPs for O(r)
# cache reads, recorded in DESIGN.md / EXPERIMENTS.md.

def init_mla_params(key, cfg, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = split_keys(key, 5)
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, H * qk_hd), dtype),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], (m.kv_lora_rank,
                                    H * (m.qk_nope_head_dim + m.v_head_dim)),
                            dtype),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), dtype),
    }


def _mla_core(q_eff, q_rope, ckv, krope, q_pos, k_pos, scale, window):
    """q_eff (B,Sq,H,r); q_rope (B,Sq,H,p); ckv (B,T,r); krope (B,T,p)."""
    s = (jnp.einsum("bshr,btr->bhst", q_eff.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bshp,btp->bhst", q_rope.astype(jnp.float32),
                      krope.astype(jnp.float32))) * scale
    mask = position_mask(q_pos, k_pos, window)              # (B,Sq,Skv)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", p, ckv.astype(jnp.float32))
    return ctx.astype(q_eff.dtype)


def mla_layer(cfg, spec, p, x, cache, pos, q_block=512, block_tables=None):
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    from repro.models.common import rms_norm
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    cq = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wq_b"]).reshape(B, S, H,
                                 m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    pos = kvc.batch_pos(pos, B)
    positions = pos[:, None] + jnp.arange(S)[None, :]      # (B,S)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, "neox")

    kv_a = x @ p["wkv_a"]
    ckv_new = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope_new = apply_rope(kv_a[..., m.kv_lora_rank:], positions,
                           cfg.rope_theta, "neox")

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H,
                               m.qk_nope_head_dim + m.v_head_dim)
    wk = wkv_b[..., :m.qk_nope_head_dim]          # (r, H, nope)
    wv = wkv_b[..., m.qk_nope_head_dim:]          # (r, H, v)
    q_eff = jnp.einsum("bshn,rhn->bshr", q_nope, wk)
    q_eff = hint(q_eff, "batch", None, _maybe_model(H), None)

    if cache is None:
        ckv, krope = ckv_new, krope_new
        k_pos = positions
        new_cache = None
    elif block_tables is not None:
        cp = paged_write(cache["ckv"], ckv_new, block_tables, positions)
        kp = paged_write(cache["krope"], krope_new, block_tables, positions)
        new_cache = {"ckv": cp, "krope": kp}
        ckv = paged_gather(cp, block_tables).astype(x.dtype)
        krope = paged_gather(kp, block_tables).astype(x.dtype)
        k_pos = kvc.slot_positions_linear(ckv.shape[1], pos + S)
    else:
        ckv = kvc.write_linear(cache["ckv"], ckv_new, pos)
        krope = kvc.write_linear(cache["krope"], krope_new, pos)
        k_pos = kvc.slot_positions_linear(ckv.shape[1], pos + S)
        new_cache = {"ckv": ckv, "krope": krope}
        ckv = ckv.astype(x.dtype)
        krope = krope.astype(x.dtype)

    # blockwise over q on the pair (q_eff, q_rope)
    Sq = q_eff.shape[1]
    if Sq <= q_block or Sq % q_block != 0:
        ctx = _mla_core(q_eff, q_rope, ckv, krope, positions, k_pos, scale,
                        spec.window)
    else:
        nb = Sq // q_block
        qe = jnp.moveaxis(q_eff.reshape(B, nb, q_block, H, -1), 1, 0)
        qr = jnp.moveaxis(q_rope.reshape(B, nb, q_block, H, -1), 1, 0)
        pb = jnp.moveaxis(positions.reshape(B, nb, q_block), 1, 0)
        fn = jax.checkpoint(lambda a: _mla_core(a[0], a[1], ckv, krope, a[2],
                                                k_pos, scale, spec.window))
        ctx = jax.lax.map(fn, (qe, qr, pb))
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, Sq, H, -1)

    out_h = jnp.einsum("bshr,rhv->bshv", ctx, wv)
    out = out_h.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    return out, new_cache
