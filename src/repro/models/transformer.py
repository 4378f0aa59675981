"""Composable decoder model: init + apply for every assigned architecture.

A model is a sequence of *stages*; each stage scans a stacked repeating
*pattern* of layers (see configs.base). The same `apply_model` serves
training (cache=None, full causal), chunked prefill (cache + pos offset —
Teola's Partial/Full Prefilling), and decode (S==1).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, LayerSpec
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (act_fn, dense_init, embed_init, rms_norm,
                                 softcap, split_keys)
from repro.models.sharding import hint


# ---------------------------------------------------------------------------
# init

def init_mlp_params(key, cfg, dtype):
    ks = split_keys(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": dense_init(ks[0], (d, f), dtype),
        "w_up": dense_init(ks[1], (d, f), dtype),
        "w_down": dense_init(ks[2], (f, d), dtype),
    }


def init_layer_elem(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    ks = split_keys(key, 4)
    p = {"ln1": jnp.zeros((cfg.d_model,), dtype),
         "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if spec.kind == "rwkv":
        p.update(ssm_mod.init_rwkv_params(ks[0], cfg, dtype))
        return p
    if cfg.attention_kind == "mla":
        p["attn"] = attn.init_mla_params(ks[0], cfg, dtype)
    else:
        p["attn"] = attn.init_gqa_params(ks[0], cfg, dtype)
    if spec.kind == "hybrid":
        p["mamba"] = ssm_mod.init_mamba_params(ks[1], cfg, dtype)
        p["fuse_na"] = jnp.zeros((cfg.d_model,), dtype)
        p["fuse_ns"] = jnp.zeros((cfg.d_model,), dtype)
    if spec.moe:
        p["moe"] = moe_mod.init_moe_params(ks[2], cfg, dtype)
    else:
        p["mlp"] = init_mlp_params(ks[2], cfg, dtype)
    return p


@partial(jax.jit, static_argnames=("cfg", "dtype"))
def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    """Seeded parameter tree. Built under jit so that each float32 draw
    fuses with its cast to ``dtype``: no float32 copy of a stacked
    weight is materialised (at ChatGLM3-6B widths the stacked ``w_down``
    alone would be a 6.3 GB float32 transient). The values are an eager
    build's, up to one rounding: the compiler folds a draw's scale into
    ``normal``'s own sqrt(2) factor."""
    ks = split_keys(key, 3 + len(cfg.stages))
    params = {"embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                  dtype),
              "final_norm": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                       dtype)
    stages = []
    for si, st in enumerate(cfg.stages):
        elem_keys = split_keys(ks[2 + si], len(st.pattern))
        elems = []
        for spec, ek in zip(st.pattern, elem_keys):
            rep_keys = jnp.stack(split_keys(ek, st.repeat))
            elems.append(jax.vmap(
                lambda k, spec=spec: init_layer_elem(k, cfg, spec, dtype)
            )(rep_keys))
        stages.append(elems)
    params["stages"] = stages
    return params


def param_shapes(cfg: ModelConfig, dtype=jnp.bfloat16):
    """Abstract param tree (no allocation) — used by the dry-run."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.key(0))


# ---------------------------------------------------------------------------
# apply

def _ffn(cfg, p, x):
    act = act_fn(cfg.act)
    h = act(x @ p["w_gate"]) * (x @ p["w_up"])
    h = hint(h, "batch", None, "model")
    return h @ p["w_down"]


def apply_layer(cfg, spec, p, x, ce, pos, q_block, block_tables=None):
    """One transformer layer. ce: cache elem dict or None (paged pool
    elems when ``block_tables`` is given). Returns
    (x, new_cache_elem, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    B, S, d = x.shape

    if spec.kind == "rwkv":
        if ce is None:
            s = cfg.ssm
            H = d // s.head_dim
            state = jnp.zeros((B, H, s.head_dim, s.head_dim), jnp.float32)
            sx_tm = jnp.zeros((B, d), jnp.float32)
            sx_cm = jnp.zeros((B, d), jnp.float32)
        else:
            state, sx_tm, sx_cm = ce["state"], ce["sx_tm"], ce["sx_cm"]
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        out, state, sx_tm = ssm_mod.rwkv_time_mix(cfg, p["tm"], h, state,
                                                  sx_tm)
        x = x + out
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        out, sx_cm = ssm_mod.rwkv_channel_mix(cfg, p["cm"], h, sx_cm)
        x = x + out
        nc = None if ce is None else {
            "state": state, "sx_tm": sx_tm.astype(jnp.float32),
            "sx_cm": sx_cm.astype(jnp.float32)}
        return x, nc, aux

    # --- attention (+ optional parallel SSM heads) ---
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    attn_cache_in = None
    if ce is not None:
        attn_cache_in = {k: v for k, v in ce.items()
                         if k in ("k", "v", "ckv", "krope")}
    # named scopes label the device trace's operations by model part
    # (the cache writes inside carry "kv_write")
    layer = attn.mla_layer if cfg.attention_kind == "mla" \
        else attn.gqa_layer
    with jax.named_scope("attention"):
        a_out, a_cache = layer(cfg, spec, p["attn"], h, attn_cache_in, pos,
                               q_block, block_tables)
    nc = dict(a_cache) if a_cache is not None else None

    if spec.kind == "hybrid":
        if ce is None:
            s = cfg.ssm
            h_state = jnp.zeros((B, d, s.state_dim), jnp.float32)
            conv_state = jnp.zeros((B, s.conv_dim - 1, d), jnp.float32)
        else:
            h_state, conv_state = ce["ssm_h"], ce["ssm_conv"]
        s_out, h_state, conv_state = ssm_mod.mamba_branch(
            cfg, p["mamba"], h, h_state, conv_state)
        mixed = 0.5 * (rms_norm(a_out, p["fuse_na"], cfg.norm_eps)
                       + rms_norm(s_out, p["fuse_ns"], cfg.norm_eps))
        x = x + mixed
        if nc is not None:
            nc["ssm_h"] = h_state
            nc["ssm_conv"] = conv_state
    else:
        x = x + a_out

    # --- FFN ---
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if spec.moe:
            f_out, aux = moe_mod.moe_ffn(cfg, p["moe"], h)
        else:
            f_out = _ffn(cfg, p["mlp"], h)
    x = x + f_out
    return x, nc, aux


def apply_model(cfg: ModelConfig, params, inputs, cache=None, pos=0, *,
                q_block=512, remat=True, logits_slice=None,
                block_tables=None, logits_at=None):
    """inputs: int tokens (B,S) or float embeddings (B,S,d) for
    modality-frontend-stub archs. Returns (logits, new_cache, aux_loss).

    cache/pos implement chunked (partial) prefill and decode; cache=None is
    training/eval over the full sequence. ``block_tables`` (B,maxblk)
    switches the cache to the PAGED layout (shared block pools indexed per
    sequence — see serving/kv_cache.py). ``logits_at`` (B,) computes the
    head only at one per-sequence chunk index (exact last-token logits
    under right-padded bucketed prefill), returning (B,1,vocab).
    """
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        x = params["embed"][inputs]
    else:
        x = inputs.astype(params["embed"].dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = hint(x, "batch", None, None)
    pos = jnp.asarray(pos, jnp.int32)

    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"stages": []} if cache is not None else None

    for si, st in enumerate(cfg.stages):
        stacked = params["stages"][si]
        cache_st = cache["stages"][si] if cache is not None else None

        def body(x, xs, st=st, cache_present=cache_st is not None):
            elems = xs[0]
            caches = xs[1] if cache_present else [None] * len(st.pattern)
            new_elems = []
            aux = jnp.zeros((), jnp.float32)
            for spec, pe, ce in zip(st.pattern, elems, caches):
                x, nce, a = apply_layer(cfg, spec, pe, x, ce, pos, q_block,
                                        block_tables)
                aux = aux + a
                if cache_present:
                    new_elems.append(nce)
            return x, (new_elems, aux) if cache_present else aux

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

        if cache_st is not None:
            x, (nc_st, auxs) = jax.lax.scan(body, x, (stacked, cache_st))
            new_cache["stages"].append(nc_st)
        else:
            x, auxs = jax.lax.scan(body, x, (stacked,))
        aux_total = aux_total + jnp.sum(auxs)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logits_at is not None:
        x = x[jnp.arange(x.shape[0]), logits_at][:, None, :]
    elif logits_slice is not None:
        x = x[:, -logits_slice:, :]
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits, cfg.final_logit_softcap)
    logits = hint(logits, "batch", None, "model")
    return logits, new_cache, aux_total


# ---------------------------------------------------------------------------
# float32 reference


@partial(jax.jit, static_argnames=("cfg", "spec"))
def _reference_layer(cfg, spec, elem, r, x):
    p = jax.tree.map(lambda a: a[r].astype(jnp.float32), elem)
    x, _, _ = apply_layer(cfg, spec, p, x, None, 0, q_block=x.shape[1])
    return x


@partial(jax.jit, static_argnames=("cfg",))
def _reference_head(cfg, params, x):
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    return softcap(x @ head.astype(jnp.float32), cfg.final_logit_softcap)


def reference_logits(cfg: ModelConfig, params, tokens):
    """Cache-free float32 forward of ``tokens`` (B, S) at the highest
    matmul precision: logits (B, S, vocab) to check the serving paths
    against (no cache, no paging, no buckets, no kernels). Weights are
    upcast one layer at a time, so a model whose float32 copy would not
    fit its device can still be checked there."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        if cfg.embed_scale:
            x = x * jnp.float32(cfg.d_model ** 0.5)
        for si, st in enumerate(cfg.stages):
            for r in range(st.repeat):
                for spec, elem in zip(st.pattern, params["stages"][si]):
                    x = _reference_layer(cfg, spec, elem, r, x)
        return _reference_head(cfg, params, x)
