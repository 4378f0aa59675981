"""Mixture-of-Experts FFN.

Two semantically-equivalent implementations of the routed path:

1. ``routed_dense`` — capacity-free masked compute; every expert weight is
   used for its assigned tokens via scatter/gather on a single device.
   Used for smoke tests and the Teola CPU engines.

2. ``routed_ep`` — expert-parallel shard_map for the production mesh:
   tokens are sequence-sharded over the 'model' axis; each model shard
   owns E/TP experts; dispatch/combine go through explicit
   ``all_to_all`` collectives with per-expert capacity (GShard-style
   token dropping at capacity_factor). Expert weights are additionally
   FSDP-sharded over 'data' and all-gathered per layer.

Shared experts are a plain dense FFN (tensor-parallel over 'model'),
computed outside the shard_map and added to the routed output — this is
the DeepSeek-V3 / Qwen-MoE shared-expert structure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import act_fn, dense_init, split_keys
from repro.models.sharding import active_mesh, hint


def init_moe_params(key, cfg, dtype):
    mo = cfg.moe
    d = cfg.d_model
    ks = split_keys(key, 5)
    p = {
        "router": dense_init(ks[0], (d, mo.num_experts), jnp.float32),
        # stacked expert weights: (E, d, f) / (E, f, d)
        "w_gate": dense_init(ks[1], (mo.num_experts, d, mo.d_expert), dtype),
        "w_up": dense_init(ks[2], (mo.num_experts, d, mo.d_expert), dtype),
        "w_down": dense_init(ks[3], (mo.num_experts, mo.d_expert, d), dtype),
    }
    if mo.num_shared_experts:
        ks2 = split_keys(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(ks2[0], (d, mo.d_shared), dtype),
            "w_up": dense_init(ks2[1], (d, mo.d_shared), dtype),
            "w_down": dense_init(ks2[2], (mo.d_shared, d), dtype),
        }
    return p


def router_probs(cfg, router_w, x2d):
    """x2d (T, d) -> (gates (T,k), idx (T,k)) with optional top-k renorm."""
    mo = cfg.moe
    logits = x2d.astype(jnp.float32) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    return gates, idx, logits


def aux_load_balance_loss(cfg, logits, idx):
    """Switch-style load-balance auxiliary loss (mean fraction * mean prob)."""
    mo = cfg.moe
    E = mo.num_experts
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs, axis=0)                        # (E,)
    counts = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    ce = counts / (idx.size + 1e-9)
    return E * jnp.sum(me * ce)


def _expert_ffn(act, xg, w_gate, w_up, w_down):
    """xg (E, C, d); weights (E, d, f)/(E, f, d)."""
    h = act(jnp.einsum("ecd,edf->ecf", xg, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", xg, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


# ---------------------------------------------------------------------------
# 1. dense/local routed path

def routed_dense(cfg, p, x2d):
    """Exact top-k MoE without capacity dropping (single device)."""
    mo = cfg.moe
    act = act_fn(cfg.act)
    gates, idx, logits = router_probs(cfg, p["router"], x2d)
    T, d = x2d.shape
    out = jnp.zeros_like(x2d)
    # one-hot combine: y = sum_e mask_e * gate_e * ffn_e(x)
    # computed expert-major to keep weights stacked.
    oh = jax.nn.one_hot(idx, mo.num_experts, dtype=x2d.dtype)   # (T,k,E)
    combine = jnp.einsum("tk,tke->te", gates.astype(x2d.dtype), oh)  # (T,E)
    h = act(jnp.einsum("td,edf->tef", x2d, p["w_gate"])) * \
        jnp.einsum("td,edf->tef", x2d, p["w_up"])
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", y, combine)
    return out, aux_load_balance_loss(cfg, logits, idx)


# ---------------------------------------------------------------------------
# 2. expert-parallel shard_map path

def _ep_local(cfg, act, x_local, router_w, w_gate, w_up, w_down, *,
              tp_size: int, ep, fsdp: tuple = ("data",)):
    """Runs per-device inside shard_map. x_local (Tl, d).
    Expert weights arrive expert-sharded over 'model' (E_local = E_pad/tp
    each; experts padded up to a multiple of tp — padded experts receive no
    tokens) and FSDP-sharded over 'data' on the d axis; the d axis is
    all-gathered here (explicit FSDP weight gather, overlappable by XLA)."""
    mo = cfg.moe
    El = w_gate.shape[0]                   # local (padded) experts per shard
    E_pad = El * tp_size
    Tl, d = x_local.shape

    if fsdp:
        wg = jax.lax.all_gather(w_gate, fsdp, axis=1, tiled=True)
        wu = jax.lax.all_gather(w_up, fsdp, axis=1, tiled=True)
        wd = jax.lax.all_gather(w_down, fsdp, axis=2, tiled=True)
    else:                                  # resident expert weights
        wg, wu, wd = w_gate, w_up, w_down

    gates, idx, logits = router_probs(cfg, router_w, x_local)
    k = mo.top_k
    # per-sender capacity per expert (based on the REAL expert count)
    cap = max(1, int(Tl * k / mo.num_experts * mo.capacity_factor))

    # slot assignment: flat (Tl*k,) expert ids -> position within expert
    eid = idx.reshape(-1)                                  # (Tl*k,)
    gat = gates.reshape(-1).astype(x_local.dtype)
    onehot = jax.nn.one_hot(eid, E_pad, dtype=jnp.int32)    # (Tl*k, E_pad)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot          # exclusive
    pos = jnp.sum(pos_in_e * onehot, axis=-1)               # (Tl*k,)
    keep = pos < cap
    slot = eid * cap + jnp.minimum(pos, cap - 1)            # (Tl*k,)

    # scatter tokens into the send buffer (E_pad*cap, d)
    tok = jnp.repeat(x_local, k, axis=0)                    # (Tl*k, d)
    send = jnp.zeros((E_pad * cap, d), x_local.dtype)
    send = send.at[slot].add(jnp.where(keep[:, None], tok, 0))

    # all_to_all over the expert-parallel axes: shard j receives its
    # experts' tokens
    send = send.reshape(tp_size, El * cap, d)
    recv = jax.lax.all_to_all(send, ep, split_axis=0, concat_axis=0,
                              tiled=True)                   # (tp*El*cap, d)
    recv = recv.reshape(tp_size, El, cap, d)
    recv = jnp.moveaxis(recv, 1, 0).reshape(El, tp_size * cap, d)

    # local experts (already this shard's E_local slice)
    y = _expert_ffn(act, recv, wg, wu, wd)                  # (El, tp*cap, d)

    # route back
    y = jnp.moveaxis(y.reshape(El, tp_size, cap, d), 1, 0)
    y = y.reshape(tp_size, El * cap, d)
    back = jax.lax.all_to_all(y, ep, split_axis=0, concat_axis=0,
                              tiled=True)
    back = back.reshape(E_pad * cap, d)                     # sender layout

    # combine: gather each assignment's slot, weight by gate
    yk = back[slot] * jnp.where(keep, gat, 0.0)[:, None]    # (Tl*k, d)
    out = jnp.sum(yk.reshape(Tl, k, d), axis=1)
    return out, aux_load_balance_loss(cfg, logits, idx)


def routed_ep(cfg, p, x2d, mesh):
    """x2d (T, d), T divisible by the full device count; tokens are
    sharded over all mesh axes so every device routes a disjoint slice
    (true expert parallelism; all_to_all runs along the EP axes —
    'model' by default, ('model','data') under the ep_all_axes flag)."""
    from repro.launch.shard_rules import ep_axes, fsdp_axes
    act = act_fn(cfg.act)
    ep = ep_axes(mesh)
    tp = 1
    for a in ep:
        tp *= mesh.shape[a]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp = tuple(a for a in fsdp_axes(mesh) if a not in ep)
    ep_sp = ep if len(ep) > 1 else ep[0]
    fsdp_sp = (fsdp if len(fsdp) > 1 else fsdp[0]) if fsdp else None
    tok_spec = P(batch_axes + ("model",), None)

    # pad expert count up to a multiple of the TP axis (padded experts are
    # never routed to; GSPMD stores the uneven original padded anyway)
    E = cfg.moe.num_experts
    E_pad = -(-E // tp) * tp
    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    if E_pad != E:
        w_gate = jnp.pad(w_gate, ((0, E_pad - E), (0, 0), (0, 0)))
        w_up = jnp.pad(w_up, ((0, E_pad - E), (0, 0), (0, 0)))
        w_down = jnp.pad(w_down, ((0, E_pad - E), (0, 0), (0, 0)))

    def body(x_l, rw, wg, wu, wd):
        out, aux = _ep_local(cfg, act, x_l, rw, wg, wu, wd, tp_size=tp,
                             ep=ep, fsdp=fsdp)
        for ax in ("model",) + batch_axes:
            aux = jax.lax.pmean(aux, ax)
        return out, aux

    w_specs = (P(ep_sp, fsdp_sp, None), P(ep_sp, fsdp_sp, None),
               P(ep_sp, None, fsdp_sp))
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None)) + w_specs,
        out_specs=(tok_spec, P()), check_vma=False,
    )(x2d, p["router"], w_gate, w_up, w_down)
    return out, aux


# ---------------------------------------------------------------------------

def shared_expert_ffn(cfg, p, x):
    act = act_fn(cfg.act)
    sp = p["shared"]
    h = act(x @ sp["w_gate"]) * (x @ sp["w_up"])
    h = hint(h, "batch", None, "model")
    return h @ sp["w_down"]


def moe_ffn(cfg, p, x):
    """x (B,S,d) -> (out, aux_loss). Chooses EP when a mesh is active and
    expert count divides the TP axis; otherwise the dense path."""
    mo = cfg.moe
    mesh = active_mesh()
    B, S, d = x.shape
    use_ep = (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
    )
    if use_ep:
        # flat-token layout, padded up to the device count so shard_map
        # divides evenly (decode steps have few tokens)
        T = B * S
        shards = _total_batch_shards(mesh) * mesh.shape["model"]
        Tp = -(-T // shards) * shards
        x2d = x.reshape(T, d)
        if Tp != T:
            x2d = jnp.pad(x2d, ((0, Tp - T), (0, 0)))
        out2d, aux = routed_ep(cfg, p, x2d, mesh)
        out = out2d[:T].reshape(B, S, d)
    else:
        out2d, aux = routed_dense(cfg, p, x.reshape(B * S, d))
        out = out2d.reshape(B, S, d)
    if mo.num_shared_experts:
        out = out + shared_expert_ffn(cfg, p, x)
    return out, aux


def _total_batch_shards(mesh):
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
