"""Plain reference of a dense decoder with grouped-query attention
(ChatGLM3, DeepSeek LLM, the Llama layout): its sizes, its weights made
from a seed, its float32 forward, and the operations and bytes of one
serving step.

Written from the published descriptions in plain ``jax.numpy``; it
imports nothing of the system under test. The weight tree uses the
system's layout (``embed``, ``final_norm``, ``lm_head`` and one stage of
stacked layers) so that the same arrays can be handed to both.

Each layer: ``x += Wo attn(rope(q), rope(k), v)`` on ``rmsnorm(x)``, then
``x += W_down (silu(W_gate h) * W_up h)`` on ``rmsnorm(x)``; RMS norms
scale by ``1 + w``. RoPE rotates the first ``rotary_dim`` dims of each
head in the split-half (NeoX) pairing. ChatGLM3 rotates half of each
head in interleaved pairs; that equals the split-half pairing up to a
fixed permutation of the query and key projection columns, so on
random weights the two describe the same family of models.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    """Sizes under one set of names, from a configuration written with
    either ChatGLM's or Llama's keys."""
    def first(*keys):
        for k in keys:
            if k in cfg:
                return cfg[k]
        raise KeyError(keys[0])
    d = int(first("hidden_size"))
    heads = int(first("num_attention_heads"))
    hd = int(cfg.get("kv_channels", d // heads))
    kv = int(first("multi_query_group_num", "num_key_value_heads"))
    rotary = hd // 2 if cfg.get("original_rope") else hd
    return {
        "d": d, "heads": heads, "kv_heads": kv, "head_dim": hd,
        "ff": int(first("ffn_hidden_size", "intermediate_size")),
        "vocab": int(first("padded_vocab_size", "vocab_size")),
        "layers": int(first("num_layers", "num_hidden_layers")),
        "rotary_dim": rotary,
        "rope_theta": float(cfg.get("rope_theta", 10000.0)),
        "qkv_bias": bool(cfg.get("add_qkv_bias", False)),
        "eps": float(first("layernorm_epsilon", "rms_norm_eps")),
        "max_len": int(first("seq_length", "max_position_embeddings")),
    }


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the system's model configuration for these
    sizes (one stage of ``layers`` attention layers)."""
    D = dims(cfg)
    return dict(d_model=D["d"], num_heads=D["heads"],
                num_kv_heads=D["kv_heads"], head_dim=D["head_dim"],
                d_ff=D["ff"], vocab_size=D["vocab"], layers=D["layers"],
                attention_kind="gqa",
                rope_kind="half" if D["rotary_dim"] < D["head_dim"]
                else "neox",
                rope_theta=D["rope_theta"], qkv_bias=D["qkv_bias"],
                act="silu", norm_eps=D["eps"])


def param_count(cfg: dict) -> dict:
    """Parameters: ``matmul`` (every projection, the head included),
    ``embed``, ``total``."""
    D = dims(cfg)
    d, H, K, hd, ff, V, L = (D[k] for k in ("d", "heads", "kv_heads",
                                            "head_dim", "ff", "vocab",
                                            "layers"))
    layer = d * H * hd * 2 + d * K * hd * 2 + 3 * d * ff
    extra = 2 * d + (H + 2 * K) * hd * D["qkv_bias"]
    matmul = L * layer + d * V
    return {"matmul": matmul, "embed": V * d,
            "total": matmul + V * d + L * extra + d}


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Weights from ``seed`` in one jitted call, on the default device,
    in ``dtype``. Projections are normal with std 1/sqrt(fan_in), the
    embedding 0.02; norm scales and QKV biases are small and non-zero so
    that both paths are exercised."""
    D = dims(cfg)
    d, H, K, hd, ff, V, L = (D[k] for k in ("d", "heads", "kv_heads",
                                            "head_dim", "ff", "vocab",
                                            "layers"))

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, F32) * std
                    ).astype(dtype)

        attn = {"wq": normal((L, d, H * hd), d ** -0.5),
                "wk": normal((L, d, K * hd), d ** -0.5),
                "wv": normal((L, d, K * hd), d ** -0.5),
                "wo": normal((L, H * hd, d), (H * hd) ** -0.5)}
        if D["qkv_bias"]:
            attn["bq"] = normal((L, H * hd), 0.02)
            attn["bk"] = normal((L, K * hd), 0.02)
            attn["bv"] = normal((L, K * hd), 0.02)
        layer = {"ln1": normal((L, d), 0.1), "ln2": normal((L, d), 0.1),
                 "attn": attn,
                 "mlp": {"w_gate": normal((L, d, ff), d ** -0.5),
                         "w_up": normal((L, d, ff), d ** -0.5),
                         "w_down": normal((L, ff, d), ff ** -0.5)}}
        return {"embed": normal((V, d), 0.02),
                "final_norm": normal((d,), 0.1),
                "lm_head": normal((d, V), d ** -0.5),
                "stages": [[layer]]}

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)
    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# forward

def fake_quant_fp8(x, axis):
    """Round ``x`` to float8_e4m3 with one scale per slice along ``axis``
    (absmax to 448), back in float32: the control's precision."""
    x = x.astype(F32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, quant):
    """``x @ w`` in float32 at the highest precision; with ``quant``
    both operands are first rounded to fp8 (rows of x, columns of w)."""
    w = w.astype(F32)
    if quant:
        x = fake_quant_fp8(x, -1)
        w = fake_quant_fp8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def _rope(x, pos, rotary, theta):
    """x (B,S,h,hd), pos (S,): rotate the first ``rotary`` dims in
    split-half pairs."""
    inv = 1.0 / theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary)
    ang = pos.astype(F32)[:, None] * inv                  # (S, rotary/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def _layer(D, quant, lp, x):
    B, S, d = x.shape
    H, K, hd = D["heads"], D["kv_heads"], D["head_dim"]
    a = lp["attn"]
    h = _rms(x, lp["ln1"], D["eps"])
    q, k, v = _mm(h, a["wq"], quant), _mm(h, a["wk"], quant), \
        _mm(h, a["wv"], quant)
    if "bq" in a:
        q, k, v = (q + a["bq"].astype(F32), k + a["bk"].astype(F32),
                   v + a["bv"].astype(F32))
    pos = jnp.arange(S)
    q = _rope(q.reshape(B, S, H, hd), pos, D["rotary_dim"], D["rope_theta"])
    k = _rope(k.reshape(B, S, K, hd), pos, D["rotary_dim"], D["rope_theta"])
    v = v.reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", q, k,
                   precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, S, H * hd)
    x = x + _mm(o, a["wo"], quant)
    m = lp["mlp"]
    h = _rms(x, lp["ln2"], D["eps"])
    g = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return x + _mm(g, m["w_down"], quant)


def _layer_fn(D, quant):
    def run(stacked, i, x):
        lp = jax.tree.map(lambda a: a[i], stacked)
        return _layer(D, quant, lp, x)
    return jax.jit(run)


def _head_fn(D, quant, slices):
    def run(params, x, rows, cols):
        """Logits (P, vocab) at the positions ``(rows, cols)`` of x; the
        head is applied in ``slices`` column blocks."""
        h = _rms(x[rows, cols], params["final_norm"], D["eps"])  # (P, d)
        V = params["lm_head"].shape[1]
        step = -(-V // slices)
        parts = [_mm(h, params["lm_head"][:, i:i + step], quant)
                 for i in range(0, V, step)]
        return jnp.concatenate(parts, axis=-1)
    return jax.jit(run)


class Reference:
    """Float32 forward over a batch of token rows, one layer at a time
    (weights are read from the stacked tree and upcast inside each
    layer's program), so that it runs beside the served weights on one
    chip. ``quant="fp8"`` rounds every projection's operands to fp8."""

    def __init__(self, cfg: dict, quant: str | None = None):
        self.D = dims(cfg)
        self.quant = quant == "fp8"
        self._layer = _layer_fn(self.D, self.quant)
        self._head = _head_fn(self.D, self.quant, slices=8)

    def logits_at(self, params, tokens: np.ndarray, rows, cols):
        """Logits (P, vocab) float32 at positions ``(rows[i], cols[i])``
        of the causal forward over ``tokens`` (B, S)."""
        with jax.default_matmul_precision("highest"):
            x = params["embed"][jnp.asarray(tokens)].astype(F32)
            stacked = params["stages"][0][0]
            for i in range(self.D["layers"]):
                x = self._layer(stacked, i, x)
            return self._head(params, x, jnp.asarray(rows),
                              jnp.asarray(cols))


# ---------------------------------------------------------------------------
# operations and bytes of one serving step

def step_cost(cfg: dict, tokens: int, context: int, logits: int,
              weight_bytes: int = 2, kv_bytes: int = 2) -> dict:
    """Model operations and the least bytes one forward step moves.

    ``tokens`` new tokens in the step, attending on average to
    ``context`` earlier positions each (their own included), with the
    head applied at ``logits`` positions. Operations: 2 per
    multiply-add of every projection, plus 4 * context * heads *
    head_dim per token per layer for scores and values. Bytes: every
    weight once, the key/value cache of the context read once per
    sequence and the new tokens' keys and values written.
    """
    D = dims(cfg)
    p = param_count(cfg)
    d, H, K, hd, V, L = (D[k] for k in ("d", "heads", "kv_heads",
                                        "head_dim", "vocab", "layers"))
    body = p["matmul"] - d * V
    flops = 2 * body * tokens + 2 * d * V * logits \
        + 4 * context * H * hd * L * tokens
    kv_tok = 2 * L * K * hd * kv_bytes
    return {"flops": float(flops),
            "weight_bytes": float((p["matmul"] + L * 2 * d + d)
                                  * weight_bytes),
            "kv_bytes_per_token": float(kv_tok)}
