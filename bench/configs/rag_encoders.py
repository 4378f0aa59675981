"""Plain reference of the retrieval chain that builds the RAG prompts:
the word-window chunker, the word-level hash tokenizer, the two small
bidirectional encoders (an embedder with mean pooling and a reranker
scoring its first position), cosine top-k search and rerank top-k.

The encoders are the system's stand-ins for bge-large-en and
bge-reranker-large; their sizes are in the configuration file under
``encoders``. Written in plain ``jax.numpy`` and ``numpy``; it imports
nothing of the system under test. Each layer: ``x += Wo attn(q, k, v)``
on ``rmsnorm(x)`` over the unmasked positions, then
``x += W2 gelu(W1 h)`` on ``rmsnorm(x)``; learned positions are added to
the token embedding; norms scale by ``1 + w``; GELU is the tanh form.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAX_WORDS = 64          # the encoders read at most 64 words of a text


def tokenize(text: str, vocab: int, reserved: int = 8) -> list:
    """Word ids: md5 of the word, first four bytes little-endian, modulo
    the ids above the reserved ones."""
    out = []
    for w in text.split():
        h = int.from_bytes(hashlib.md5(w.encode()).digest()[:4], "little")
        out.append(reserved + h % (vocab - reserved))
    return out


def chunk(docs, size: int = 48, overlap: int = 8) -> list:
    """Windows of ``size`` words every ``size - overlap`` words."""
    out = []
    step = max(1, size - overlap)
    for doc in docs:
        w = doc["text"].split()
        for i in range(0, max(1, len(w) - overlap), step):
            piece = " ".join(w[i:i + size])
            if piece:
                out.append(piece)
    return out


def make_weights(ecfg: dict, seed: int):
    """One encoder's float32 weights from ``seed`` in one jitted call."""
    d, ff, L = ecfg["d_model"], ecfg["d_ff"], ecfg["num_layers"]

    def build(key):
        ks = iter(jax.random.split(key, 4 + 8 * L))

        def normal(shape, std):
            return jax.random.normal(next(ks), shape, F32) * std

        return {"embed": normal((ecfg["vocab_size"], d), 0.02),
                "pos_embed": normal((ecfg["max_len"], d), 0.02),
                "final_norm": normal((d,), 0.1),
                "head": normal((d, ecfg["out_dim"]), d ** -0.5),
                "layers": [{"ln1": normal((d,), 0.1),
                            "ln2": normal((d,), 0.1),
                            "wq": normal((d, d), d ** -0.5),
                            "wk": normal((d, d), d ** -0.5),
                            "wv": normal((d, d), d ** -0.5),
                            "wo": normal((d, d), d ** -0.5),
                            "w1": normal((d, ff), d ** -0.5),
                            "w2": normal((ff, d), ff ** -0.5)}
                           for _ in range(L)]}

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)
    return jax.jit(build)(key)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def fake_quant_fp8(x, axis):
    """Round ``x`` to float8_e4m3 with one scale per slice along ``axis``
    (absmax to 448), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _forward(ecfg, quant, p, tokens, mask):
    """Pooled output: unit-norm embeddings (mean pooling) or scores
    (first position), in float32 at the highest precision; with
    ``quant`` every projection's operands are first rounded to fp8 (the
    control)."""
    prec = jax.lax.Precision.HIGHEST
    B, S = tokens.shape
    H = ecfg["num_heads"]
    hd = ecfg["d_model"] // H
    eps = ecfg["norm_eps"]

    def mm(a, b):
        if quant:
            a, b = fake_quant_fp8(a, -1), fake_quant_fp8(b, 0)
        return jnp.matmul(a, b, precision=prec)

    x = p["embed"][tokens] + p["pos_embed"][:S]
    for lp in p["layers"]:
        h = _rms(x, lp["ln1"], eps)
        q = mm(h, lp["wq"]).reshape(B, S, H, hd)
        k = mm(h, lp["wk"]).reshape(B, S, H, hd)
        v = mm(h, lp["wv"]).reshape(B, S, H, hd)
        s = jnp.einsum("bshd,bthd->bhst", q, k, precision=prec) * hd ** -0.5
        s = jnp.where(mask[:, None, None, :] > 0, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", a, v,
                       precision=prec).reshape(B, S, H * hd)
        x = x + mm(o, lp["wo"])
        h = _rms(x, lp["ln2"], eps)
        x = x + mm(jax.nn.gelu(mm(h, lp["w1"])), lp["w2"])
    x = _rms(x, p["final_norm"], eps)
    if ecfg["pooling"] == "mean":
        pooled = jnp.sum(x * mask[..., None], axis=1) / (
            jnp.sum(mask, axis=1, keepdims=True) + 1e-6)
        e = mm(pooled, p["head"])
        return e / (jnp.linalg.norm(e, axis=-1, keepdims=True) + 1e-6)
    return mm(x[:, 0], p["head"])[:, 0]


class Encoder:
    """One encoder's reference over texts (``quant="fp8"``: the
    control)."""

    def __init__(self, ecfg: dict, quant: str | None = None):
        self.ecfg = ecfg
        q = quant == "fp8"
        self._fwd = jax.jit(lambda p, t, m: _forward(ecfg, q, p, t, m))

    def __call__(self, params, texts) -> np.ndarray:
        ids = [tokenize(t, self.ecfg["vocab_size"])[:MAX_WORDS]
               for t in texts]
        S = max(1, max(len(i) for i in ids))
        toks = np.zeros((len(ids), S), np.int32)
        mask = np.zeros((len(ids), S), np.float32)
        for r, i in enumerate(ids):
            toks[r, :len(i)] = i
            mask[r, :len(i)] = 1.0
        with jax.default_matmul_precision("highest"):
            out = self._fwd(params, jnp.asarray(toks), jnp.asarray(mask))
        return np.asarray(out, np.float64)


def cosine(mat: np.ndarray, q: np.ndarray) -> np.ndarray:
    return mat @ q / (np.linalg.norm(mat, axis=1) * np.linalg.norm(q)
                      + 1e-9)
