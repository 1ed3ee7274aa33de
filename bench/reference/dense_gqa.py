"""Plain float32 reference of a low-rank-decomposed dense GQA decoder.

The whole forward pass over one sequence, in straightforward
``jax.numpy`` at ``highest`` matmul precision, with no kernel, cache or
batching: token embedding, then per layer RMSNorm, q/k/v projections,
rotary position embedding (rotate-half, over the whole head), causal
grouped-query softmax attention, the output projection and a residual
add, RMSNorm, the feed-forward block (SwiGLU, or GELU with its tanh
approximation) and a residual add; a final RMSNorm and the head.  Every
decomposed linear is ``(x @ w0) @ w1``; a dense one ``x @ w``.

It reads the parameter tree by its key names (``embed``, ``blocks``
with ``attn_norm``, ``attn/{q,k,v,o}``, ``mlp_norm``,
``mlp/{up,gate,down}``, ``final_norm``, ``unembed``) and the sizes from
the configuration file; it imports nothing of the system under test.
Layers run one at a time under ``lax.scan`` (each upcast to float32 in
turn) and attention runs in blocks of queries, so the pass fits beside
the served tree.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512


def _linear(p: dict, x: jax.Array) -> jax.Array:
    f32 = lambda w: w.astype(jnp.float32)
    if "w0" in p:
        return (x @ f32(p["w0"])) @ f32(p["w1"])
    return x @ f32(p["w"])


def _rms_norm(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (T, heads, D): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal GQA: q (T, H, D), k/v (T, KH, D) -> (T, H, D)."""
    t, h, d = q.shape
    kh = k.shape[1]
    g = h // kh
    qg = q.reshape(t, kh, g, d)
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = qg[start:start + Q_BLOCK]
        n = qb.shape[0]
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / math.sqrt(d)
        qpos = start + jnp.arange(n)
        mask = qpos[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(n, h, d))
    return jnp.concatenate(outs, axis=0)


def _ffn(p: dict, x: jax.Array, act: str) -> jax.Array:
    up = _linear(p["up"], x)
    if act in ("silu", "swiglu"):
        h = jax.nn.silu(_linear(p["gate"], x)) * up
    elif act == "gelu":
        h = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(f"unknown activation {act!r}")
    return _linear(p["down"], h)


def logits_at(params: Any, config: dict, tokens: jax.Array,
              rows: jax.Array) -> jax.Array:
    """Logits ``(len(rows), vocab)`` at positions ``rows`` of the
    sequence ``tokens (T,)``.  Positions after a row never reach it
    (causal), so ``tokens`` may be right-padded."""
    eps = config.get("rms_norm_eps", config.get("norm_eps", 1e-5))
    h, kh = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    theta = float(config["rope_theta"])
    act = config["hidden_act"]
    vocab = config["vocab_size"]
    t = tokens.shape[0]
    positions = jnp.arange(t)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["w"][tokens].astype(jnp.float32)

        def layer(x, p):
            a = _rms_norm(p["attn_norm"]["scale"], x, eps)
            q = _linear(p["attn"]["q"], a).reshape(t, h, hd)
            k = _linear(p["attn"]["k"], a).reshape(t, kh, hd)
            v = _linear(p["attn"]["v"], a).reshape(t, kh, hd)
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
            o = _attention(q, k, v).reshape(t, h * hd)
            x = x + _linear(p["attn"]["o"], o)
            f = _rms_norm(p["mlp_norm"]["scale"], x, eps)
            return x + _ffn(p["mlp"], f, act), None

        x, _ = lax.scan(layer, x, params["blocks"])
        xr = _rms_norm(params["final_norm"]["scale"], x[rows], eps)
        return _linear(params["unembed"], xr)[:, :vocab]
