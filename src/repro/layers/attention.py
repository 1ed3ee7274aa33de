"""Attention: RoPE, GQA (train/prefill/decode), MLA (DeepSeek-V2), cross-attn.

Prefill/train attention is computed with a *query-chunked* online pass
(`scan` over query blocks, full KV per block, f32 logits) so the logits
tensor never exceeds ``chunk x kv_len`` per (batch, head) — the jnp analogue
of flash attention, and the shape the TPU splash kernel would take.

Decode attends one token against a cache of ``S`` slots; the new token's K/V
is scattered in at each slot's ``pos``.  Under the model's layer scan the
cache is the whole stack ``(L, ...)`` with a traced ``layer`` index: the
write lands in the stacked buffer in place and attention reads the layer
back from it.

Cache layout is the :class:`repro.layers.cache.CachePlan`'s concern:
one plan per attention layer declares the family (``gqa_f32 |
gqa_int8 | mla_latent | mla_latent_int8``), and ``apply_attention`` /
``apply_mla`` are thin executors over it — they own projections, RoPE,
and the prefill softmax (computed on the in-layer full-precision
values), while every write (prefill / chunk-at-offset / decode
scatter), quantize-on-insert, dequant view, and fused-kernel decision
lives on the plan.  The serve stack threads plans explicitly
(``models/blocks.py`` → ``models/lm.py`` → ``serve/runner.py``);
direct layer-level callers fall back to
:func:`repro.layers.cache.plan_from_cache`, the one remaining place a
cache dict's keys are sniffed.

All projections go through :func:`repro.layers.param.apply_linear`, so LRD
surgery (SVD pairs / branched factors) applies transparently — and the
*merged attention* variant (paper §2.3 mapped to QK^T/V·O joint
factorization, DESIGN.md §4) lives here as ``init_merged_attention``.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import tracing
from repro.layers import cache as cache_mod
from repro.layers.cache import CachePlan
from repro.layers.param import (
    ParamBuilder, apply_linear, init_linear, shard_act,
    BATCH, SEQ, EMBED, QKV, RANK, HEADS, KV_HEADS, HEAD_DIM,
)
from repro.layers.norm import init_rms_norm, rms_norm

Q_CHUNK = 1024


class AttnOpts(NamedTuple):
    freeze_factors: bool = False
    use_pallas: bool = False
    softcap: float = 0.0
    act_quantize: bool = False


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_sincos(positions: jax.Array, dim: int, theta: float):
    """positions (...,) -> sin/cos (..., dim/2) in f32."""
    half = dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x (..., S, n_heads, dim); sin/cos (..., S, dim/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s = sin[..., None, :].astype(jnp.float32)
    c = cos[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Core softmax-attention passes
# ---------------------------------------------------------------------------

def _scaled_logits(q, k, scale, softcap):
    # q (B,Sq,KH,G,hd) k (B,Skv,KH,hd) -> (B,KH,G,Sq,Skv), f32
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    return s


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, q_offset: jax.Array | int = 0,
                      softcap: float = 0.0, q_chunk: int = Q_CHUNK,
                      scale: float | None = None) -> jax.Array:
    """q (B,Sq,H,hd), k/v (B,Skv,KH,hd) -> (B,Sq,H,hd).

    Query-chunked: memory O(q_chunk * Skv) per (b, kv-head-group).
    ``q_offset`` is the absolute position of q[0] for causal masking.
    """
    b, sq, h, hd = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, sq, kh, g, hd)

    def attend(qc, qpos):
        s = _scaled_logits(qc, k, scale, softcap)          # (B,KH,G,qc,Skv)
        if causal:
            kpos = jnp.arange(skv)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
        return o.reshape(b, qc.shape[1], h, hd)

    if sq <= q_chunk:
        qpos = q_offset + jnp.arange(sq)
        return attend(qg, qpos)

    n_chunks = sq // q_chunk
    assert sq % q_chunk == 0, (sq, q_chunk)
    qs = qg.reshape(b, n_chunks, q_chunk, kh, g, hd)

    def body(_, xs):
        qc, idx = xs                     # qc (B, q_chunk, KH, G, hd)
        qpos = q_offset + idx * q_chunk + jnp.arange(q_chunk)
        return None, attend(qc, qpos)

    _, out = lax.scan(body, None,
                      (jnp.moveaxis(qs, 1, 0), jnp.arange(n_chunks)))
    # out (n_chunks, B, q_chunk, H, hd) -> (B, Sq, H, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def init_attention(pb: ParamBuilder, name: str, d_model: int, num_heads: int,
                   num_kv_heads: int, head_dim: int) -> None:
    sub = pb.child(name)
    init_linear(sub, "q", d_model, num_heads * head_dim, EMBED, QKV)
    init_linear(sub, "k", d_model, num_kv_heads * head_dim, EMBED, QKV)
    init_linear(sub, "v", d_model, num_kv_heads * head_dim, EMBED, QKV)
    init_linear(sub, "o", num_heads * head_dim, d_model, QKV, EMBED)


def init_kv_cache(batch: int, seq_len: int, num_kv_heads: int, head_dim: int,
                  dtype, quantize: str | None = None) -> dict:
    return cache_mod.gqa_plan(num_kv_heads, head_dim, dtype,
                              quantize).init(batch, seq_len)


def kv_cache_spec(batch: int, seq_len: int, num_kv_heads: int, head_dim: int,
                  dtype, quantize: str | None = None) -> dict:
    return cache_mod.gqa_plan(num_kv_heads, head_dim, dtype,
                              quantize).spec(batch, seq_len)


def apply_attention(p: dict, x: jax.Array, *, num_heads: int,
                    num_kv_heads: int, head_dim: int, rope_theta: float,
                    positions: jax.Array, causal: bool = True,
                    cache: dict | None = None,
                    cache_pos: jax.Array | None = None,
                    prompt_len: jax.Array | None = None,
                    start_pos: jax.Array | None = None,
                    plan: CachePlan | None = None,
                    layer: jax.Array | None = None,
                    opts: AttnOpts = AttnOpts()) -> tuple[jax.Array, dict | None]:
    """Self-attention. Returns (output, updated_cache).

    * train:   cache=None — pure causal attention over x.
    * prefill: cache provided (zeros) — fills cache[0:S], causal.
      ``prompt_len`` (scalar) marks the real token count of a
      right-padded prompt: the plan's quantized prefill write zeroes
      pad positions' K/V before the scale reduction, so bucket padding
      cannot inflate the per-channel scales (causality already hides
      pad *keys* from real queries, padded or not).
    * prefill chunk: ``start_pos`` (scalar) given — x holds prompt
      positions ``[start_pos, start_pos + Sq)`` of a prompt whose
      ``[0, start_pos)`` K/V prefix is already in ``cache``.  The
      chunk's K/V is written at the offset and attention runs over the
      plan's *whole-pool* view with absolute causal masking —
      positions beyond the written prefix can never satisfy
      ``key_pos <= q_pos``, so the full-pool read is exact.
      ``positions`` must carry the absolute offsets.
    * decode:  x has Sq=1, cache full; writes K/V at ``cache_pos`` and
               attends over the whole cache via the plan (fused int8
               kernel under ``use_pallas``).

    ``plan`` is the layer's :class:`repro.layers.cache.CachePlan`; when
    None it is classified from the cache once (static metadata, safe
    under jit).  ``layer`` (traced scalar) marks ``cache`` as every
    layer's stacked cache, of which this is layer ``layer``; the
    returned cache is then the whole stack, written in place.
    """
    b, sq, _ = x.shape
    kw = dict(freeze_factors=opts.freeze_factors, use_pallas=opts.use_pallas,
              act_quantize=opts.act_quantize)
    with jax.named_scope(tracing.QKV_PROJ):
        q = apply_linear(p["q"], x, **kw).reshape(b, sq, num_heads, head_dim)
        k = apply_linear(p["k"], x, **kw).reshape(b, sq, num_kv_heads,
                                                  head_dim)
        v = apply_linear(p["v"], x, **kw).reshape(b, sq, num_kv_heads,
                                                  head_dim)
        sin, cos = rope_sincos(positions, head_dim, rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        q = shard_act(q, BATCH, SEQ, HEADS, HEAD_DIM)
        k = shard_act(k, BATCH, SEQ, KV_HEADS, HEAD_DIM)
        v = shard_act(v, BATCH, SEQ, KV_HEADS, HEAD_DIM)

    new_cache = None
    if cache is None:
        with jax.named_scope(tracing.ATTEND):
            o = chunked_attention(q, k, v, causal=causal,
                                  softcap=opts.softcap)
    else:
        if plan is None:
            plan = cache_mod.plan_from_cache(cache, x.dtype)
        if cache_pos is not None:    # decode: per-slot positions (B,)
            assert sq == 1, sq
            with jax.named_scope(tracing.KV_WRITE):
                new_cache = plan.write_decode(
                    cache, {"k": k[:, 0], "v": v[:, 0]}, cache_pos, layer)
            if layer is not None:
                # read the layer out of the stack only once the query is
                # ready: read earlier, its slice stays live across the
                # query's projection and XLA keeps it in HBM, a copy of
                # the whole layer, where read late it is staged on core
                # as attention's own read
                q, new_cache = lax.optimization_barrier((q, new_cache))
            with jax.named_scope(tracing.ATTEND):
                o = plan.attend_decode(q, plan.layer_view(new_cache, layer),
                                       cache_pos, softcap=opts.softcap,
                                       use_pallas=opts.use_pallas)
        elif start_pos is not None:  # prefill chunk at a sequence offset
            with jax.named_scope(tracing.KV_WRITE):
                new_cache, view = plan.write_chunk(cache, {"k": k, "v": v},
                                                   start_pos, prompt_len,
                                                   layer)
            with jax.named_scope(tracing.ATTEND):
                o = chunked_attention(q, view["k"], view["v"],
                                      causal=causal, q_offset=start_pos,
                                      softcap=opts.softcap)
        else:                        # prefill (any length, incl. 1 token)
            with jax.named_scope(tracing.KV_WRITE):
                new_cache = plan.write_prefill(cache, {"k": k, "v": v},
                                               prompt_len, layer)
            with jax.named_scope(tracing.ATTEND):
                o = chunked_attention(q, k, v, causal=causal,
                                      softcap=opts.softcap)
    with jax.named_scope(tracing.O_PROJ):
        o = o.reshape(b, sq, num_heads * head_dim)
        out = apply_linear(p["o"], o, **kw)
    return out, new_cache


#: full-width decode attention (kept under its historical name — the
#: plan's ``attend_decode`` is the dispatching entry)
_decode_attention = cache_mod.gqa_decode_attention


# ---------------------------------------------------------------------------
# Merged attention (paper §2.3 mapped to transformers, DESIGN.md §4)
# ---------------------------------------------------------------------------

def init_merged_attention(pb: ParamBuilder, name: str, d_model: int,
                          num_heads: int, head_dim: int, qk_rank: int,
                          vo_rank: int) -> None:
    """Joint factorization of the weight *products* W_q W_k^T and W_v W_o.

    Per head group: logits = (x A_q)(x A_k)^T with A_q (d, H, qk_rank),
    A_k (d, qk_rank) shared latent (MLA-style); context = attn · (x B_v) and
    out = ctx · B_o with a vo_rank bottleneck.  Layer count matches the
    original attention (4 matmuls), parameters shrink by rank/d — the
    transformer realization of "layer merging keeps the original depth".
    """
    sub = pb.child(name)
    sub.param("aq", (d_model, num_heads, qk_rank), (EMBED, HEADS, RANK))
    sub.param("ak", (d_model, qk_rank), (EMBED, RANK))
    sub.param("bv", (d_model, vo_rank), (EMBED, RANK))
    sub.param("bo", (vo_rank, num_heads, d_model), (RANK, HEADS, EMBED))


def apply_merged_attention(p: dict, x: jax.Array, *, positions: jax.Array,
                           causal: bool = True,
                           opts: AttnOpts = AttnOpts()) -> jax.Array:
    b, s, d = x.shape
    h = p["aq"].shape[1]
    r = p["aq"].shape[2]
    aq, ak, bv, bo = p["aq"], p["ak"], p["bv"], p["bo"]
    if opts.freeze_factors:
        ak = lax.stop_gradient(ak)
        bv = lax.stop_gradient(bv)
    q = jnp.einsum("bsd,dhr->bshr", x, aq)          # (B,S,H,r)
    k = jnp.einsum("bsd,dr->bsr", x, ak)            # shared latent keys
    sin, cos = rope_sincos(positions, r, 1e4)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k[:, :, None, :], sin, cos)[:, :, 0]
    vlat = jnp.einsum("bsd,dr->bsr", x, bv)         # (B,S,vo_rank)
    o = chunked_attention(q, k[:, :, None, :],
                          vlat[:, :, None, :], causal=causal,
                          softcap=opts.softcap, scale=1.0 / math.sqrt(r))
    out = jnp.einsum("bshr,rhd->bsd", o.reshape(b, s, h, -1), bo)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) — inherently the paper's merged/low-rank attention
# ---------------------------------------------------------------------------

def init_mla(pb: ParamBuilder, name: str, cfg) -> None:
    sub = pb.child(name)
    d = cfg.d_model
    h = cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        init_linear(sub, "q_a", d, cfg.q_lora_rank, EMBED, RANK)
        init_rms_norm(sub, "q_norm", cfg.q_lora_rank)
        init_linear(sub, "q_b", cfg.q_lora_rank, h * qk, RANK, QKV)
    else:
        init_linear(sub, "q_b", d, h * qk, EMBED, QKV)
    init_linear(sub, "kv_a", d, cfg.kv_lora_rank + cfg.qk_rope_dim, EMBED, RANK)
    init_rms_norm(sub, "kv_norm", cfg.kv_lora_rank)
    init_linear(sub, "kv_b", cfg.kv_lora_rank,
                h * (cfg.qk_nope_dim + cfg.v_head_dim), RANK, QKV)
    init_linear(sub, "o", h * cfg.v_head_dim, d, QKV, EMBED)


def mla_cache_spec(batch: int, seq_len: int, cfg, dtype,
                   quantize: str | None = None) -> dict:
    return cache_mod.mla_plan(cfg.kv_lora_rank, cfg.qk_rope_dim, dtype,
                              quantize).spec(batch, seq_len)


def init_mla_cache(batch: int, seq_len: int, cfg, dtype,
                   quantize: str | None = None) -> dict:
    return cache_mod.mla_plan(cfg.kv_lora_rank, cfg.qk_rope_dim, dtype,
                              quantize).init(batch, seq_len)


def _mla_qkr(p, x, cfg, positions, kw):
    b, sq, _ = x.shape
    h = cfg.num_heads
    if cfg.q_lora_rank:
        qa = rms_norm(p["q_norm"], apply_linear(p["q_a"], x, **kw),
                      cfg.norm_eps)
        q = apply_linear(p["q_b"], qa, **kw)
    else:
        q = apply_linear(p["q_b"], x, **kw)
    q = q.reshape(b, sq, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    sin, cos = rope_sincos(positions, cfg.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, sin, cos)
    kva = apply_linear(p["kv_a"], x, **kw)
    ckv, k_rope = jnp.split(kva, [cfg.kv_lora_rank], axis=-1)
    ckv = rms_norm(p["kv_norm"], ckv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def apply_mla(p: dict, x: jax.Array, cfg, *, positions: jax.Array,
              causal: bool = True, cache: dict | None = None,
              cache_pos: jax.Array | None = None,
              prompt_len: jax.Array | None = None,
              start_pos: jax.Array | None = None,
              plan: CachePlan | None = None,
              layer: jax.Array | None = None,
              opts: AttnOpts = AttnOpts()) -> tuple[jax.Array, dict | None]:
    """Multi-head latent attention. Decode uses the *absorbed* form:
    queries projected into the kv_lora latent space, attention runs entirely
    against the cached latents (never materializing per-head K/V) — this is
    exactly the paper's layer-merging executed at inference time.  The
    latent cache is the plan's concern: ``mla_latent`` stores it full
    width, ``mla_latent_int8`` as int8 values + per-(slot, channel) f32
    running-max scales, attended through the fused latent kernel.

    ``start_pos`` (scalar) switches prefill into chunk mode: the chunk's
    latents land at the sequence offset and K/V for attention are
    re-expanded from the *whole* cached latent prefix (unwritten
    positions are zero latents, hidden by the absolute causal mask).
    ``prompt_len`` (scalar) marks the real end of a right-padded chunk
    or prompt — pad rows are zeroed at the latent write, mirroring the
    GQA path, so bucketed chunked prefill is exact for MLA stacks too.
    ``layer`` marks a stacked cache, as in :func:`apply_attention`.
    """
    b, sq, _ = x.shape
    h, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    vd = cfg.v_head_dim
    kw = dict(freeze_factors=opts.freeze_factors, use_pallas=opts.use_pallas,
              act_quantize=opts.act_quantize)
    q_nope, q_rope, ckv, k_rope = _mla_qkr(p, x, cfg, positions, kw)
    scale = 1.0 / math.sqrt(nope + rope_d)

    new_cache = None
    if cache is not None and plan is None:
        plan = cache_mod.plan_from_cache(cache, x.dtype)
    if cache is not None and cache_pos is not None:  # absorbed decode
        assert sq == 1, sq
        new_cache = plan.write_decode(
            cache, {"ckv": ckv[:, 0], "krope": k_rope[:, 0]}, cache_pos,
            layer)
        # Absorbed decode: fold kv_b's K-half into q, V-half into output.
        wkv = _kv_b_matrix(p["kv_b"], cfg)             # (lora, h, nope+vd)
        wk, wv = wkv[..., :nope], wkv[..., nope:]
        q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, wk)     # (B,1,H,lora)
        ctx_lat = plan.attend_decode_latent(
            q_lat, q_rope, plan.layer_view(new_cache, layer), cache_pos,
            scale=scale, use_pallas=opts.use_pallas)
        o = jnp.einsum("bqhl,lhv->bqhv", ctx_lat, wv)
    else:
        if cache is not None and start_pos is not None:
            # chunk: write at the offset, attend over the whole cached
            # latent prefix (the plan's full-precision view)
            new_cache, view = plan.write_chunk(
                cache, {"ckv": ckv, "krope": k_rope}, start_pos, prompt_len,
                layer)
            src_ckv, src_rope = view["ckv"], view["krope"]
            skv, q_off = src_ckv.shape[1], start_pos
        else:
            if cache is not None:   # whole prefill: fill the latent cache
                new_cache = plan.write_prefill(
                    cache, {"ckv": ckv, "krope": k_rope}, prompt_len, layer)
            src_ckv, src_rope, skv, q_off = ckv, k_rope, sq, 0
        kv = apply_linear(p["kv_b"], src_ckv, **kw).reshape(b, skv, h,
                                                            nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(src_rope[:, :, None, :],
                                      (b, skv, h, rope_d))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # pad v to qk dim for the shared attention kernel, then slice
        o = chunked_attention(q, k, _pad_last(v, nope + rope_d - vd),
                              causal=causal, q_offset=q_off,
                              softcap=opts.softcap, scale=scale)[..., :vd]
    out = apply_linear(p["o"], o.reshape(b, sq, h * vd), **kw)
    return out, new_cache


def _kv_b_matrix(p: dict, cfg) -> jax.Array:
    """kv_b as a dense (lora, h, nope+vd) tensor (recompose if decomposed)."""
    from repro.layers.param import linear_kind
    if linear_kind(p) == "dense":
        w = p["w"]
    elif linear_kind(p) == "lowrank":
        w = p["w0"] @ p["w1"]
    else:
        w = jnp.einsum("ncr,nrs,nso->co", p["u"], p["xc"], p["v"])
    return w.reshape(cfg.kv_lora_rank, cfg.num_heads,
                     cfg.qk_nope_dim + cfg.v_head_dim)


def _pad_last(x, n):
    if n <= 0:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n)]
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# Cross attention (VLM): queries from text, K/V from image embeddings
# ---------------------------------------------------------------------------

def init_cross_attention(pb: ParamBuilder, name: str, d_model: int,
                         num_heads: int, num_kv_heads: int,
                         head_dim: int, kv_dim: int) -> None:
    sub = pb.child(name)
    init_linear(sub, "q", d_model, num_heads * head_dim, EMBED, QKV)
    init_linear(sub, "k", kv_dim, num_kv_heads * head_dim, EMBED, QKV)
    init_linear(sub, "v", kv_dim, num_kv_heads * head_dim, EMBED, QKV)
    init_linear(sub, "o", num_heads * head_dim, d_model, QKV, EMBED)
    sub.param("gate", (), (), init="zeros")


def cross_attn_kv(p: dict, kv_feats: jax.Array, *, num_kv_heads: int,
                  head_dim: int, opts: AttnOpts = AttnOpts()) -> dict:
    """Precompute cross-attention K/V from image features (cached at
    prefill — image tokens never change during decode)."""
    b, t, _ = kv_feats.shape
    kw = dict(freeze_factors=opts.freeze_factors, use_pallas=opts.use_pallas,
              act_quantize=opts.act_quantize)
    k = apply_linear(p["k"], kv_feats, **kw).reshape(b, t, num_kv_heads,
                                                     head_dim)
    v = apply_linear(p["v"], kv_feats, **kw).reshape(b, t, num_kv_heads,
                                                     head_dim)
    return {"k": k, "v": v}


def apply_cross_attention(p: dict, x: jax.Array,
                          kv_feats: jax.Array | None = None, *,
                          num_heads: int, num_kv_heads: int, head_dim: int,
                          kv: dict | None = None,
                          opts: AttnOpts = AttnOpts()) -> jax.Array:
    b, sq, _ = x.shape
    kw = dict(freeze_factors=opts.freeze_factors, use_pallas=opts.use_pallas,
              act_quantize=opts.act_quantize)
    if kv is None:
        kv = cross_attn_kv(p, kv_feats, num_kv_heads=num_kv_heads,
                           head_dim=head_dim, opts=opts)
    q = apply_linear(p["q"], x, **kw).reshape(b, sq, num_heads, head_dim)
    o = chunked_attention(q, kv["k"], kv["v"], causal=False,
                          softcap=opts.softcap)
    o = apply_linear(p["o"], o.reshape(b, sq, num_heads * head_dim), **kw)
    return jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * o
