"""Runtime KV-cache quantization: per-(slot, head, channel) int8 K/V.

Weight quantization (:mod:`repro.quant.quantize`) shrinks the *static*
stream; at serve time the decode step is bound by the *runtime* stream —
every token reads the entire KV pool ``(slots, S_max, KV_heads,
head_dim)`` to attend to one query.  This module stores that pool as
int8 values plus f32 scales so the decode-attention read moves ~4x
fewer bytes than an f32 pool (~2x vs bf16), and the fused kernel
(:mod:`repro.kernels.decode_attention_q`) dequantizes tiles in VMEM so
no full-precision copy ever materializes in HBM.

Layout (mirrors the ``k_q``/``k_scale`` pair convention of the weight
subsystem):

    {"k":  (B, S, KH, D) f32}
      -> {"k_q": int8 (B, S, KH, D), "k_scale": f32 (B, KH, D)}

Scales are **per (slot, head, channel)** — one f32 scale per head_dim
channel of each slot's K (or V) stream, i.e. the absmax reduction runs
over the *sequence* axis.  Two reasons over per-token scales:

* the kernel folds K scales into the single query row and V scales into
  the final output (O(D) multiplies instead of O(S*D) dequant work);
* scale storage is O(KH*D) per slot instead of O(S*KH), so the byte
  overhead vanishes as contexts grow.

The cost is that the sequence-reduced scale must cover tokens that have
not arrived yet.  :func:`kv_write_token` handles this *incrementally*:
the scale is a running per-channel max, and when a new token enlarges
it, the slot's int8 history is rescaled in place (``round(q * old/new)``
— at most half an LSB of extra rounding at the new, larger scale; the
O(S) rescale pass is skipped via ``lax.cond`` when no channel grew, so
the steady-state write is a one-row scatter).
Symmetric, no zero point: ``x ~= q * scale`` with ``q in [-127, 127]``.

Prefill quantizes on insert: the whole prompt's K/V is reduced over its
sequence axis in one shot, so the cache pool and the engine's
``_insert_slot`` scatter stay int8 throughout — no f32 staging copy.

The write/quantize primitives are *rank-polymorphic over the tail*: the
same running-max math that handles GQA pools ``(B, S, KH, D)`` with
scales ``(B, KH, D)`` handles the MLA latent cache ``(B, S, r)`` with
per-(slot, channel) scales ``(B, r)`` — the ``mla_latent_int8`` family
of :mod:`repro.layers.cache` reuses ``quantize_kv_prefill`` /
``kv_write_token`` / ``kv_write_chunk`` verbatim on its ``ckv`` /
``krope`` leaves.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.quant.quantize import INT8_QMAX

PyTree = Any

#: runtime KV quantization modes (weight-side fp8 has no KV variant:
#: the decode kernel's dequant-free scale folding needs the int8 grid).
KV_MODES = ("int8",)

#: overflow ceiling for the running-max scales.  A single NaN/Inf
#: activation must corrupt only its own cache row — NOT the
#: per-(slot, head, channel) scale, which the requant pass multiplies
#: into the slot's entire int8 history (``ratio = old/new`` goes to ~0
#: under an overflowed scale, silently zeroing every past token, and a
#: NaN propagates through ``maximum`` forever).
KV_SCALE_MAX = 1e30


def _finite_scale(candidate: jax.Array) -> jax.Array:
    """Overflow-guard a running-max scale candidate: a non-finite
    absmax contributes **nothing** (the running max keeps its old
    value, so the slot's int8 history survives bit-exact — the
    poisoned row itself is sanitized to 0 by :func:`quantize_kv`, and
    the numerical watchdog quarantines the stream off its own NaN
    logits the same step); finite candidates are capped at
    :data:`KV_SCALE_MAX`."""
    return jnp.minimum(jnp.where(jnp.isfinite(candidate), candidate, 0.0),
                       KV_SCALE_MAX)


def layer_of(x: jax.Array, layer: jax.Array | None) -> jax.Array:
    """Layer ``layer`` of a leaf stacked ``(L, ...)`` along the layer
    scan, or ``x`` itself when ``layer`` is None (a per-layer leaf)."""
    if layer is None:
        return x
    return jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)


def with_layer(x: jax.Array, value: jax.Array,
               layer: jax.Array | None) -> jax.Array:
    """``x`` with layer ``layer`` replaced by ``value``, or ``value``
    itself when ``layer`` is None — the write twin of :func:`layer_of`."""
    if layer is None:
        return value
    return jax.lax.dynamic_update_index_in_dim(x, value, layer, 0)


def layer_index(layer: jax.Array | None, *idx) -> tuple:
    """An ``.at[...]`` index of per-layer rows ``idx``, prefixed with
    ``layer`` for a stacked leaf: the scatter then lands in the stacked
    buffer itself, not in a copy of the layer."""
    return idx if layer is None else (layer, *idx)


def _check_mode(mode: str) -> None:
    if mode not in KV_MODES:
        raise ValueError(
            f"unknown kv quant mode {mode!r} (want one of {KV_MODES})")


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def kv_cache_spec_q(batch: int, seq_len: int, num_kv_heads: int,
                    head_dim: int, mode: str = "int8") -> dict:
    """ShapeDtypeStruct tree of an int8 KV cache (the quantized twin of
    :func:`repro.layers.attention.kv_cache_spec`)."""
    _check_mode(mode)
    vshape = (batch, seq_len, num_kv_heads, head_dim)
    sshape = (batch, num_kv_heads, head_dim)
    return {"k_q": jax.ShapeDtypeStruct(vshape, jnp.int8),
            "k_scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
            "v_q": jax.ShapeDtypeStruct(vshape, jnp.int8),
            "v_scale": jax.ShapeDtypeStruct(sshape, jnp.float32)}


def init_kv_cache_q(batch: int, seq_len: int, num_kv_heads: int,
                    head_dim: int, mode: str = "int8") -> dict:
    """Zero-initialized int8 KV cache (zero scales dequantize to zeros)."""
    spec = kv_cache_spec_q(batch, seq_len, num_kv_heads, head_dim, mode)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def is_quantized_kv(cache: Any) -> bool:
    """Does this per-layer cache dict hold int8 K/V (or int8 MLA
    latents)?"""
    return isinstance(cache, dict) and ("k_q" in cache or "ckv_q" in cache)


# ---------------------------------------------------------------------------
# Quantize / dequantize
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize ``x`` with a given (broadcastable) scale -> int8.

    Non-finite inputs land as 0 (``int8`` cast of NaN is undefined;
    a poisoned activation must corrupt only its own row,
    deterministically)."""
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(x.astype(jnp.float32) / safe)
    q = jnp.where(jnp.isfinite(q), jnp.clip(q, -INT8_QMAX, INT8_QMAX), 0.0)
    return q.astype(jnp.int8)


def kv_scales(x: jax.Array, axis: int = 1) -> jax.Array:
    """Per-(slot, head, channel) scales: absmax over the seq ``axis``,
    clamped to :data:`KV_SCALE_MAX` (overflow guard)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis)
    return _finite_scale(amax / INT8_QMAX)


def quantize_kv_prefill(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One-shot prompt quantization.

    ``x (B, S, KH, D)`` -> ``(q int8 (B, S, KH, D), scale f32 (B, KH, D))``
    with the absmax reduced over the prompt's sequence axis.
    """
    scale = kv_scales(x, axis=1)
    return quantize_kv(x, scale[:, None]), scale


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """``q (B, S, KH, D) * scale (B, KH, D)`` -> ``(B, S, KH, D)``."""
    return (q.astype(jnp.float32) * scale[:, None]).astype(dtype)


def kv_write_chunk(cache_q: jax.Array, scale: jax.Array, new: jax.Array,
                   start: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Insert a prefill chunk's K (or V) into an int8 cache pool.

    ``cache_q (B, S, KH, D)`` int8; ``scale (B, KH, D)`` f32;
    ``new (B, C, KH, D)``; ``start`` scalar — the chunk's sequence
    offset.  The chunked twin of :func:`kv_write_token`: ONE vectorized
    per-channel absmax over the whole chunk updates the running-max
    scale (instead of C sequential per-token passes, each with its own
    potential O(S) history requant), the slot history is requantized at
    most once per chunk, and the chunk lands as a single
    ``dynamic_update_slice``.  The final scale equals the per-token
    loop's (max is associative); requantized history values can differ
    by 1 LSB from the sequential path (one rounding instead of several).
    """
    newf = new.astype(jnp.float32)
    scale_new = jnp.maximum(
        scale, _finite_scale(jnp.max(jnp.abs(newf), axis=1) / INT8_QMAX))

    def _requant(c):
        safe = jnp.where(scale_new > 0, scale_new, 1.0)
        ratio = jnp.where(scale_new > 0, scale / safe, 1.0)
        return jnp.clip(jnp.round(c.astype(jnp.float32) * ratio[:, None]),
                        -INT8_QMAX, INT8_QMAX).astype(jnp.int8)

    cache_q = jax.lax.cond(jnp.any(scale_new > scale), _requant,
                           lambda c: c, cache_q)
    q_new = quantize_kv(newf, scale_new[:, None])
    return jax.lax.dynamic_update_slice_in_dim(cache_q, q_new, start, 1), \
        scale_new


def quantize_kv_tree(cache: PyTree, prompt_len: jax.Array | None = None
                     ) -> PyTree:
    """Quantize a full-precision stream cache into the int8 pool layout.

    Walks the cache pytree and replaces every GQA KV dict ``{"k","v"}``
    (leaves ``(..., S, KH, D)``, sequence axis -3) and every MLA latent
    dict ``{"ckv","krope"}`` (leaves ``(..., S, r)``, sequence axis -2)
    with the quantized ``*_q``/``*_scale`` layout — works on both
    per-layer and stacked ``(L, B, S, ...)`` caches; non-KV state
    passes through untouched.  ``prompt_len`` masks positions
    ``>= prompt_len`` (the right-padded prefill tail) out of both the
    values and the absmax scale reduction, so the result is
    bit-identical to the quantize-on-insert whole-prefill path.

    The chunked-prefill scheduler stages an in-flight prompt at full
    precision (chunk attention over the exact K/V prefix, so chunked
    greedy == whole-prefill greedy) and calls this once at slot insert
    — the stacked-cache one-shot twin of :func:`quantize_kv_prefill`.
    """
    def one(x, seq_axis):
        xf = x.astype(jnp.float32)
        if prompt_len is not None:
            s = x.shape[seq_axis]
            mask = (jnp.arange(s) < prompt_len).reshape(
                (s,) + (1,) * (-seq_axis - 1))
            xf = jnp.where(mask, xf, 0.0)
        scale = _finite_scale(jnp.max(jnp.abs(xf), axis=seq_axis)
                              / INT8_QMAX)
        sc = jnp.expand_dims(scale, seq_axis)
        safe = jnp.where(sc > 0, sc, 1.0)
        q = jnp.round(xf / safe)
        q = jnp.where(jnp.isfinite(q),
                      jnp.clip(q, -INT8_QMAX, INT8_QMAX), 0.0)
        return q.astype(jnp.int8), scale

    def pair(t, names, seq_axis):
        out = {}
        for name in names:
            q, scale = one(t[name], seq_axis)
            out[name + "_q"] = q
            out[name + "_scale"] = scale
        return out

    def rec(t):
        if isinstance(t, dict):
            if set(t) == {"k", "v"}:
                return pair(t, ("k", "v"), -3)
            if set(t) == {"ckv", "krope"}:
                return pair(t, ("ckv", "krope"), -2)
            return {key: rec(v) for key, v in t.items()}
        return t

    return rec(cache)


def kv_write_token(cache_q: jax.Array, scale: jax.Array, new: jax.Array,
                   pos: jax.Array, layer: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Insert one decoded token's K (or V) into an int8 cache pool.

    ``cache_q (B, S, KH, D)`` int8; ``scale (B, KH, D)`` f32;
    ``new (B, KH, D)``; ``pos (B,)`` per-slot write positions.
    Returns ``(cache_q', scale')``.  Given ``layer``, ``cache_q`` and
    ``scale`` are stacked ``(L, ...)`` along the layer scan and only
    layer ``layer``'s rows are read and written (requant included).

    The scale is a per-channel running max: ``scale' = max(scale,
    |new| / 127)``.  Where it grew, the slot's history is requantized at
    the larger scale (``round(q * scale/scale')``); where it did not,
    the ratio is exactly 1 and the rescale is a bit-exact no-op — so the
    whole O(S) history pass runs under a ``lax.cond`` and is skipped
    entirely unless some channel's max actually grew (rare once a slot
    is warm).  The steady-state write stays O(1) like the f32 scatter:
    one token row, not a full pool read-modify-write per step.
    """
    newf = new.astype(jnp.float32)
    old = layer_of(scale, layer)
    scale_new = jnp.maximum(old, _finite_scale(jnp.abs(newf) / INT8_QMAX))

    def _requant(c):
        safe = jnp.where(scale_new > 0, scale_new, 1.0)
        ratio = jnp.where(scale_new > 0, old / safe, 1.0)
        hist = layer_of(c, layer).astype(jnp.float32) * ratio[:, None]
        hist = jnp.clip(jnp.round(hist), -INT8_QMAX,
                        INT8_QMAX).astype(jnp.int8)
        return with_layer(c, hist, layer)

    cache_q = jax.lax.cond(jnp.any(scale_new > old), _requant,
                           lambda c: c, cache_q)
    q_new = quantize_kv(newf, scale_new)
    bidx = jnp.arange(new.shape[0])
    return (cache_q.at[layer_index(layer, bidx, pos)].set(q_new),
            with_layer(scale, scale_new, layer))


# ---------------------------------------------------------------------------
# Accounting (cost model)
# ---------------------------------------------------------------------------

def kv_bytes_per_step(slots: int, seq_len: int, num_kv_heads: int,
                      head_dim: int, *, quantize: str | None = None,
                      dtype_bytes: int = 4) -> int:
    """HBM bytes one layer's K+V pool streams per decode step.

    Decode attention reads every slot's full cache (invalid positions
    are masked, not skipped), so the per-step read is the whole pool:
    values at 1 byte/elt for int8 (plus the f32 scale rows) vs
    ``dtype_bytes`` for the unquantized pool.

    Analytic GQA convenience only — the serve pool and roofline derive
    their numbers from :meth:`repro.layers.cache.CachePlan.
    bytes_per_step` (which covers the MLA latent families too); the
    plan-contract tests cross-check the two.
    """
    n = slots * seq_len * num_kv_heads * head_dim
    if quantize in (None, "none"):
        return 2 * n * dtype_bytes
    _check_mode(quantize)
    return 2 * n + 2 * slots * num_kv_heads * head_dim * 4
