"""Fused int8-KV decode attention: one query row vs a quantized cache.

The decode step is the roofline's memory corner: each new token streams
the entire KV pool ``(slots, S_max, KV_heads, head_dim)`` through the
core just to attend one query.  Quantizing the pool
(:mod:`repro.quant.kv`) shrinks those bytes 4x vs f32 — but only if the
attention read consumes int8 *directly*.  A dequantize-then-attend
fallback materializes a full-precision pool copy in HBM every step and
hands the win straight back.  This kernel keeps the narrow bytes all
the way into VMEM:

* int8 K/V tiles stream in per ``(slot, sequence block)`` program,
  all KV heads at once;
* per-(slot, head, channel) scales (:mod:`repro.quant.kv` layout) fold
  into the *query* row for K (``(q * k_scale) @ k_q^T == q @ dq(k)^T``)
  and into the final output for V (``(p @ v_q) * v_scale``) — O(D)
  multiplies replace O(S*D) dequantization work;
* online softmax over sequence blocks: f32 running max / sum /
  accumulator live in VMEM scratch across the arbitrary grid dim, so
  logits for the full S_max never materialize;
* per-slot validity is masked from ``cache_pos`` (position ``p`` is
  live iff ``p <= cache_pos[slot]`` — the slot's freshly written token
  included), which also neutralizes the S padding ``ops.py`` adds.

Grid: ``(B, S/bs)`` with the sequence dim innermost (arbitrary); slots
are parallel.  A K/V block spans the full ``(KH, D)`` minor dims, the
only block shape the TPU tiling accepts for every head count, and the
program loops over the KV heads inside.  The GQA group of G = H/KH
query heads rides along as rows of the q/out tiles, so one pass over a
K/V tile serves the whole group.

``decode_attention_latent_q`` is the MLA twin: the absorbed decode form
attends latent-space queries against an int8 *latent* pool
(``ckv_q (B, S, r)`` + ``krope_q (B, S, rope)``, per-(slot, channel)
f32 scales — no head axis, every head shares the one latent stream).
The ckv/krope scales fold into the two latent query rows for the
logits, and the ckv scales into the context output (the "V" of latent
attention is the ckv stream again), so the int8 latents are consumed
directly — same online-softmax scratch discipline, grid ``(B, S/bs)``
with all H heads riding as tile rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu

DEFAULT_BS = 128
_NEG_INF = -1e30
_MINOR = 128        # f32 scratch lane width for the (G, 1) running stats


def online_softmax_step(s, v, h, acc_ref, m_ref, l_ref, v_scale=None):
    """Fold one sequence block of KV head ``h`` into the running
    softmax: ``s`` (G, bs) masked f32 logits, ``v`` (bs, D) values,
    optional per-channel ``v_scale`` (D,) applied to this block's
    context.  Scratch acc (KH, G, D), m/l (KH, G, 128) f32 (col 0
    live, broadcast across lanes)."""
    m_prev = m_ref[h][:, :1]                                # (G, 1)
    l_prev = l_ref[h][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # (G, bs)
    pv = jnp.dot(p, v, preferred_element_type=jnp.float32)  # (G, D)
    if v_scale is not None:
        pv = pv * v_scale[None, :]
    acc_ref[h] = acc_ref[h] * alpha + pv
    m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
    l_ref[h] = jnp.broadcast_to(l_prev * alpha
                                + jnp.sum(p, axis=-1, keepdims=True),
                                l_ref.shape[1:])


def init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def block_logits(q, k, k_scale, scale, softcap, pos, limit):
    """Masked (G, bs) f32 logits of one KV head over one block: the
    K scales (when quantized) and 1/sqrt(D) fold into the query rows;
    position ``p`` is live iff ``p <= limit``."""
    qs = q * (scale if k_scale is None else (k_scale * scale)[None, :])
    s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    return jnp.where(pos <= limit, s, _NEG_INF)


def _kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, cp_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, softcap):
    """q (1,KH,G,D); k_q/v_q (1,bs,KH,D) int8; k/v_scale (1,KH,D) f32;
    cache_pos (B,1) i32 SMEM; o (1,KH,G,D); scratch acc (KH,G,D),
    m/l (KH,G,128) f32.  The KV heads loop inside the program, so the
    K/V block spans the full (KH, D) minor dims (tiling-legal for any
    head count)."""
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    bs, kh = kq_ref.shape[1], kq_ref.shape[2]

    @pl.when(si == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    limit = cp_ref[pl.program_id(0), 0]
    for h in range(kh):
        s = block_logits(q_ref[0, h].astype(jnp.float32),
                         kq_ref[0, :, h, :].astype(jnp.float32),
                         ks_ref[0, h].astype(jnp.float32), scale,
                         softcap, pos, limit)
        online_softmax_step(s, vq_ref[0, :, h, :].astype(jnp.float32), h,
                            acc_ref, m_ref, l_ref)

    @pl.when(si == ns - 1)
    def _flush():
        for h in range(kh):
            vs = vs_ref[0, h].astype(jnp.float32)           # (D,)
            # V scales fold into the output
            o = acc_ref[h] / l_ref[h][:, :1] * vs[None, :]
            o_ref[0, h] = o.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bs", "softcap", "interpret"))
def decode_attention_q(q: jax.Array, k_q: jax.Array, k_scale: jax.Array,
                       v_q: jax.Array, v_scale: jax.Array,
                       cache_pos: jax.Array, *, bs: int = DEFAULT_BS,
                       softcap: float = 0.0,
                       interpret: bool = False) -> jax.Array:
    """Fused decode attention over an int8 KV pool.

    q (B, KH, G, D); k_q/v_q (B, S, KH, D) int8; k/v_scale (B, KH, D)
    f32; cache_pos (B, 1) int32 -> (B, KH, G, D) in q.dtype.
    Requires S % bs == 0 (ops.py pads; padded positions mask out).
    """
    b, kh, g, d = q.shape
    _, s, kh2, d2 = k_q.shape
    assert (kh, d) == (kh2, d2), (q.shape, k_q.shape)
    assert k_q.shape == v_q.shape
    assert k_scale.shape == v_scale.shape == (b, kh, d), \
        (k_scale.shape, v_scale.shape)
    assert cache_pos.shape == (b, 1), cache_pos.shape
    assert s % bs == 0, (s, bs)

    grid = (b, s // bs)
    kernel = functools.partial(_kernel, scale=1.0 / (d ** 0.5),
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, kh, g, d), lambda i, k: (i, 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, d), lambda i, k: (i, k, 0, 0)),
            pl.BlockSpec((1, kh, d), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((1, bs, kh, d), lambda i, k: (i, k, 0, 0)),
            pl.BlockSpec((1, kh, d), lambda i, k: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # whole (B, 1)
        ],
        out_specs=pl.BlockSpec((1, kh, g, d), lambda i, k: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((kh, g, d), jnp.float32),
                        pltpu.VMEM((kh, g, _MINOR), jnp.float32),
                        pltpu.VMEM((kh, g, _MINOR), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(q, k_q, k_scale, v_q, v_scale, cache_pos)


def vmem_bytes(kh: int, g: int, d: int, s_block: int, act_bytes: int = 4,
               q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py)."""
    blocks = (kh * g * d * act_bytes          # q tile
              + 2 * s_block * kh * d * q_bytes   # k_q + v_q tiles
              + 2 * kh * d * 4                # k/v scale rows
              + kh * g * d * act_bytes)       # out tile
    return (tpu.BUFFERS * blocks
            + kh * g * d * 4                  # f32 accumulator
            + 2 * kh * g * _MINOR * 4)        # running max / sum


# ---------------------------------------------------------------------------
# MLA latent variant: absorbed decode over an int8 latent pool
# ---------------------------------------------------------------------------

def _latent_kernel(ql_ref, qr_ref, cq_ref, cs_ref, rq_ref, rs_ref, cp_ref,
                   o_ref, acc_ref, m_ref, l_ref, *, scale):
    """q_lat (1,H,L); q_rope (1,H,R); ckv_q (1,bs,L) / krope_q (1,bs,R)
    int8; ckv/krope_scale (1,1,L)/(1,1,R) f32; cache_pos (B,1) i32 SMEM;
    o (1,H,L); scratch acc (H,L), m/l (H,128) f32 (col 0 live)."""
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    bs = cq_ref.shape[1]

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ql = ql_ref[0].astype(jnp.float32)                      # (H, L)
    qr = qr_ref[0].astype(jnp.float32)                      # (H, R)
    cs = cs_ref[0, 0].astype(jnp.float32)                   # (L,)
    rs = rs_ref[0, 0].astype(jnp.float32)                   # (R,)
    cq = cq_ref[0].astype(jnp.float32)                      # (bs, L)
    rq = rq_ref[0].astype(jnp.float32)                      # (bs, R)
    # Latent + rope scales (and the 1/sqrt(nope+rope) logit scale) fold
    # into the two query rows: (ql * cs) @ cq^T == ql @ dq(ckv)^T.
    s = (jnp.dot(ql * (cs * scale)[None, :], cq.T,
                 preferred_element_type=jnp.float32)
         + jnp.dot(qr * (rs * scale)[None, :], rq.T,
                   preferred_element_type=jnp.float32))     # (H, bs)
    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    s = jnp.where(pos <= cp_ref[pl.program_id(0), 0], s, _NEG_INF)

    m_prev = m_ref[:, :1]                                   # (H, 1)
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                  # (H, bs)
    acc = acc_ref[...] * alpha + jnp.dot(
        p, cq, preferred_element_type=jnp.float32)          # ctx over ckv
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(si == ns - 1)
    def _flush():
        o = acc / l_new * cs[None, :]   # ckv scales fold into the context
        o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bs", "interpret"))
def decode_attention_latent_q(q_lat: jax.Array, q_rope: jax.Array,
                              ckv_q: jax.Array, ckv_scale: jax.Array,
                              krope_q: jax.Array, krope_scale: jax.Array,
                              cache_pos: jax.Array, *, scale: float,
                              bs: int = DEFAULT_BS,
                              interpret: bool = False) -> jax.Array:
    """Fused absorbed-form MLA decode over an int8 latent pool.

    q_lat (B, H, L); q_rope (B, H, R); ckv_q (B, S, L) / krope_q
    (B, S, R) int8; ckv/krope_scale (B, L)/(B, R) f32; cache_pos (B, 1)
    int32 -> context latents (B, H, L) in q_lat.dtype.  ``scale`` is
    the logit scale 1/sqrt(qk_nope + qk_rope).  Requires S % bs == 0
    (ops.py pads; padded positions mask out).
    """
    b, h, lora = q_lat.shape
    rope = q_rope.shape[-1]
    assert q_rope.shape == (b, h, rope), q_rope.shape
    s = ckv_q.shape[1]
    assert ckv_q.shape == (b, s, lora), (ckv_q.shape, q_lat.shape)
    assert krope_q.shape == (b, s, rope), krope_q.shape
    assert ckv_scale.shape == (b, lora), ckv_scale.shape
    assert krope_scale.shape == (b, rope), krope_scale.shape
    assert cache_pos.shape == (b, 1), cache_pos.shape
    assert s % bs == 0, (s, bs)

    grid = (b, s // bs)
    kernel = functools.partial(_latent_kernel, scale=scale)
    # Scale rows go in as (B, 1, C): a (1, 1, C) block is tiling-legal
    # for any B, a (1, C) block over (B, C) only for B == 1.
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, lora), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((1, h, rope), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((1, bs, lora), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, 1, lora), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((1, bs, rope), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, 1, rope), lambda i, k: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # whole (B, 1)
        ],
        out_specs=pl.BlockSpec((1, h, lora), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lora), q_lat.dtype),
        scratch_shapes=[pltpu.VMEM((h, lora), jnp.float32),
                        pltpu.VMEM((h, _MINOR), jnp.float32),
                        pltpu.VMEM((h, _MINOR), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(q_lat, q_rope, ckv_q, ckv_scale[:, None], krope_q,
      krope_scale[:, None], cache_pos)


def vmem_bytes_latent(h: int, lora: int, rope: int, s_block: int,
                      act_bytes: int = 4, q_bytes: int = 1) -> int:
    """VMEM footprint of one latent grid step (fit check for ops.py)."""
    blocks = (h * (lora + rope) * act_bytes   # q_lat + q_rope tiles
              + s_block * (lora + rope) * q_bytes   # ckv_q + krope_q
              + (lora + rope) * 4             # scale rows
              + h * lora * act_bytes)         # out tile
    return (tpu.BUFFERS * blocks
            + h * lora * 4                    # f32 accumulator
            + 2 * h * _MINOR * 4)             # running max / sum
