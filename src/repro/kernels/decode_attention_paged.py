"""Fused block-table decode attention over a paged KV pool.

The paged serve pool (:mod:`repro.serve.paging`) stores K/V as
fixed-size physical blocks ``(num_blocks + 1, block_size, KH, D)`` and
addresses them per stream through an int32 block table — so decode
cannot stream a contiguous ``(slots, S_max, ...)`` region; each
stream's logical sequence is scattered across the pool.  A
gather-then-attend fallback materializes every stream's contiguous
copy in HBM each step, handing back exactly the bytes paging saved.
This kernel keeps the indirection in the *index maps*:

* the block table and ``cache_pos`` ride in as **scalar-prefetch**
  operands (:class:`pltpu.PrefetchScalarGridSpec`) — the k/v BlockSpec
  index maps read ``bt[slot, blk]`` to aim each grid step's DMA at the
  right physical block, so K/V tiles stream straight from their paged
  homes into VMEM, one block per sequence step;
* idle table entries alias the reserved dummy block (physical id
  ``num_blocks``); the ``pos <= cache_pos`` validity mask kills their
  logits, so the garbage they hold never reaches the softmax;
* online softmax over the logical block sequence: f32 running max /
  sum / accumulator in VMEM scratch across the arbitrary grid dim,
  identical discipline to :mod:`repro.kernels.decode_attention_q`.

``decode_attention_paged_q`` is the int8 twin.  Scales are PER BLOCK
(``(num_blocks + 1, KH, D)`` f32 — blocked together with their values,
so a copy-on-write shared prefix block travels with its own scales).
Per-block K scales fold into the query row exactly as the slot kernel
folds per-slot scales; per-block V scales can no longer fold into the
final output (they change block to block), so each block's context
contribution is scaled before accumulation — O(G*D) multiplies per
block in place of O(bs*D) dequantization.

Grid: ``(B_slots, blocks_per_slot)`` with the block dim innermost
(arbitrary); slots are parallel.  A K/V tile spans the full ``(KH, D)``
minor dims (tiling-legal for every head count) and the program loops
over the KV heads inside.  The GQA group of G = H/KH query heads rides
as rows of the q/out tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import decode_attention_q as dak
from repro.kernels import tpu
from repro.kernels.decode_attention_q import (_MINOR, block_logits,
                                              init_scratch,
                                              online_softmax_step)


def _kernel(bt_ref, cp_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, softcap):
    """q (1,KH,G,D); k/v (1,bs,KH,D) — the physical block the index map
    aimed at; bt (B,nblk) / cache_pos (B,1) i32 SMEM (scalar prefetch);
    o (1,KH,G,D); scratch acc (KH,G,D), m/l (KH,G,128) f32."""
    b = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    bs, kh = k_ref.shape[1], k_ref.shape[2]

    @pl.when(si == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    limit = cp_ref[b, 0]
    for h in range(kh):
        s = block_logits(q_ref[0, h].astype(jnp.float32),
                         k_ref[0, :, h, :].astype(jnp.float32), None,
                         scale, softcap, pos, limit)
        online_softmax_step(s, v_ref[0, :, h, :].astype(jnp.float32), h,
                            acc_ref, m_ref, l_ref)

    @pl.when(si == ns - 1)
    def _flush():
        for h in range(kh):
            o_ref[0, h] = (acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)


def _kernel_q(bt_ref, cp_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, o_ref,
              acc_ref, m_ref, l_ref, *, scale, softcap):
    """Int8 twin: k_q/v_q (1,bs,KH,D) int8 + PER-BLOCK k/v_scale (1,KH,D)
    f32 tiles follow the same block-table index maps.  K scales fold
    into the query row per block; V scales multiply each block's
    context contribution before accumulation."""
    b = pl.program_id(0)
    si = pl.program_id(1)
    ns = pl.num_programs(1)
    bs, kh = kq_ref.shape[1], kq_ref.shape[2]

    @pl.when(si == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    limit = cp_ref[b, 0]
    for h in range(kh):
        s = block_logits(q_ref[0, h].astype(jnp.float32),
                         kq_ref[0, :, h, :].astype(jnp.float32),
                         ks_ref[0, h].astype(jnp.float32), scale,
                         softcap, pos, limit)
        online_softmax_step(s, vq_ref[0, :, h, :].astype(jnp.float32), h,
                            acc_ref, m_ref, l_ref,
                            v_scale=vs_ref[0, h].astype(jnp.float32))

    @pl.when(si == ns - 1)
    def _flush():
        for h in range(kh):
            o_ref[0, h] = (acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)


def _call(kernel, q, kv, block_tables, cache_pos, *, softcap, interpret,
          scales=None):
    """Shared launch of both paged kernels.  ``kv`` = (k, v) tiles,
    ``scales`` = per-block (k_scale, v_scale) for the int8 pool."""
    b, kh, g, d = q.shape
    k, v = kv
    _, bs, kh2, d2 = k.shape
    assert (kh, d) == (kh2, d2), (q.shape, k.shape)
    assert k.shape == v.shape
    nblk = block_tables.shape[1]
    assert block_tables.shape == (b, nblk), block_tables.shape
    assert cache_pos.shape == (b, 1), cache_pos.shape

    qo = pl.BlockSpec((1, kh, g, d), lambda i, s, bt, cp: (i, 0, 0, 0))
    kvs = pl.BlockSpec((1, bs, kh, d),
                       lambda i, s, bt, cp: (bt[i, s], 0, 0, 0))
    if scales is None:
        in_specs, operands = [qo, kvs, kvs], (q, k, v)
    else:
        ks, vs = scales
        assert ks.shape == vs.shape == (k.shape[0], kh, d), \
            (ks.shape, vs.shape)
        sc = pl.BlockSpec((1, kh, d), lambda i, s, bt, cp: (bt[i, s], 0, 0))
        in_specs, operands = [qo, kvs, sc, kvs, sc], (q, k, ks, v, vs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblk),
        in_specs=in_specs,
        out_specs=qo,
        scratch_shapes=[pltpu.VMEM((kh, g, d), jnp.float32),
                        pltpu.VMEM((kh, g, _MINOR), jnp.float32),
                        pltpu.VMEM((kh, g, _MINOR), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=1.0 / (d ** 0.5), softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(block_tables, cache_pos, *operands)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def decode_attention_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                           block_tables: jax.Array, cache_pos: jax.Array,
                           *, softcap: float = 0.0,
                           interpret: bool = False) -> jax.Array:
    """Fused decode attention over a full-width paged KV pool.

    q (B, KH, G, D); k/v (NB+1, bs, KH, D) — batch axis = physical
    block, id NB reserved dummy; block_tables (B, nblk) int32;
    cache_pos (B, 1) int32 -> (B, KH, G, D) in q.dtype.  The sequence
    block size IS the pool's block size (no padding: nblk covers
    exactly blocks_per_slot logical blocks).
    """
    return _call(_kernel, q, (k, v), block_tables, cache_pos,
                 softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def decode_attention_paged_q(q: jax.Array, k_q: jax.Array,
                             k_scale: jax.Array, v_q: jax.Array,
                             v_scale: jax.Array, block_tables: jax.Array,
                             cache_pos: jax.Array, *, softcap: float = 0.0,
                             interpret: bool = False) -> jax.Array:
    """Fused decode attention over an int8 paged KV pool.

    q (B, KH, G, D); k_q/v_q (NB+1, bs, KH, D) int8; per-block
    k/v_scale (NB+1, KH, D) f32; block_tables (B, nblk) int32;
    cache_pos (B, 1) int32 -> (B, KH, G, D) in q.dtype.
    """
    return _call(_kernel_q, q, (k_q, v_q), block_tables, cache_pos,
                 softcap=softcap, interpret=interpret,
                 scales=(k_scale, v_scale))


def vmem_bytes(kh: int, g: int, d: int, block_size: int,
               act_bytes: int = 4, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py) —
    same tile inventory as the slot kernel; the scale rows are absent
    from the full-width variant but cost nothing to keep in the bound."""
    return dak.vmem_bytes(kh, g, d, block_size, act_bytes=act_bytes,
                          q_bytes=q_bytes)
