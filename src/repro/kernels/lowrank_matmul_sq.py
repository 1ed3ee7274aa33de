"""Fused *sparse-quantized* low-rank matmul: y = (x @ ds(w0)) @ ds(w1).

Compound-compression variant of :mod:`repro.kernels.lowrank_matmul_q`
(same grid, same f32 rank scratch): each factor arrives in VMEM as
2:4-packed int8 values (slot-major ``(2, C/4, R)``) plus int8 row-index
metadata ``(2, C/4, 1)`` and f32 per-output-channel scales, is
**expanded and dequantized in VMEM** right before the MXU dot, and the
rank intermediate ``h = x @ ds(w0)`` lives in f32 scratch — neither a
dense nor a dequantized weight ever touches HBM.

Why it compounds: decode is memory-bound on weight streaming, and the
2:4 packing halves the *int8* bytes again — ``0.5·C·R`` values +
``C/2`` index bytes + ``4R`` scale bytes vs ``C·R + 4R`` for int8-only
(~1.9-2x fewer at production sizes, ~4x vs bf16, ~8x vs f32), on top of
the rank reduction itself.

The in-VMEM expand is pure VPU work, no gathers: the slot-major packing
makes ``sp_ref[i]`` a contiguous ``(C/4, N)`` tile; each of the two
kept slots is broadcast 4x along the sublane axis (``jnp.repeat``) and
masked against a ``row % 4`` iota compared with the (also repeated)
index column — two multiply-adds reconstruct the dense ``(C, N)`` tile
with pruned rows as exact zeros.  An expansion-*matmul* formulation
(``E^T @ sp``) was rejected: it costs ``C²R/2`` MXU FLOPs per tile,
catastrophic at decode block sizes.

Layout follows :mod:`repro.quant.sparse`: ``w0_sp (2, C/4, R)``,
``w0_idx (2, C/4, 1)``, ``w0_scale (1, R)``; ``w1_sp (2, R/4, S)``,
``w1_idx (2, R/4, 1)``, ``w1_scale (1, S)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu

DEFAULT_BM = 256
DEFAULT_BN = 256


def expand_tile(sp, idx, scale, out_dtype):
    """Dense ``(4G, N)`` tile from a slot-major 2:4 pack, in VMEM.

    ``sp (2, G, N)`` packed values; ``idx (2, G, 1)`` int8 within-group
    row positions; ``scale (1, N)`` f32 (pass 1.0 for unquantized).
    Row ``4g + j`` gets slot ``i``'s value iff ``idx[i, g] == j`` —
    pruned rows stay exactly zero.
    """
    g, n = sp.shape[1], sp.shape[2]
    pos = jax.lax.broadcasted_iota(jnp.int32, (4 * g, 1), 0) % 4
    dense = jnp.zeros((4 * g, n), jnp.float32)
    for i in range(sp.shape[0]):
        vals = jnp.repeat(sp[i].astype(jnp.float32), 4, axis=0)
        sel = jnp.repeat(idx[i].astype(jnp.int32), 4, axis=0)
        dense = dense + jnp.where(sel == pos, vals, 0.0)
    return (dense * scale).astype(out_dtype)


def _kernel(x_ref, w0sp_ref, w0i_ref, w0s_ref, w1sp_ref, w1i_ref, w1s_ref,
            o_ref, h_ref):
    """x (bm, C); w0 pack (2, C/4, R)+(2, C/4, 1)+(1, R); w1 pack
    (2, R/4, bn)+(2, R/4, 1)+(1, bn); o (bm, bn); scratch h (bm, R)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _compute_h():
        w0 = expand_tile(w0sp_ref[...], w0i_ref[...], w0s_ref[...],
                         x_ref.dtype)
        h_ref[...] = jnp.dot(x_ref[...], w0,
                             preferred_element_type=jnp.float32)

    w1 = expand_tile(w1sp_ref[...], w1i_ref[...], w1s_ref[...], x_ref.dtype)
    h = h_ref[...].astype(x_ref.dtype)
    o_ref[...] = jnp.dot(h, w1,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def lowrank_matmul_sq(x: jax.Array, w0_sp: jax.Array, w0_idx: jax.Array,
                      w0_scale: jax.Array, w1_sp: jax.Array,
                      w1_idx: jax.Array, w1_scale: jax.Array, *,
                      bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      interpret: bool = False) -> jax.Array:
    """y = (x @ ds(w0)) @ ds(w1), fused sparse-int8 chain.

    x (M, C); w0_sp (2, C/4, R) + w0_idx (2, C/4, 1) + w0_scale (1, R);
    w1_sp (2, R/4, S) + w1_idx (2, R/4, 1) + w1_scale (1, S) -> (M, S).
    Requires M % bm == 0 and S % bn == 0 (ops.py pads), C % 4 == 0 and
    R % 4 == 0 (the packing's group size).
    """
    m, c = x.shape
    two, c4, r = w0_sp.shape
    _, r4, s = w1_sp.shape
    assert two == 2 and c == 4 * c4 and r == 4 * r4, \
        (x.shape, w0_sp.shape, w1_sp.shape)
    assert w0_idx.shape == (2, c4, 1) and w1_idx.shape == (2, r4, 1), \
        (w0_idx.shape, w1_idx.shape)
    assert w0_scale.shape == (1, r) and w1_scale.shape == (1, s), \
        (w0_scale.shape, w1_scale.shape)
    assert m % bm == 0 and s % bn == 0, (m, s, bm, bn)

    grid = (m // bm, s // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda i, j: (i, 0)),
            pl.BlockSpec((2, c4, r), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((2, c4, 1), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, r), lambda i, j: (0, 0)),
            pl.BlockSpec((2, r4, bn), lambda i, j: (0, 0, j)),
            pl.BlockSpec((2, r4, 1), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(x, w0_sp, w0_idx, w0_scale, w1_sp, w1_idx, w1_scale)


def vmem_bytes(m_block: int, c: int, r: int, s_block: int,
               act_bytes: int = 2, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py).

    Counts the packed tiles + index/scale metadata, the expanded f32
    and activation-width dense copies, and the f32 rank scratch.
    """
    packed = (c // 2) * r + (r // 2) * s_block       # kept values
    meta = (c // 2) + (r // 2)                       # int8 indices
    expanded = (c * r + r * s_block) * (4 + act_bytes)
    blocks = (m_block * c * act_bytes                # x block
              + packed * q_bytes + meta
              + (r + s_block) * 4                    # f32 scales
              + m_block * s_block * act_bytes)       # out block
    return (tpu.BUFFERS * blocks + expanded
            + m_block * r * 4)                       # f32 scratch h
