"""Fused *quantized* low-rank matmul: y = (x @ dq(w0)) @ dq(w1).

Weight-only quantized variant of :mod:`repro.kernels.lowrank_matmul`
(same grid, same scratch-accumulator design): the factor tiles arrive in
VMEM as int8 (or fp8) values plus f32 per-channel scales, are
dequantized *in VMEM* right before the MXU dot, and the rank-bottleneck
intermediate ``h = x @ dq(w0)`` lives in the f32 scratch accumulator —
it never round-trips to HBM, and neither does any dequantized weight.

Why it's a serving win on top of the bf16 fused kernel: decode is
memory-bound on weight streaming, and int8 factors move **half the
bytes** per step (1 byte/elem vs 2, + a negligible ``R + S`` f32 scale
row).  Combined with the rank reduction itself the weight bytes per
token drop by ``2 * alpha`` vs the dense bf16 layer.

Scales follow :mod:`repro.quant.quantize`: ``w0_scale (1, R)``,
``w1_scale (1, S)`` — one f32 scale per output channel, broadcast over
the tile's input axis at dequant.
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


def _kernel(x_ref, w0q_ref, w0s_ref, w1q_ref, w1s_ref, o_ref, h_ref):
    """x (bm, C); w0_q (C, R) + w0_scale (1, R); w1_q (R, bn) +
    w1_scale (1, bn); o (bm, bn); scratch h (bm, R) f32."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _compute_h():
        w0 = (w0q_ref[...].astype(jnp.float32) * w0s_ref[...]
              ).astype(x_ref.dtype)
        h_ref[...] = jnp.dot(x_ref[...], w0,
                             preferred_element_type=jnp.float32)

    w1 = (w1q_ref[...].astype(jnp.float32) * w1s_ref[...]
          ).astype(x_ref.dtype)
    h = h_ref[...].astype(x_ref.dtype)
    o_ref[...] = jnp.dot(h, w1,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret"))
def lowrank_matmul_q(x: jax.Array, w0_q: jax.Array, w0_scale: jax.Array,
                     w1_q: jax.Array, w1_scale: jax.Array, *,
                     bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                     interpret: bool = False) -> jax.Array:
    """y = (x @ (w0_q*w0_scale)) @ (w1_q*w1_scale), fused.

    x (M,C); w0_q (C,R); w0_scale (1,R); w1_q (R,S); w1_scale (1,S)
    -> (M,S).  Requires M % bm == 0 and S % bn == 0 (ops.py pads).
    """
    m, c = x.shape
    c2, r = w0_q.shape
    r2, s = w1_q.shape
    assert c == c2 and r == r2, (x.shape, w0_q.shape, w1_q.shape)
    assert w0_scale.shape == (1, r) and w1_scale.shape == (1, s), \
        (w0_scale.shape, w1_scale.shape)
    assert m % bm == 0 and s % bn == 0, (m, s, bm, bn)

    grid = (m // bm, s // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda i, j: (i, 0)),
            pl.BlockSpec((c, r), lambda i, j: (0, 0)),
            pl.BlockSpec((1, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(x, w0_q, w0_scale, w1_q, w1_scale)


def vmem_bytes(m_block: int, c: int, r: int, s_block: int,
               act_bytes: int = 2, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py)."""
    blocks = (m_block * c * act_bytes         # x block
              + c * r * q_bytes               # w0_q (resident)
              + r * 4                         # w0_scale
              + r * s_block * q_bytes         # w1_q block
              + s_block * 4                   # w1_scale block
              + m_block * s_block * act_bytes)  # out block
    return (tpu.BUFFERS * blocks
            + m_block * r * 4                 # f32 scratch h
            + r * s_block * (4 + act_bytes))  # dequantized w1 tile
