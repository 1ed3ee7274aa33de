"""Fused *activation-quantized* low-rank matmul: int8 x int8 on the MXU.

Activation-quantized variant of :mod:`repro.kernels.lowrank_matmul_q`
(same grid, same once-per-row-block rank intermediate): instead of
dequantizing the int8 factors up to activation width and multiplying in
f32, the activation rows are quantized *on the fly* — per-token (row)
absmax over the contraction axis — so both MXU dots run int8 x int8
with int32 accumulation.  Scales fold into the output exactly once per
dot: ``x_scale (bm,1) * w0_scale (1,R)`` after stage 1, and
``h_scale (bm,1) * w1_scale (1,bn)`` after stage 2.  The rank
intermediate ``h`` is requantized per-row to int8 in VMEM scratch
(int8 values + f32 row scales) so stage 2 also runs at int8 operand
width — no f32 activation tile is ever re-read.

Why prefill cares: prefill is the M-large MXU-bound segment, and the
MXU runs int8 x int8 at ~2x the f32 rate while the activation stream
between the decomposed stages halves.  Decode (M = batch) stays on the
weight-only kernels — its dots are too skinny for the throughput term
to matter and per-row scales over a handful of rows buy nothing.

Padding discipline: per-token scales are **row-local** (absmax over the
row's own C entries), so bucket-padded all-zero rows get scale 0,
quantize to all-zero int8 rows, and contribute exactly zero — real
rows' scales never see padding (the KV pad-masking discipline from the
serve tier, applied to activations).
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

INT8_QMAX = 127.0


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row (per-token) symmetric absmax int8 quantization.

    x (M, K) any float -> (int8 (M, K), f32 scales (M, 1)).  All-zero
    rows get scale 0 with a safe divisor (the convention of
    :func:`repro.quant.quantize.quantize_array`, per-row instead of
    per-channel), so padded rows stay exactly zero.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / INT8_QMAX
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe), -INT8_QMAX, INT8_QMAX)
    return q.astype(jnp.int8), scale


def _kernel(x_ref, w0q_ref, w0s_ref, w1q_ref, w1s_ref, o_ref,
            hq_ref, hs_ref):
    """x (bm, C); w0_q (C, R) + w0_scale (1, R); w1_q (R, bn) +
    w1_scale (1, bn); o (bm, bn); scratch hq (bm, R) int8 +
    h_scale (bm, 1) f32."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _compute_h():
        xq, xs = quantize_rows(x_ref[...])
        acc = jnp.dot(xq, w0q_ref[...],
                      preferred_element_type=jnp.int32)
        h = acc.astype(jnp.float32) * xs * w0s_ref[...]
        hq_ref[...], hs_ref[...] = quantize_rows(h)

    acc = jnp.dot(hq_ref[...], w1q_ref[...],
                  preferred_element_type=jnp.int32)
    o_ref[...] = (acc.astype(jnp.float32) * hs_ref[...] * w1s_ref[...]
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret"))
def lowrank_matmul_qa(x: jax.Array, w0_q: jax.Array, w0_scale: jax.Array,
                      w1_q: jax.Array, w1_scale: jax.Array, *,
                      bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      interpret: bool = False) -> jax.Array:
    """y = dq(q(x) @ w0_q) -> requant -> dq(h_q @ w1_q), all-int8 dots.

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
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.int8),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(x, w0_q, w0_scale, w1_q, w1_scale)


def vmem_bytes(m_block: int, c: int, r: int, s_block: int,
               act_bytes: int = 2, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py).

    Counts the f32 pre-quant activation block plus its transient int8
    copy and row scales, the int8 factor tiles + scale rows, the int8
    rank scratch (+ f32 transient h at requant), and the out block.
    """
    blocks = (m_block * c * act_bytes         # x block
              + c * r * q_bytes               # w0_q (resident)
              + r * 4                         # w0_scale
              + r * s_block * q_bytes         # w1_q block
              + s_block * 4                   # w1_scale block
              + m_block * s_block * act_bytes)  # out block
    return (tpu.BUFFERS * blocks
            + m_block * c                     # int8 x (transient)
            + m_block * 4                     # x row scales
            + m_block * r                     # int8 scratch h
            + m_block * r * 4                 # f32 h at requant (transient)
            + m_block * 4)                    # h row scales
