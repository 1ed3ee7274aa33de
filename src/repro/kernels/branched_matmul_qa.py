"""Fused *activation-quantized* branched matmul: int8 x int8 per branch.

Activation-quantized variant of :mod:`repro.kernels.branched_matmul_q`
(same ``(M/bm, S/bn, N)`` branch-innermost grid, same f32 branch-sum
accumulator): the activation rows quantize once per row-block into an
int8 VMEM scratch (per-token absmax scales, see
:func:`repro.kernels.lowrank_matmul_qa.quantize_rows`), and every
branch's three-stage chain runs int8 x int8 with int32 accumulation —
each rank intermediate is dequantized by its row x channel scale
product and immediately requantized per-row, so no activation tile at
f32 width ever hits the MXU.

Scale folding order per branch: ``x_scale * u_scale`` after stage 1,
``h1_scale * xc_scale`` after stage 2, ``h2_scale * v_scale`` after
stage 3; the f32 branch contributions then sum in the scratch
accumulator exactly like the weight-only kernel.

Padding discipline: per-token scales are row-local, so bucket-padded
all-zero rows quantize to zero rows with scale 0 and contribute exactly
zero to every branch — real rows never see padding in their scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu
from repro.kernels.lowrank_matmul_qa import quantize_rows

DEFAULT_BM = 256
DEFAULT_BN = 256


def _kernel(x_ref, uq_ref, us_ref, xcq_ref, xcs_ref, vq_ref, vs_ref,
            o_ref, acc_ref, xq_ref, xs_ref):
    """x (bm,C); u_q (1,C,r1) + u_scale (1,1,r1); xc_q (1,r1,r2) +
    xc_scale (1,1,r2); v_q (1,r2,bn) + v_scale (1,1,bn); o (bm,bn);
    scratch: acc (bm,bn) f32, xq (bm,C) int8, xs (bm,1) f32."""
    j = pl.program_id(1)
    n = pl.program_id(2)
    n_total = pl.num_programs(2)

    @pl.when((j == 0) & (n == 0))
    def _quantize_x():
        xq_ref[...], xs_ref[...] = quantize_rows(x_ref[...])

    h1 = (jnp.dot(xq_ref[...], uq_ref[0],
                  preferred_element_type=jnp.int32).astype(jnp.float32)
          * xs_ref[...] * us_ref[0])
    h1q, h1s = quantize_rows(h1)
    h2 = (jnp.dot(h1q, xcq_ref[0],
                  preferred_element_type=jnp.int32).astype(jnp.float32)
          * h1s * xcs_ref[0])
    h2q, h2s = quantize_rows(h2)
    contrib = (jnp.dot(h2q, vq_ref[0],
                       preferred_element_type=jnp.int32).astype(jnp.float32)
               * h2s * vs_ref[0])

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(n > 0)
    def _accum():
        acc_ref[...] += contrib

    @pl.when(n == n_total - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def branched_matmul_qa(x: jax.Array, u_q: jax.Array, u_scale: jax.Array,
                       xc_q: jax.Array, xc_scale: jax.Array,
                       v_q: jax.Array, v_scale: jax.Array, *,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       interpret: bool = False) -> jax.Array:
    """x (M,C); u_q (N,C,r1); xc_q (N,r1,r2); v_q (N,r2,S) + per-branch
    per-output-channel scales -> (M,S), all dots int8 x int8.  Requires
    M % bm == 0 and S % bn == 0 (ops.py pads)."""
    m, c = x.shape
    n, c2, r1 = u_q.shape
    _, _, r2 = xc_q.shape
    _, _, s = v_q.shape
    assert c == c2, (x.shape, u_q.shape)
    assert u_scale.shape == (n, 1, r1) and xc_scale.shape == (n, 1, r2) \
        and v_scale.shape == (n, 1, s), \
        (u_scale.shape, xc_scale.shape, v_scale.shape)
    assert m % bm == 0 and s % bn == 0, (m, s, bm, bn)

    grid = (m // bm, s // bn, n)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, c, r1), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, r1), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, r1, r2), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, r2), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, r2, bn), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, c), jnp.int8),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params(
            "parallel", "parallel", "arbitrary"),
    )(x, u_q, u_scale, xc_q, xc_scale, v_q, v_scale)


def vmem_bytes(m_block: int, c: int, r1: int, r2: int, s_block: int,
               act_bytes: int = 2, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py).

    Counts the f32 activation block, the int8 activation scratch + row
    scales, the quantized branch tiles + their channel scales, the
    transient int8/f32 rank intermediates, and the f32 branch
    accumulator + out block.
    """
    blocks = (m_block * c * act_bytes                  # x block
              + (c * r1 + r1 * r2 + r2 * s_block) * q_bytes
              + (r1 + r2 + s_block) * 4                # channel scales
              + m_block * s_block * act_bytes)         # out block
    return (tpu.BUFFERS * blocks
            + m_block * c + m_block * 4                # int8 x scratch + scales
            + m_block * (r1 + r2) * (1 + 4)            # int8+f32 intermediates
            + 2 * m_block * 4                          # h1/h2 row scales
            + m_block * s_block * 2 * 4)               # acc + contrib
