"""Fused *sparse-quantized* branched matmul (paper Eq. 17 chain).

    y = sum_n ((x @ ds(u_n)) @ dq(xc_n)) @ ds(v_n)

Compound-compression variant of :mod:`repro.kernels.branched_matmul_q`
(same ``(M/bm, S/bn, N)`` branch-innermost grid, same branch-sum f32
scratch accumulator): the outer ``u``/``v`` factors arrive per branch
as 2:4-packed int8 values + int8 row-index metadata + f32 scales and
are **expanded and dequantized in VMEM**
(:func:`repro.kernels.lowrank_matmul_sq.expand_tile`); the small
trainable core ``xc`` stays a plain int8 tile (it is excluded from the
default sparse targets — pruning the already-tiny core buys little and
costs accuracy).  Neither a dense nor a dequantized weight ever
round-trips to HBM.

Layout follows :mod:`repro.quant.sparse` with the branch axis leading:
``u_sp (N, 2, C/4, r1)``, ``u_idx (N, 2, C/4, 1)``,
``u_scale (N, 1, r1)``; ``xc_q (N, r1, r2)``, ``xc_scale (N, 1, r2)``;
``v_sp (N, 2, r2/4, S)``, ``v_idx (N, 2, r2/4, 1)``,
``v_scale (N, 1, S)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu
from repro.kernels.lowrank_matmul_sq import expand_tile

DEFAULT_BM = 256
DEFAULT_BN = 256


def _kernel(x_ref, usp_ref, ui_ref, us_ref, xcq_ref, xcs_ref,
            vsp_ref, vi_ref, vs_ref, o_ref, acc_ref):
    """x (bm, C); u pack (1, 2, C/4, r1)+(1, 2, C/4, 1)+(1, 1, r1);
    xc (1, r1, r2)+(1, 1, r2); v pack (1, 2, r2/4, bn)+(1, 2, r2/4, 1)
    +(1, 1, bn); o (bm, bn); acc (bm, bn) f32 scratch."""
    n = pl.program_id(2)
    n_total = pl.num_programs(2)

    u = expand_tile(usp_ref[0], ui_ref[0], us_ref[0], x_ref.dtype)
    xc = (xcq_ref[0].astype(jnp.float32) * xcs_ref[0]).astype(x_ref.dtype)
    v = expand_tile(vsp_ref[0], vi_ref[0], vs_ref[0], x_ref.dtype)

    h1 = jnp.dot(x_ref[...], u,
                 preferred_element_type=jnp.float32).astype(x_ref.dtype)
    h2 = jnp.dot(h1, xc,
                 preferred_element_type=jnp.float32).astype(x_ref.dtype)
    contrib = jnp.dot(h2, v, preferred_element_type=jnp.float32)

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
def branched_matmul_sq(x: jax.Array, u_sp: jax.Array, u_idx: jax.Array,
                       u_scale: jax.Array, xc_q: jax.Array,
                       xc_scale: jax.Array, v_sp: jax.Array,
                       v_idx: jax.Array, v_scale: jax.Array, *,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       interpret: bool = False) -> jax.Array:
    """x (M, C); u_sp (N, 2, C/4, r1); xc_q (N, r1, r2); v_sp
    (N, 2, r2/4, S) + index metadata + per-branch per-output-channel
    scales -> (M, S).  Requires M % bm == 0 and S % bn == 0 (ops.py
    pads), C % 4 == 0 and r2 % 4 == 0 (the packing's group size)."""
    m, c = x.shape
    nb, two, c4, r1 = u_sp.shape
    _, _, r2 = xc_q.shape
    _, _, r24, s = v_sp.shape
    assert two == 2 and c == 4 * c4 and r2 == 4 * r24, \
        (x.shape, u_sp.shape, xc_q.shape, v_sp.shape)
    assert u_idx.shape == (nb, 2, c4, 1) and v_idx.shape == (nb, 2, r24, 1), \
        (u_idx.shape, v_idx.shape)
    assert u_scale.shape == (nb, 1, r1) and xc_scale.shape == (nb, 1, r2) \
        and v_scale.shape == (nb, 1, s), \
        (u_scale.shape, xc_scale.shape, v_scale.shape)
    assert m % bm == 0 and s % bn == 0, (m, s, bm, bn)

    grid = (m // bm, s // bn, nb)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, 2, c4, r1), lambda i, j, k: (k, 0, 0, 0)),
            pl.BlockSpec((1, 2, c4, 1), lambda i, j, k: (k, 0, 0, 0)),
            pl.BlockSpec((1, 1, r1), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, r1, r2), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, 1, r2), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, 2, r24, bn), lambda i, j, k: (k, 0, 0, j)),
            pl.BlockSpec((1, 2, r24, 1), lambda i, j, k: (k, 0, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params(
            "parallel", "parallel", "arbitrary"),
    )(x, u_sp, u_idx, u_scale, xc_q, xc_scale, v_sp, v_idx, v_scale)


def vmem_bytes(m_block: int, c: int, r1: int, r2: int, s_block: int,
               act_bytes: int = 2, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py).

    Counts one branch's packed u/v tiles + the int8 core + index/scale
    metadata, their expanded f32 and activation-width copies, and the
    f32 branch accumulator + out block.
    """
    packed = (c // 2) * r1 + (r2 // 2) * s_block     # kept u/v values
    meta = (c // 2) + (r2 // 2)                      # int8 indices
    expanded = (c * r1 + r1 * r2 + r2 * s_block) * (4 + act_bytes)
    blocks = (m_block * c * act_bytes
              + packed * q_bytes + r1 * r2 * q_bytes + meta
              + (r1 + r2 + s_block) * 4
              + m_block * s_block * act_bytes)        # out block
    return (tpu.BUFFERS * blocks + expanded
            + m_block * s_block * (act_bytes + 2 * 4))  # acc + contrib
