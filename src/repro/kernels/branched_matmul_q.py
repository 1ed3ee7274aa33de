"""Fused *quantized* branched matmul: y = sum_n ((x @ dq(u_n)) @ dq(xc_n)) @ dq(v_n).

Weight-only quantized variant of :mod:`repro.kernels.branched_matmul`
(same grid, same branch-sum scratch accumulator): each branch's factor
tiles arrive in VMEM as int8 (or fp8) values plus f32 per-output-channel
scales, are dequantized *in VMEM* right before the MXU dots, and both
rank-bottleneck intermediates plus the branch-sum accumulator never
touch HBM.  Before this kernel, quantized branched/Tucker layers
dequantized *outside* the kernel (a full-size bf16 weight materialized
in HBM per step), forfeiting exactly the bandwidth the quantization was
bought for.

Grid: ``(M/bm, S/bn, N)`` with the branch dim innermost — the output
block is revisited across consecutive branch steps (the Pallas reduction
pattern), so per-branch weights stream through VMEM one branch at a
time at int8 width: the paper's "N x smaller core" (Eq. 17) compounds
with the 2x narrower storage into a 2N x smaller working set vs the
dense bf16 layer.

Scales follow :mod:`repro.quant.quantize` (absmax over the input axis,
one f32 scale per output channel, per branch): ``u_scale (N, 1, r1)``,
``xc_scale (N, 1, r2)``, ``v_scale (N, 1, S)``.
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


def _kernel(x_ref, uq_ref, us_ref, xcq_ref, xcs_ref, vq_ref, vs_ref,
            o_ref, acc_ref):
    """x (bm,C); u_q (1,C,r1) + u_scale (1,1,r1); xc_q (1,r1,r2) +
    xc_scale (1,1,r2); v_q (1,r2,bn) + v_scale (1,1,bn); o (bm,bn);
    acc (bm,bn) f32 scratch."""
    n = pl.program_id(2)
    n_total = pl.num_programs(2)

    u = (uq_ref[0].astype(jnp.float32) * us_ref[0]).astype(x_ref.dtype)
    xc = (xcq_ref[0].astype(jnp.float32) * xcs_ref[0]).astype(x_ref.dtype)
    v = (vq_ref[0].astype(jnp.float32) * vs_ref[0]).astype(x_ref.dtype)

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
def branched_matmul_q(x: jax.Array, u_q: jax.Array, u_scale: jax.Array,
                      xc_q: jax.Array, xc_scale: jax.Array,
                      v_q: jax.Array, v_scale: jax.Array, *,
                      bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      interpret: bool = False) -> jax.Array:
    """x (M,C); u_q (N,C,r1); xc_q (N,r1,r2); v_q (N,r2,S) + per-branch
    per-output-channel scales -> (M,S).  Requires M % bm == 0 and
    S % bn == 0 (ops.py pads)."""
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
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params(
            "parallel", "parallel", "arbitrary"),
    )(x, u_q, u_scale, xc_q, xc_scale, v_q, v_scale)


def vmem_bytes(m_block: int, c: int, r1: int, r2: int, s_block: int,
               act_bytes: int = 2, q_bytes: int = 1) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py).

    Counts the quantized branch tiles + scales, their dequantized
    activation-width copies, and the f32 branch accumulator + out block.
    """
    deq = (c * r1 + r1 * r2 + r2 * s_block) * act_bytes
    blocks = (m_block * c * act_bytes
              + (c * r1 + r1 * r2 + r2 * s_block) * q_bytes
              + (r1 + r2 + s_block) * 4
              + m_block * s_block * act_bytes)        # out block
    return (tpu.BUFFERS * blocks + deq
            + m_block * s_block * (act_bytes + 2 * 4))  # acc + contrib
