"""Branched (block-diagonal) low-rank matmul Pallas kernel — paper Fig. 4.

Computes ``y = sum_n ((x @ u_n) @ xc_n) @ v_n`` — the grouped-matmul
realization of branched Tucker/SVD on the MXU.  Each branch's chain runs
entirely in VMEM (two rank-bottleneck intermediates never touch HBM) and
the branch sum accumulates into a VMEM f32 accumulator.

Grid: ``(M/bm, S/bn, N)`` with the branch dim innermost (the output block
is revisited across consecutive branch steps — the Pallas reduction
pattern).  Per-branch weights ``u_n (C, r1)``, ``xc_n (r1, r2)``,
``v_n (r2, bn)`` stream through VMEM one branch at a time, which is how
the paper's "N x smaller core" translates into N x smaller *working set*
on TPU.
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


def _kernel(x_ref, u_ref, xc_ref, v_ref, o_ref, acc_ref):
    """x (bm,C); u (1,C,r1); xc (1,r1,r2); v (1,r2,bn); o (bm,bn);
    acc (bm,bn) f32 scratch."""
    n = pl.program_id(2)
    n_total = pl.num_programs(2)

    h1 = jnp.dot(x_ref[...], u_ref[0],
                 preferred_element_type=jnp.float32).astype(x_ref.dtype)
    h2 = jnp.dot(h1, xc_ref[0],
                 preferred_element_type=jnp.float32).astype(x_ref.dtype)
    contrib = jnp.dot(h2, v_ref[0], preferred_element_type=jnp.float32)

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
def branched_matmul(x: jax.Array, u: jax.Array, xc: jax.Array,
                    v: jax.Array, *, bm: int = DEFAULT_BM,
                    bn: int = DEFAULT_BN, interpret: bool = False
                    ) -> jax.Array:
    """x (M,C); u (N,C,r1); xc (N,r1,r2); v (N,r2,S) -> (M,S)."""
    m, c = x.shape
    n, c2, r1 = u.shape
    _, _, r2 = xc.shape
    _, _, s = v.shape
    assert c == c2, (x.shape, u.shape)
    assert m % bm == 0 and s % bn == 0, (m, s, bm, bn)

    grid = (m // bm, s // bn, n)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, c, r1), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, r1, r2), lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((1, r2, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params(
            "parallel", "parallel", "arbitrary"),
    )(x, u, xc, v)


def vmem_bytes(m_block: int, c: int, r1: int, r2: int, s_block: int,
               dtype_bytes: int = 2) -> int:
    blocks = (m_block * c * dtype_bytes
              + c * r1 * dtype_bytes + r1 * r2 * dtype_bytes
              + r2 * s_block * dtype_bytes
              + m_block * s_block * dtype_bytes)      # out block
    return (tpu.BUFFERS * blocks
            + m_block * s_block * (dtype_bytes + 2 * 4))  # acc + contrib