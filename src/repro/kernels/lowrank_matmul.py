"""Fused low-rank matmul Pallas kernel: y = (x @ w0) @ w1.

The whole point of the kernel (DESIGN.md §3): the rank-bottleneck
intermediate ``h = x @ w0`` ( M x R ) stays in a VMEM scratch accumulator
and **never round-trips to HBM**.  XLA on its own materializes ``h``
between the two dots; at training token counts (M ~ 1e6, R ~ 512, bf16)
that is ~1 GB of avoidable HBM traffic per decomposed layer per step —
the TPU analogue of the paper's "more layers = more latency" complaint.

Grid: ``(M/bm, S/bn)`` with j innermost.  At ``j == 0`` the kernel
computes ``h_i = x_i @ w0`` (full C and R resident in VMEM) into scratch;
every j-step then computes ``y_ij = h_i @ w1_j`` on the MXU.  Both
matmuls accumulate in f32.

Block shapes are MXU-aligned (multiples of 128 lanes / 8 sublanes) —
which is exactly why the paper's §2.1 rank alignment matters: an
unaligned R pads w0/w1 tiles with zeros and burns MXU cycles.
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


def _kernel(x_ref, w0_ref, w1_ref, o_ref, h_ref):
    """x (bm, C); w0 (C, R); w1 (R, bn); o (bm, bn); scratch h (bm, R) f32."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _compute_h():
        h_ref[...] = jnp.dot(x_ref[...], w0_ref[...],
                             preferred_element_type=jnp.float32)

    h = h_ref[...].astype(x_ref.dtype)
    o_ref[...] = jnp.dot(h, w1_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret"))
def lowrank_matmul(x: jax.Array, w0: jax.Array, w1: jax.Array, *,
                   bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   interpret: bool = False) -> jax.Array:
    """y = (x @ w0) @ w1, fused. x (M,C); w0 (C,R); w1 (R,S) -> (M,S).

    Requires M % bm == 0 and S % bn == 0 (ops.py pads & dispatches).
    """
    m, c = x.shape
    c2, r = w0.shape
    r2, s = w1.shape
    assert c == c2 and r == r2, (x.shape, w0.shape, w1.shape)
    assert m % bm == 0 and s % bn == 0, (m, s, bm, bn)

    grid = (m // bm, s // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, c), lambda i, j: (i, 0)),
            pl.BlockSpec((c, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, s), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
        compiler_params=tpu.compiler_params("parallel", "arbitrary"),
    )(x, w0, w1)


def vmem_bytes(m_block: int, c: int, r: int, s_block: int,
               dtype_bytes: int = 2) -> int:
    """VMEM footprint of one grid step (fit check used by ops.py)."""
    blocks = (m_block * c * dtype_bytes        # x block
              + c * r * dtype_bytes            # w0 (resident)
              + r * s_block * dtype_bytes      # w1 block
              + m_block * s_block * dtype_bytes)  # out block
    return tpu.BUFFERS * blocks + m_block * r * 4   # + f32 scratch h
