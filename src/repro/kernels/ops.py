"""Jit'd public wrappers for the Pallas kernels.

Handles:
* leading-batch flattening (``(..., C) -> (M, C)``),
* padding M/S up to tile multiples (and slicing back),
* interpret mode on the CPU backend, compiled everywhere else
  (:func:`repro.kernels.tpu.interpret`),
* VMEM-fit dispatch — :func:`kernel_fits` is the single fit predicate;
  :class:`repro.layers.plan.LinearPlan` consults it for its kernel
  eligibility decision and the wrappers use it as the fallback check for
  direct callers.  Oversize geometries fall back to the jnp reference
  (which XLA fuses reasonably); the kernels cover the production-common
  block sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import branched_matmul as bk
from repro.kernels import branched_matmul_q as bqk
from repro.kernels import branched_matmul_qa as bak
from repro.kernels import branched_matmul_sq as bsk
from repro.kernels import decode_attention_paged as dap
from repro.kernels import decode_attention_q as dak
from repro.kernels import lowrank_matmul as lk
from repro.kernels import lowrank_matmul_q as qk
from repro.kernels import lowrank_matmul_qa as aqk
from repro.kernels import lowrank_matmul_sq as sk
from repro.kernels import ref
from repro.kernels import tpu

#: the budget :func:`kernel_fits` checks against — the same number every
#: kernel passes to the compiler as ``vmem_limit_bytes``
VMEM_BUDGET = tpu.VMEM_LIMIT_BYTES

#: serve-tier fault injection hook (``kernel_gate`` point): when set,
#: :func:`kernel_fits` consults it and a fire forces the jnp reference
#: fallback — exercised at trace/plan time, so the chaos suite proves
#: a kernel rejection degrades throughput, never correctness (the
#: references are bit-exact oracles).  ``None`` when inert.
_FAULT_INJECTOR = None


def set_fault_injector(inj) -> None:
    """Install (or with ``None`` clear) the serve tier's
    :class:`repro.serve.faults.FaultInjector` for the ``kernel_gate``
    injection point."""
    global _FAULT_INJECTOR
    _FAULT_INJECTOR = inj


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _bm_eff(bm: int, m: int) -> int:
    return min(bm, max(8, m))


def kernel_fits(kernel: str, m: int, **geometry) -> bool:
    """Does ``kernel`` run at this geometry?  The one predicate behind
    plan eligibility and the wrappers' fallback dispatch: the VMEM fit
    (:func:`vmem_fits`), unless the serve tier's fault injector rejects
    the kernel (the ``kernel_gate`` point)."""
    if _FAULT_INJECTOR is not None and _FAULT_INJECTOR.fire("kernel_gate"):
        return False               # injected rejection -> jnp fallback
    return vmem_fits(kernel, m, **geometry)


def vmem_fits(kernel: str, m: int, *, c: int, s: int, r: int = 0,
              r1: int = 0, r2: int = 0, kh: int = 1, q_bytes: int = 1,
              bm: int | None = None, bn: int | None = None) -> bool:
    """Does one grid step of ``kernel`` at this geometry fit the VMEM
    budget the compiler is given?  ``bm``/``bn`` default to the kernel's
    own tile sizes; wrappers pass the caller's so the fit check matches
    the launch.  The S-block is the full ``bn`` — the wrappers pad S up
    to a ``bn`` multiple, so the launched block is never narrower."""
    del s  # padded up to a bn multiple at launch
    if kernel == "lowrank":
        return lk.vmem_bytes(_bm_eff(bm or lk.DEFAULT_BM, m), c, r,
                             bn or lk.DEFAULT_BN) <= VMEM_BUDGET
    if kernel == "lowrank_q":
        return qk.vmem_bytes(_bm_eff(bm or qk.DEFAULT_BM, m), c, r,
                             bn or qk.DEFAULT_BN,
                             q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "lowrank_qa":
        return aqk.vmem_bytes(_bm_eff(bm or aqk.DEFAULT_BM, m), c, r,
                              bn or aqk.DEFAULT_BN,
                              q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "lowrank_sq":
        return sk.vmem_bytes(_bm_eff(bm or sk.DEFAULT_BM, m), c, r,
                             bn or sk.DEFAULT_BN,
                             q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "branched":
        return bk.vmem_bytes(_bm_eff(bm or bk.DEFAULT_BM, m), c, r1, r2,
                             bn or bk.DEFAULT_BN) <= VMEM_BUDGET
    if kernel == "branched_q":
        return bqk.vmem_bytes(_bm_eff(bm or bqk.DEFAULT_BM, m), c, r1, r2,
                              bn or bqk.DEFAULT_BN,
                              q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "branched_qa":
        return bak.vmem_bytes(_bm_eff(bm or bak.DEFAULT_BM, m), c, r1, r2,
                              bn or bak.DEFAULT_BN,
                              q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "branched_sq":
        return bsk.vmem_bytes(_bm_eff(bm or bsk.DEFAULT_BM, m), c, r1, r2,
                              bn or bsk.DEFAULT_BN,
                              q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "decode_attn_q":
        # Per-(slot, sequence block) program over all kh KV heads: c =
        # head_dim, r = GQA group size, bn = the sequence block; m (the
        # slot count) is grid-parallel.
        return dak.vmem_bytes(kh, max(1, r), c, bn or dak.DEFAULT_BS,
                              q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "decode_attn_paged":
        # Per-(slot, physical block) program over all kh KV heads: c =
        # head_dim, r = GQA group size, bn = the pool's block size.
        # Same tile inventory as the slot kernel (the f32 variant skips
        # the scale rows, a rounding error in the bound).
        return dap.vmem_bytes(kh, max(1, r), c, bn or 16,
                              q_bytes=q_bytes) <= VMEM_BUDGET
    if kernel == "decode_latent_q":
        # Per-slot program: c = kv_lora_rank, r = head count, r1 = the
        # rope dim; all H heads ride as tile rows of one program.
        return dak.vmem_bytes_latent(max(1, r), c, r1,
                                     bn or dak.DEFAULT_BS,
                                     q_bytes=q_bytes) <= VMEM_BUDGET
    raise ValueError(f"unknown kernel {kernel!r}")


def lowrank_matmul(x: jax.Array, w0: jax.Array, w1: jax.Array, *,
                   bm: int = lk.DEFAULT_BM, bn: int = lk.DEFAULT_BN,
                   force_kernel: bool = False) -> jax.Array:
    """y = (x @ w0) @ w1 with the fused kernel when it fits VMEM."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    r, s = w1.shape
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    if not (force_kernel or kernel_fits("lowrank", m, c=c, r=r, s=s,
                                        bm=bm, bn=bn)):
        return ref.lowrank_matmul_ref(x, w0, w1)
    x2, pad_m = _pad_to(x2, 0, bm_eff)
    w1p, pad_s = _pad_to(w1, 1, bn)
    y = lk.lowrank_matmul(x2, w0, w1p, bm=bm_eff, bn=min(bn, w1p.shape[1]),
                          interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def lowrank_matmul_q(x: jax.Array, w0_q: jax.Array, w0_scale: jax.Array,
                     w1_q: jax.Array, w1_scale: jax.Array, *,
                     bm: int = qk.DEFAULT_BM, bn: int = qk.DEFAULT_BN,
                     force_kernel: bool = False) -> jax.Array:
    """y = (x @ dq(w0)) @ dq(w1) with the fused quantized kernel."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    r, s = w1_q.shape
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    q_bytes = jnp.dtype(w0_q.dtype).itemsize
    if not (force_kernel or kernel_fits("lowrank_q", m, c=c, r=r, s=s,
                                        q_bytes=q_bytes, bm=bm,
                                        bn=bn)):
        return ref.lowrank_matmul_q_ref(x, w0_q, w0_scale, w1_q, w1_scale)
    x2, pad_m = _pad_to(x2, 0, bm_eff)
    w1p, pad_s = _pad_to(w1_q, 1, bn)
    w1sp, _ = _pad_to(w1_scale, 1, bn)     # zero scales -> zero columns
    y = qk.lowrank_matmul_q(x2, w0_q, w0_scale, w1p, w1sp,
                            bm=bm_eff, bn=min(bn, w1p.shape[1]),
                            interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def lowrank_matmul_qa(x: jax.Array, w0_q: jax.Array, w0_scale: jax.Array,
                      w1_q: jax.Array, w1_scale: jax.Array, *,
                      bm: int = aqk.DEFAULT_BM, bn: int = aqk.DEFAULT_BN,
                      force_kernel: bool = False) -> jax.Array:
    """y = dq(q(x) @ w0_q) -> requant -> dq(h_q @ w1_q) with the fused
    activation-quantized kernel — both dots int8 x int8 on the MXU,
    per-token act scales folded with the per-channel weight scales."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    r, s = w1_q.shape
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    q_bytes = jnp.dtype(w0_q.dtype).itemsize
    if not (force_kernel or kernel_fits("lowrank_qa", m, c=c, r=r, s=s,
                                        q_bytes=q_bytes, bm=bm,
                                        bn=bn)):
        return ref.lowrank_matmul_qa_ref(x2, w0_q, w0_scale, w1_q,
                                         w1_scale).reshape(*lead, s)
    x2, pad_m = _pad_to(x2, 0, bm_eff)     # zero rows -> zero act scales
    w1p, pad_s = _pad_to(w1_q, 1, bn)
    w1sp, _ = _pad_to(w1_scale, 1, bn)     # zero scales -> zero columns
    y = aqk.lowrank_matmul_qa(x2, w0_q, w0_scale, w1p, w1sp,
                              bm=bm_eff, bn=min(bn, w1p.shape[1]),
                              interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def lowrank_matmul_sq(x: jax.Array, w0_sp: jax.Array, w0_idx: jax.Array,
                      w0_scale: jax.Array, w1_sp: jax.Array,
                      w1_idx: jax.Array, w1_scale: jax.Array, *,
                      bm: int = sk.DEFAULT_BM, bn: int = sk.DEFAULT_BN,
                      force_kernel: bool = False) -> jax.Array:
    """y = (x @ ds(w0)) @ ds(w1) with the fused sparse-int8 kernel —
    2:4-packed factors expanded + dequantized in VMEM."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    r = w0_sp.shape[-1]
    s = w1_sp.shape[-1]
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    q_bytes = jnp.dtype(w0_sp.dtype).itemsize
    if not (force_kernel or kernel_fits("lowrank_sq", m, c=c, r=r, s=s,
                                        q_bytes=q_bytes, bm=bm, bn=bn)):
        return ref.lowrank_matmul_sq_ref(x, w0_sp, w0_idx, w0_scale,
                                         w1_sp, w1_idx, w1_scale)
    x2, pad_m = _pad_to(x2, 0, bm_eff)
    w1p, pad_s = _pad_to(w1_sp, 2, bn)
    w1sp, _ = _pad_to(w1_scale, 1, bn)     # zero scales -> zero columns
    y = sk.lowrank_matmul_sq(x2, w0_sp, w0_idx, w0_scale,
                             w1p, w1_idx, w1sp,
                             bm=bm_eff, bn=min(bn, w1p.shape[2]),
                             interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def branched_matmul(x: jax.Array, u: jax.Array, xc: jax.Array,
                    v: jax.Array, *, bm: int = bk.DEFAULT_BM,
                    bn: int = bk.DEFAULT_BN,
                    force_kernel: bool = False) -> jax.Array:
    """y = sum_n ((x @ u_n) @ xc_n) @ v_n with the grouped kernel."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    n, _, r1 = u.shape
    _, _, r2 = xc.shape
    s = v.shape[-1]
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    if not (force_kernel or kernel_fits("branched", m, c=c, r1=r1, r2=r2,
                                        s=s, bm=bm, bn=bn)):
        return ref.branched_matmul_ref(x2, u, xc, v).reshape(*lead, s)
    x2, pad_m = _pad_to(x2, 0, bm_eff)
    vp, pad_s = _pad_to(v, 2, bn)
    y = bk.branched_matmul(x2, u, xc, vp, bm=bm_eff,
                           bn=min(bn, vp.shape[2]),
                           interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def branched_matmul_q(x: jax.Array, u_q: jax.Array, u_scale: jax.Array,
                      xc_q: jax.Array, xc_scale: jax.Array,
                      v_q: jax.Array, v_scale: jax.Array, *,
                      bm: int = bqk.DEFAULT_BM, bn: int = bqk.DEFAULT_BN,
                      force_kernel: bool = False) -> jax.Array:
    """y = sum_n ((x @ dq(u_n)) @ dq(xc_n)) @ dq(v_n) with the fused
    quantized branched kernel — int8 branch tiles dequantized in VMEM,
    branch sum in the scratch accumulator."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    n, _, r1 = u_q.shape
    _, _, r2 = xc_q.shape
    s = v_q.shape[-1]
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    q_bytes = jnp.dtype(u_q.dtype).itemsize
    if not (force_kernel or kernel_fits("branched_q", m, c=c, r1=r1, r2=r2,
                                        s=s, q_bytes=q_bytes, bm=bm,
                                        bn=bn)):
        return ref.branched_matmul_q_ref(x2, u_q, u_scale, xc_q, xc_scale,
                                         v_q, v_scale).reshape(*lead, s)
    x2, pad_m = _pad_to(x2, 0, bm_eff)
    vp, pad_s = _pad_to(v_q, 2, bn)
    vsp, _ = _pad_to(v_scale, 2, bn)       # zero scales -> zero columns
    y = bqk.branched_matmul_q(x2, u_q, u_scale, xc_q, xc_scale, vp, vsp,
                              bm=bm_eff, bn=min(bn, vp.shape[2]),
                              interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def branched_matmul_qa(x: jax.Array, u_q: jax.Array, u_scale: jax.Array,
                       xc_q: jax.Array, xc_scale: jax.Array,
                       v_q: jax.Array, v_scale: jax.Array, *,
                       bm: int = bak.DEFAULT_BM, bn: int = bak.DEFAULT_BN,
                       force_kernel: bool = False) -> jax.Array:
    """y = sum_n of the all-int8 branch chains with the fused
    activation-quantized branched kernel — activations quantize once
    per row block, every branch dot runs int8 x int8, branch sum in the
    f32 scratch accumulator."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    n, _, r1 = u_q.shape
    _, _, r2 = xc_q.shape
    s = v_q.shape[-1]
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    q_bytes = jnp.dtype(u_q.dtype).itemsize
    if not (force_kernel or kernel_fits("branched_qa", m, c=c, r1=r1,
                                        r2=r2, s=s, q_bytes=q_bytes,
                                        bm=bm, bn=bn)):
        return ref.branched_matmul_qa_ref(x2, u_q, u_scale, xc_q, xc_scale,
                                          v_q, v_scale).reshape(*lead, s)
    x2, pad_m = _pad_to(x2, 0, bm_eff)     # zero rows -> zero act scales
    vp, pad_s = _pad_to(v_q, 2, bn)
    vsp, _ = _pad_to(v_scale, 2, bn)       # zero scales -> zero columns
    y = bak.branched_matmul_qa(x2, u_q, u_scale, xc_q, xc_scale, vp, vsp,
                               bm=bm_eff, bn=min(bn, vp.shape[2]),
                               interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def branched_matmul_sq(x: jax.Array, u_sp: jax.Array, u_idx: jax.Array,
                       u_scale: jax.Array, xc_q: jax.Array,
                       xc_scale: jax.Array, v_sp: jax.Array,
                       v_idx: jax.Array, v_scale: jax.Array, *,
                       bm: int = bsk.DEFAULT_BM, bn: int = bsk.DEFAULT_BN,
                       force_kernel: bool = False) -> jax.Array:
    """y = sum_n ((x @ ds(u_n)) @ dq(xc_n)) @ ds(v_n) with the fused
    sparse-int8 branched kernel — 2:4-packed u/v tiles expanded +
    dequantized in VMEM, int8 core, branch sum in scratch."""
    lead = x.shape[:-1]
    c = x.shape[-1]
    r1 = u_sp.shape[-1]
    r2 = xc_q.shape[-1]
    s = v_sp.shape[-1]
    x2 = x.reshape(-1, c)
    m = x2.shape[0]
    bm_eff = _bm_eff(bm, m)
    q_bytes = jnp.dtype(u_sp.dtype).itemsize
    if not (force_kernel or kernel_fits("branched_sq", m, c=c, r1=r1,
                                        r2=r2, s=s, q_bytes=q_bytes,
                                        bm=bm, bn=bn)):
        return ref.branched_matmul_sq_ref(
            x2, u_sp, u_idx, u_scale, xc_q, xc_scale, v_sp, v_idx,
            v_scale).reshape(*lead, s)
    x2, pad_m = _pad_to(x2, 0, bm_eff)
    vp, pad_s = _pad_to(v_sp, 3, bn)
    vsp, _ = _pad_to(v_scale, 2, bn)       # zero scales -> zero columns
    y = bsk.branched_matmul_sq(x2, u_sp, u_idx, u_scale, xc_q, xc_scale,
                               vp, v_idx, vsp, bm=bm_eff,
                               bn=min(bn, vp.shape[3]),
                               interpret=tpu.interpret())
    if pad_m:
        y = y[:m]
    if pad_s:
        y = y[:, :s]
    return y.reshape(*lead, s)


def decode_attention_q(q: jax.Array, k_q: jax.Array, k_scale: jax.Array,
                       v_q: jax.Array, v_scale: jax.Array,
                       cache_pos: jax.Array, *, softcap: float = 0.0,
                       bs: int = dak.DEFAULT_BS,
                       force_kernel: bool = False) -> jax.Array:
    """One decode step of attention over an int8 KV pool, fused.

    q (B, 1, H, D); k_q/v_q (B, S, KH, D) int8; k/v_scale (B, KH, D)
    f32 per-(slot, head, channel); cache_pos (B,) -> (B, 1, H, D).
    Positions beyond each slot's ``cache_pos`` are masked in-kernel, so
    the S padding added here never leaks into the softmax.
    """
    b, sq, h, d = q.shape
    assert sq == 1, q.shape
    s, kh = k_q.shape[1], k_q.shape[2]
    g = h // kh
    q_bytes = jnp.dtype(k_q.dtype).itemsize
    if not (force_kernel or kernel_fits("decode_attn_q", b, c=d, s=s, r=g,
                                        kh=kh, q_bytes=q_bytes, bn=bs)):
        return ref.decode_attention_q_ref(q, k_q, k_scale, v_q, v_scale,
                                          cache_pos, softcap=softcap)
    # Head layout matches the jnp decode path: H rows group as (KH, G).
    qg = q[:, 0].reshape(b, kh, g, d)
    kq_p, _ = _pad_to(k_q, 1, bs)
    vq_p, _ = _pad_to(v_q, 1, bs)
    o = dak.decode_attention_q(
        qg, kq_p, k_scale, vq_p, v_scale,
        cache_pos.astype(jnp.int32).reshape(b, 1),
        bs=min(bs, kq_p.shape[1]), softcap=softcap,
        interpret=tpu.interpret())
    return o.reshape(b, 1, h, d)


def decode_attention_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                           block_tables: jax.Array, cache_pos: jax.Array,
                           *, softcap: float = 0.0,
                           force_kernel: bool = False) -> jax.Array:
    """One decode step of attention over a full-width paged KV pool.

    q (B, 1, H, D); k/v (NB+1, bs, KH, D) — batch axis = physical
    block; block_tables (B, nblk) int32; cache_pos (B,) ->
    (B, 1, H, D).  The kernel's sequence block IS the pool block (no S
    padding); table entries beyond a stream's allocation alias the
    dummy block and mask out by position.
    """
    b, sq, h, d = q.shape
    assert sq == 1, q.shape
    bs, kh = k.shape[1], k.shape[2]
    g = h // kh
    q_bytes = jnp.dtype(k.dtype).itemsize
    if not (force_kernel or kernel_fits("decode_attn_paged", b, c=d, s=bs,
                                        r=g, kh=kh, q_bytes=q_bytes,
                                        bn=bs)):
        return ref.decode_attention_paged_ref(q, k, v, block_tables,
                                              cache_pos, softcap=softcap)
    qg = q[:, 0].reshape(b, kh, g, d)
    o = dap.decode_attention_paged(
        qg, k, v, block_tables.astype(jnp.int32),
        cache_pos.astype(jnp.int32).reshape(b, 1),
        softcap=softcap, interpret=tpu.interpret())
    return o.reshape(b, 1, h, d)


def decode_attention_paged_q(q: jax.Array, k_q: jax.Array,
                             k_scale: jax.Array, v_q: jax.Array,
                             v_scale: jax.Array, block_tables: jax.Array,
                             cache_pos: jax.Array, *, softcap: float = 0.0,
                             force_kernel: bool = False) -> jax.Array:
    """One decode step of attention over an int8 paged KV pool, fused.

    q (B, 1, H, D); k_q/v_q (NB+1, bs, KH, D) int8; PER-BLOCK k/v_scale
    (NB+1, KH, D) f32; block_tables (B, nblk) int32; cache_pos (B,) ->
    (B, 1, H, D).  K scales fold into the query row per block, V scales
    into each block's context contribution.
    """
    b, sq, h, d = q.shape
    assert sq == 1, q.shape
    bs, kh = k_q.shape[1], k_q.shape[2]
    g = h // kh
    q_bytes = jnp.dtype(k_q.dtype).itemsize
    if not (force_kernel or kernel_fits("decode_attn_paged", b, c=d, s=bs,
                                        r=g, kh=kh, q_bytes=q_bytes,
                                        bn=bs)):
        return ref.decode_attention_paged_q_ref(
            q, k_q, k_scale, v_q, v_scale, block_tables, cache_pos,
            softcap=softcap)
    qg = q[:, 0].reshape(b, kh, g, d)
    o = dap.decode_attention_paged_q(
        qg, k_q, k_scale, v_q, v_scale, block_tables.astype(jnp.int32),
        cache_pos.astype(jnp.int32).reshape(b, 1),
        softcap=softcap, interpret=tpu.interpret())
    return o.reshape(b, 1, h, d)


def decode_attention_latent_q(q_lat: jax.Array, q_rope: jax.Array,
                              ckv_q: jax.Array, ckv_scale: jax.Array,
                              krope_q: jax.Array, krope_scale: jax.Array,
                              cache_pos: jax.Array, *, scale: float,
                              bs: int = dak.DEFAULT_BS,
                              force_kernel: bool = False) -> jax.Array:
    """One absorbed-form MLA decode step over an int8 latent pool, fused.

    q_lat (B, 1, H, L); q_rope (B, 1, H, R); ckv_q (B, S, L) / krope_q
    (B, S, R) int8; ckv/krope_scale (B, L)/(B, R) f32 per-(slot,
    channel); cache_pos (B,) -> context latents (B, 1, H, L).
    ``scale`` is the logit scale 1/sqrt(qk_nope + qk_rope).  Positions
    beyond each slot's ``cache_pos`` are masked in-kernel, so the S
    padding added here never leaks into the softmax.
    """
    b, sq, h, lora = q_lat.shape
    assert sq == 1, q_lat.shape
    s = ckv_q.shape[1]
    rope = q_rope.shape[-1]
    q_bytes = jnp.dtype(ckv_q.dtype).itemsize
    if not (force_kernel or kernel_fits("decode_latent_q", b, c=lora, s=s,
                                        r=h, r1=rope, q_bytes=q_bytes,
                                        bn=bs)):
        return ref.decode_attention_latent_q_ref(
            q_lat, q_rope, ckv_q, ckv_scale, krope_q, krope_scale,
            cache_pos, scale=scale)
    cq_p, _ = _pad_to(ckv_q, 1, bs)
    rq_p, _ = _pad_to(krope_q, 1, bs)
    o = dak.decode_attention_latent_q(
        q_lat[:, 0], q_rope[:, 0], cq_p, ckv_scale, rq_p, krope_scale,
        cache_pos.astype(jnp.int32).reshape(b, 1), scale=scale,
        bs=min(bs, cq_p.shape[1]), interpret=tpu.interpret())
    return o[:, None]
