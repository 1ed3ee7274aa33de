"""2:4 semi-structured sparsity of decomposed factor matrices.

The third compression axis: low-rank surgery shrinks the *rank*
(:mod:`repro.core.surgery`), per-channel quantization shrinks the
*width* (:mod:`repro.quant.quantize`), and this module shrinks the
*density* — a magnitude-based N:M (2:4) prune of the factor matrices
that composes multiplicatively with both, halving the weight bytes
streamed per decode token again on top of the int8 halving.

Conventions mirror :mod:`repro.quant.quantize`: params stay plain
nested dicts, and a sparsified factor ``k (..., C, S)`` is rewritten in
place as the key triple

    k_sp  — packed kept values, slot-major ``(..., 2, C/4, S)``
            (int8 when composed with quantization, else ``k``'s dtype)
    k_idx — int8 within-group row positions ``(..., 2, C/4, 1)``,
            values in ``{0..3}``, ascending per group
    k_scale — f32 per-output-channel scales ``(..., 1, S)`` (only when
            quantized; same convention as ``quantize_tree``)

**The 2:4 mask is shared across the output axis**: for every group of 4
input rows, the 2 rows with the largest aggregate magnitude (L1 norm
across output channels) are kept for *all* columns.  A per-column mask
would need 2 bits of metadata per kept value (``0.25 byte/value`` — on
int8 values that caps the byte gain at 1.6x, below the 2x the sparsity
nominally buys); the shared mask needs one int8 position per kept *row*
(``C/2`` bytes per factor, amortized over all S columns), so the byte
gain stays ~2x.  The trade is coarser pruning — acceptable on low-rank
factors, whose rows are energy-sorted by construction (the SVD already
concentrated magnitude).

Slot-major packing (keep-slot as the leading axis, not interleaved)
lets the fused kernels slice ``sp_ref[i]`` as a contiguous 2D tile —
no strided sublane access — and expand it in VMEM with two
repeat/iota-compare passes (:mod:`repro.kernels.lowrank_matmul_sq`).
"""
from __future__ import annotations

from typing import Any, Iterable

import jax
import jax.numpy as jnp

from repro.quant.quantize import (IDX_SUFFIX, MODE_INT8, SCALE_SUFFIX,
                                  SP_SUFFIX, is_quantized, quantize_array,
                                  scale_axes, sparse_index_axes,
                                  sparse_value_axes)

PyTree = Any

PATTERN_24 = "2:4"
PATTERNS = (PATTERN_24,)

#: factor keys the 2:4 pass targets by default: the teacher-derived
#: outer factors (SVD pair w0/w1, branched u/v).  The trainable core
#: (xc) and the spatial Tucker factors are excluded — they are small,
#: and the branched kernel keeps xc as a plain int8 tile.
SPARSE_KEYS = ("w0", "w1", "u", "v")


def pattern_nm(pattern: str) -> tuple[int, int]:
    """``"2:4" -> (2, 4)`` — kept rows per group, group size."""
    if pattern not in PATTERNS:
        raise ValueError(
            f"unknown sparsity pattern {pattern!r} (want one of {PATTERNS})")
    keep, group = (int(t) for t in pattern.split(":"))
    return keep, group


def sparsify_array(w: jax.Array, pattern: str = PATTERN_24,
                   mode: str = MODE_INT8
                   ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Magnitude-prune ``w (..., C, S)`` to 2:4 along the input axis.

    Returns ``(sp, idx, scale)``: packed values ``(..., 2, C/4, S)``,
    int8 within-group positions ``(..., 2, C/4, 1)`` (ascending), and
    per-output-channel f32 scales ``(..., 1, S)`` when ``mode`` is a
    quant mode (``sp`` is then int8/fp8); ``mode="none"`` keeps ``sp``
    in ``w``'s dtype and returns ``scale=None``.

    Magnitude is the row's L1 norm across output channels — the mask is
    shared over S (see module docstring for the byte math).  Requires
    ``C % 4 == 0``.
    """
    keep, group = pattern_nm(pattern)
    *lead, c, s = w.shape
    if c % group:
        raise ValueError(f"input dim {c} not divisible by {group} "
                         f"for {pattern} sparsity: {w.shape}")
    g = c // group
    wf = w.astype(jnp.float32)
    wg = wf.reshape(*lead, g, group, s)
    score = jnp.sum(jnp.abs(wg), axis=-1)                # (..., G, 4)
    # Top-`keep` rows per group; ascending positions for a stable layout
    # (argsort of -score is stable, so ties keep the lower row).
    top = jnp.argsort(-score, axis=-1)[..., :keep]
    idx = jnp.sort(top, axis=-1)                         # (..., G, 2)
    sp = jnp.take_along_axis(wg, idx[..., None], axis=-2)  # (..., G, 2, S)
    # Slot-major: (..., 2, G, S) / (..., 2, G, 1).
    sp = jnp.moveaxis(sp, -2, -3)
    idx = jnp.swapaxes(idx, -1, -2)[..., None].astype(jnp.int8)
    if mode == "none":
        return sp.astype(w.dtype), idx, None
    # Reuse the per-output-channel quantizer by flattening the packed
    # axes: absmax over all kept rows, one f32 scale per column.
    flat = sp.reshape(*lead, keep * g, s)
    q, scale = quantize_array(flat, mode)
    return q.reshape(*lead, keep, g, s), idx, scale


def expand_sparse(sp: jax.Array, idx: jax.Array,
                  scale: jax.Array | None = None,
                  dtype=None) -> jax.Array:
    """Inverse scatter: ``(..., 2, C/4, S) -> (..., C, S)`` dense.

    Pruned rows come back as zeros; with ``scale`` the values are also
    dequantized (matching the fused kernels' in-VMEM expand+dequant).
    Default output dtype: bf16 when dequantizing, else ``sp``'s dtype.
    """
    *lead, keep, g, s = sp.shape
    group = 4 * idx.shape[-1]        # idx (..., keep, G, 1); 2:4 -> 4
    oh = (idx.astype(jnp.int32)
          == jnp.arange(group, dtype=jnp.int32))          # (..., 2, G, 4)
    dense = jnp.einsum("...igj,...igs->...gjs", oh.astype(jnp.float32),
                       sp.astype(jnp.float32))            # (..., G, 4, S)
    dense = dense.reshape(*lead, g * group, s)
    if scale is not None:
        dense = dense * scale
        return dense.astype(dtype or jnp.bfloat16)
    return dense.astype(dtype or sp.dtype)


def is_sparse(node: dict) -> bool:
    """Does this (linear) subtree hold 2:4-packed factors?"""
    return isinstance(node, dict) and any(
        k.endswith(SP_SUFFIX) for k in node)


def desparsify_subtree(node: dict, dtype=jnp.bfloat16) -> dict:
    """Restore one subtree's ``k_sp``/``k_idx``(/``k_scale``) triples to
    plain dense ``k`` (pruned rows as zeros)."""
    out = {}
    for k, v in node.items():
        if k.endswith(SP_SUFFIX):
            base = k[: -len(SP_SUFFIX)]
            out[base] = expand_sparse(v, node[base + IDX_SUFFIX],
                                      node.get(base + SCALE_SUFFIX), dtype)
        elif k.endswith(IDX_SUFFIX):
            continue
        elif (k.endswith(SCALE_SUFFIX)
              and k[: -len(SCALE_SUFFIX)] + SP_SUFFIX in node):
            continue
        else:
            out[k] = v
    return out


def sparsify_tree(params: PyTree, pattern: str = PATTERN_24,
                  mode: str = MODE_INT8, *,
                  targets: Iterable[str] = SPARSE_KEYS,
                  axes: PyTree | None = None) -> PyTree:
    """Sparsify (and optionally quantize) every targeted factor leaf.

    Walks the nested-dict tree the way ``quantize_tree`` does; only 2D+
    array leaves whose key is in ``targets`` *and* whose input dim is
    divisible by the group size are rewritten — other factors pass
    through untouched (a later ``quantize_tree`` still picks them up,
    and mixed subtrees take the reference execution path).  Subtrees
    already sparse or already quantized are left alone, so the
    transform is idempotent and runs *before* ``quantize_tree`` in the
    serve-engine load pipeline.

    ``mode`` is a quant mode (``"int8"``/``"fp8"`` — one pass does
    prune + quantize, emitting ``k_sp``+``k_idx``+``k_scale``) or
    ``"none"`` (prune only, ``k_sp`` keeps the source dtype — the
    sparse-only point of the compression frontier).

    With ``axes`` (the matching logical-axes tree) the rewrite is
    applied to both trees and ``(sparams, saxes)`` is returned, same
    contract as ``quantize_tree``.
    """
    _, group = pattern_nm(pattern)
    targets = set(targets)

    def walk(node: Any, ax: Any) -> tuple[Any, Any]:
        if not isinstance(node, dict):
            return node, ax
        if is_sparse(node) or is_quantized(node):
            return dict(node), (dict(ax) if isinstance(ax, dict) else ax)
        out, a_out = {}, {}
        for k, v in node.items():
            a_k = ax[k] if isinstance(ax, dict) else None
            if (k in targets and hasattr(v, "ndim") and v.ndim >= 2
                    and v.shape[-2] % group == 0 and v.shape[-2] >= group):
                sp, idx, scale = sparsify_array(v, pattern, mode)
                out[k + SP_SUFFIX] = sp
                out[k + IDX_SUFFIX] = idx
                if scale is not None:
                    out[k + SCALE_SUFFIX] = scale
                if isinstance(ax, dict):
                    a_out[k + SP_SUFFIX] = sparse_value_axes(a_k)
                    a_out[k + IDX_SUFFIX] = sparse_index_axes(a_k)
                    if scale is not None:
                        a_out[k + SCALE_SUFFIX] = scale_axes(a_k)
            else:
                out[k], a_out[k] = walk(v, a_k)
        return out, a_out

    sparams, saxes = walk(params, axes)
    if axes is None:
        return sparams
    return sparams, saxes


def desparsify_tree(params: PyTree, dtype=jnp.bfloat16) -> PyTree:
    """Inverse tree transform: restore plain (zero-padded) factor keys."""

    def walk(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        if is_sparse(node):
            return desparsify_subtree(node, dtype)
        return {k: walk(v) for k, v in node.items()}

    return walk(params)


def relative_error_sparse(w: jax.Array, pattern: str = PATTERN_24,
                          mode: str = MODE_INT8) -> float:
    """||w - expand(sparsify(w))|| / ||w|| — prune + quant round trip."""
    sp, idx, scale = sparsify_array(w, pattern, mode)
    wd = expand_sparse(sp, idx, scale, jnp.float32)
    num = float(jnp.linalg.norm((w.astype(jnp.float32) - wd).reshape(-1)))
    den = float(jnp.linalg.norm(w.astype(jnp.float32).reshape(-1)))
    return num / max(den, 1e-30)
