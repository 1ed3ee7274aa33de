"""Per-channel symmetric quantization of decomposed factor matrices.

The paper gets compression *and* speed from the low-rank structure; this
module compounds both by quantizing the factor matrices themselves —
int8 (4x smaller than f32, 2x smaller than bf16) or fp8-emulated — which
halves the HBM weight traffic on the serving hot path on top of the
rank reduction.

Conventions mirror :mod:`repro.core.surgery`: params stay plain nested
dicts, and a quantized factor ``k`` is rewritten *in place* as the key
pair ``k_q`` (narrow values) + ``k_scale`` (f32 per-channel scales), e.g.

    {"w0": (C, R), "w1": (R, S)}
      -> {"w0_q": int8 (C, R), "w0_scale": f32 (1, R),
          "w1_q": int8 (R, S), "w1_scale": f32 (1, S)}

so :func:`repro.layers.param.apply_linear` / ``apply_conv`` dispatch on
the keys present and model code never changes — the same seam the LRD
surgery uses.

Scales are *per output channel*: the absmax reduction runs over the
input (second-to-last) axis only, keeping one scale per column (and per
leading batch/branch index for stacked or branched factors).  Symmetric
(no zero-point): ``w ≈ q * scale`` with ``q in [-127, 127]`` for int8.
"""
from __future__ import annotations

from typing import Any, Iterable

import jax
import jax.numpy as jnp

PyTree = Any

MODE_INT8 = "int8"
MODE_FP8 = "fp8"
MODES = (MODE_INT8, MODE_FP8)

#: keys the LRD surgery can produce (SVD pair, branched, Tucker-2).
FACTOR_KEYS = ("w0", "w1", "u", "xc", "v", "tucker_u", "core", "tucker_v")

QUANT_SUFFIX = "_q"
SCALE_SUFFIX = "_scale"
# 2:4 structured-sparsity pair (repro.quant.sparse): ``k_sp`` packed
# values (slot-major ``(..., 2, C/4, S)``) + ``k_idx`` int8 within-group
# row positions ``(..., 2, C/4, 1)``.  Defined here so the axes
# alignment below covers sparse trees without a circular import.
SP_SUFFIX = "_sp"
IDX_SUFFIX = "_idx"

INT8_QMAX = 127.0          # symmetric narrow range [-127, 127]
FP8_MAX = 448.0            # e4m3 max finite


def quantize_array(w: jax.Array, mode: str = MODE_INT8, *,
                   axis: int = -2) -> tuple[jax.Array, jax.Array]:
    """Quantize ``w`` per-channel along ``axis`` -> ``(q, scale)``.

    ``scale`` keeps ``w``'s shape with ``axis`` collapsed to 1, so
    ``q.astype(f32) * scale`` broadcasts back to ``w``.  All-zero
    channels get scale 0 (dequantizes to exact zeros).
    """
    if mode not in MODES:
        raise ValueError(f"unknown quant mode {mode!r} (want one of {MODES})")
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    qmax = INT8_QMAX if mode == MODE_INT8 else FP8_MAX
    scale = amax / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    scaled = wf / safe
    if mode == MODE_INT8:
        q = jnp.clip(jnp.round(scaled), -INT8_QMAX, INT8_QMAX
                     ).astype(jnp.int8)
    else:
        q = scaled.astype(jnp.float8_e4m3fn)
    return q, scale


def dequantize_array(q: jax.Array, scale: jax.Array,
                     dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_array` (up to rounding error)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def is_quantized(node: dict) -> bool:
    """Does this (linear/conv) subtree hold quantized factors?"""
    return isinstance(node, dict) and any(
        k.endswith(QUANT_SUFFIX) for k in node)


def dequantize_subtree(node: dict, dtype=jnp.bfloat16) -> dict:
    """Restore one subtree's ``k_q``/``k_scale`` pairs to plain ``k``."""
    out = {}
    for k, v in node.items():
        if k.endswith(QUANT_SUFFIX):
            base = k[: -len(QUANT_SUFFIX)]
            out[base] = dequantize_array(v, node[base + SCALE_SUFFIX], dtype)
        elif k.endswith(SCALE_SUFFIX):
            continue
        else:
            out[k] = v
    return out


def scale_axes(axes: tuple) -> tuple:
    """Logical axes of a ``k_scale`` leaf given factor ``k``'s axes.

    The absmax reduction collapses the input (second-to-last) axis to 1,
    so the scale keeps ``k``'s axes with that position unsharded (None)
    and the out-dim axis intact — which is how quantized trees shard:
    ``k_scale`` follows ``k``'s output dim or replicates.
    """
    if len(axes) < 2:
        raise ValueError(f"factor axes must be 2D+: {axes}")
    return (*axes[:-2], None, axes[-1])


def sparse_value_axes(axes: tuple) -> tuple:
    """Logical axes of a ``k_sp`` leaf given factor ``k``'s axes.

    The slot-major packing ``(..., 2, C/4, S)`` inserts an unsharded
    keep-slot axis before the (grouped) input axis; the input and output
    axes keep their logical names, so a sparse tree shards like its
    dense twin (the grouped input dim is C/4 — still divisible for any
    mesh that divided C, since C % 4 == 0).
    """
    if len(axes) < 2:
        raise ValueError(f"factor axes must be 2D+: {axes}")
    return (*axes[:-2], None, axes[-2], axes[-1])


def sparse_index_axes(axes: tuple) -> tuple:
    """Logical axes of a ``k_idx`` leaf ``(..., 2, C/4, 1)``: keep-slot
    and the collapsed output dim unsharded, input axis as the value."""
    if len(axes) < 2:
        raise ValueError(f"factor axes must be 2D+: {axes}")
    return (*axes[:-2], None, axes[-2], None)


def align_quantized_axes(params_node: dict, axes_node: dict) -> dict:
    """Axes dict aligned with a (possibly quantized/sparse) params dict.

    For every ``k_q``/``k_scale`` (and sparse ``k_sp``/``k_idx``) key
    whose axes entry is missing, derives it from factor ``k``'s logical
    axes: ``k_q`` inherits them verbatim, ``k_scale`` gets
    :func:`scale_axes`, ``k_sp``/``k_idx`` get
    :func:`sparse_value_axes`/:func:`sparse_index_axes`.  This is the
    one place the rewrite conventions meet the axes trees —
    ``parallel.sharding.make_param_shardings`` calls it per dict node,
    so trees quantized or sparsified *after* the axes were built still
    resolve.
    """
    out = {}
    for k in params_node:
        if k in axes_node:
            out[k] = axes_node[k]
            continue
        if k.endswith(QUANT_SUFFIX):
            base = k[: -len(QUANT_SUFFIX)]
            if base in axes_node:
                out[k] = axes_node[base]
                continue
        elif k.endswith(SCALE_SUFFIX):
            base = k[: -len(SCALE_SUFFIX)]
            if base in axes_node:
                out[k] = scale_axes(axes_node[base])
                continue
        elif k.endswith(SP_SUFFIX):
            base = k[: -len(SP_SUFFIX)]
            if base in axes_node:
                out[k] = sparse_value_axes(axes_node[base])
                continue
        elif k.endswith(IDX_SUFFIX):
            base = k[: -len(IDX_SUFFIX)]
            if base in axes_node:
                out[k] = sparse_index_axes(axes_node[base])
                continue
        raise KeyError(
            f"cannot resolve logical axes for param key {k!r} "
            f"(axes node has {sorted(axes_node)})")
    return out


def quantize_tree(params: PyTree, mode: str = MODE_INT8, *,
                  targets: Iterable[str] = FACTOR_KEYS,
                  axes: PyTree | None = None) -> PyTree:
    """Quantize every targeted factor leaf in a param tree.

    Walks the nested-dict tree the way the surgery does; only 2D+ array
    leaves whose key is in ``targets`` are rewritten (norms, embeddings,
    dense ``w`` layers the surgery kept as ORG, and biases pass through
    untouched).  Already-quantized subtrees are left alone, so the
    transform is idempotent.

    When ``axes`` (the matching logical-axes tree) is given, the rewrite
    is applied to *both* trees and ``(qparams, qaxes)`` is returned:
    ``k_q`` inherits ``k``'s axes, ``k_scale`` gets :func:`scale_axes` —
    so quantized trees keep sharding through
    ``parallel.sharding.make_param_shardings``.
    """
    targets = set(targets)

    def walk(node: Any, ax: Any) -> tuple[Any, Any]:
        if not isinstance(node, dict):
            return node, ax
        if is_quantized(node):
            out = dict(node)
            a_out = (align_quantized_axes(node, ax)
                     if isinstance(ax, dict) else ax)
            return out, a_out
        out, a_out = {}, {}
        for k, v in node.items():
            if isinstance(ax, dict):
                if k not in ax:
                    raise KeyError(
                        f"axes tree missing entry for param key {k!r} "
                        f"(axes node has {sorted(ax)})")
                a_k = ax[k]
            else:
                a_k = None
            if (k in targets and hasattr(v, "ndim") and v.ndim >= 2):
                q, scale = quantize_array(v, mode)
                out[k + QUANT_SUFFIX] = q
                out[k + SCALE_SUFFIX] = scale
                if isinstance(ax, dict):
                    a_out[k + QUANT_SUFFIX] = a_k
                    a_out[k + SCALE_SUFFIX] = scale_axes(a_k)
            else:
                out[k], a_out[k] = walk(v, a_k)
        return out, a_out

    qparams, qaxes = walk(params, axes)
    if axes is None:
        return qparams
    return qparams, qaxes


def dequantize_tree(params: PyTree, dtype=jnp.bfloat16) -> PyTree:
    """Inverse tree transform: restore plain factor keys everywhere."""

    def walk(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        if is_quantized(node):
            return dequantize_subtree(node, dtype)
        return {k: walk(v) for k, v in node.items()}

    return walk(params)


# ---------------------------------------------------------------------------
# Accounting helpers (reports)
# ---------------------------------------------------------------------------

def tree_bytes(params: PyTree) -> int:
    """Total parameter bytes (what HBM must hold / stream per full pass)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        itemsize = getattr(leaf.dtype, "itemsize", None)
        if itemsize is None:                      # fp8 dtypes on old numpy
            itemsize = jnp.dtype(leaf.dtype).itemsize
        total += int(leaf.size) * int(itemsize)
    return total


def relative_error(w: jax.Array, mode: str = MODE_INT8, *,
                   axis: int = -2) -> float:
    """||w - dq(q(w))|| / ||w|| — the round-trip quantization error."""
    q, scale = quantize_array(w, mode, axis=axis)
    wd = dequantize_array(q, scale, jnp.float32)
    num = float(jnp.linalg.norm((w.astype(jnp.float32) - wd).reshape(-1)))
    den = float(jnp.linalg.norm(w.astype(jnp.float32).reshape(-1)))
    return num / max(den, 1e-30)
