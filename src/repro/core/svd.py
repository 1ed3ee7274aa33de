"""Truncated-SVD low-rank decomposition of linear layers (paper Eq. 1-3).

Each dense weight ``W (C, S)`` decomposes into the balanced factor pair

    W0 = U' sqrt(S'),   W1 = sqrt(S') V'^T          (Eq. 3)

with ``W0 (C, R)``, ``W1 (R, S)``.  The balanced split (sqrt of the singular
values on both sides) keeps the two factors at comparable norms, which
matters for fine-tuning stability and for the paper's freezing variant
(§2.2: the frozen factor is a near-orthogonal transform).

Batched variants (leading expert / branch axes) reuse the same code through
vmap so MoE expert banks decompose in one call.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SVDFactors(NamedTuple):
    w0: jax.Array        # (..., C, R)
    w1: jax.Array        # (..., R, S)


def svd_decompose(w: jax.Array, rank: int) -> SVDFactors:
    """Truncated SVD of ``w (..., C, S)`` into balanced rank-``rank`` factors.

    Computed in float32 regardless of the input dtype (bf16 SVD is
    numerically useless); factors are cast back to ``w.dtype``.
    """
    orig_dtype = w.dtype
    wf = w.astype(jnp.float32)
    u, s, vt = jnp.linalg.svd(wf, full_matrices=False)
    r = min(rank, s.shape[-1])
    sq = jnp.sqrt(s[..., :r])
    w0 = u[..., :, :r] * sq[..., None, :]
    w1 = sq[..., :, None] * vt[..., :r, :]
    return SVDFactors(w0.astype(orig_dtype), w1.astype(orig_dtype))


def reconstruct(f: SVDFactors) -> jax.Array:
    """W' = W0 @ W1 (paper Eq. 2/3)."""
    return jnp.matmul(f.w0.astype(jnp.float32),
                      f.w1.astype(jnp.float32)).astype(f.w0.dtype)


def approximation_error(w: jax.Array, f: SVDFactors) -> float:
    """Relative Frobenius error ||W - W0 W1||_F / ||W||_F."""
    wf = w.astype(jnp.float32)
    err = jnp.linalg.norm(wf - reconstruct(f).astype(jnp.float32))
    return float(err / (jnp.linalg.norm(wf) + 1e-30))


def energy_rank(w: jax.Array, energy: float) -> int:
    """Smallest rank whose singular values keep ``energy`` of sum sigma_i^2."""
    s = jnp.linalg.svd(w.astype(jnp.float32), compute_uv=False)
    e = jnp.cumsum(s**2)
    e = e / e[-1]
    return int(jnp.searchsorted(e, energy) + 1)


def ratio_rank(c: int, s: int, compression: float) -> int:
    """Rank giving ``compression``x fewer params: R = C*S / (alpha*(C+S))."""
    r = int(math.floor(c * s / (compression * (c + s))))
    return max(1, min(r, min(c, s)))


def compression_of_rank(c: int, s: int, rank: int) -> float:
    """Achieved parameter compression ratio for a rank-R pair."""
    return (c * s) / (rank * (c + s))


def lowrank_params(c: int, s: int, rank: int) -> int:
    return rank * (c + s)


def svd_flops_per_row(c: int, s: int, rank: int) -> float:
    """Forward matmul FLOPs per input row (2 matmuls through the bottleneck)."""
    return 2.0 * rank * (c + s)


def dense_flops_per_row(c: int, s: int) -> float:
    return 2.0 * c * s


def randomized_svd(w: jax.Array, rank: int, *, oversample: int = 8,
                   n_iter: int = 2, key: jax.Array | None = None
                   ) -> SVDFactors:
    """Halko-style randomized SVD — O(C*S*R) instead of O(C*S*min(C,S)).

    Used by surgery on very large matrices (e.g. 163840x2048 embeddings)
    where full SVD on host would dominate decomposition time; the paper's
    "takes only a few seconds" property is preserved this way.
    """
    orig_dtype = w.dtype
    wf = w.astype(jnp.float32)
    c, s = wf.shape[-2:]
    k = min(rank + oversample, min(c, s))
    if key is None:
        key = jax.random.PRNGKey(0)
    omega = jax.random.normal(key, (*wf.shape[:-2], s, k), jnp.float32)
    y = wf @ omega                                     # (..., C, k)
    for _ in range(n_iter):                            # power iterations
        y = wf @ (jnp.swapaxes(wf, -1, -2) @ y)
        y, _ = jnp.linalg.qr(y)
    q, _ = jnp.linalg.qr(y)                            # (..., C, k)
    b = jnp.swapaxes(q, -1, -2) @ wf                   # (..., k, S)
    ub, sb, vtb = jnp.linalg.svd(b, full_matrices=False)
    r = min(rank, sb.shape[-1])
    sq = jnp.sqrt(sb[..., :r])
    w0 = (q @ ub[..., :, :r]) * sq[..., None, :]
    w1 = sq[..., :, None] * vtb[..., :r, :]
    return SVDFactors(w0.astype(orig_dtype), w1.astype(orig_dtype))


@functools.partial(jax.jit, static_argnames="wide")
def _gram(w: jax.Array, wide: bool) -> jax.Array:
    """f32 Gram matrix of ``w``'s short side: W W^T when ``wide``."""
    a = w if wide else w.T
    return jnp.dot(a, a.T, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames="wide")
def _balanced(w: jax.Array, vec: jax.Array, sig: jax.Array,
              wide: bool) -> SVDFactors:
    """Balanced factors from the short side's top singular vectors
    ``vec`` (n, r) and values ``sig`` (r,): the long side's vectors come
    from one product with ``w``.  Directions at or below the f32 Gram
    noise floor get zero factors (they carry no signal)."""
    root = jnp.sqrt(sig)
    floor = sig[0] * 1e-3
    inv = jnp.where(sig > floor, 1.0 / jnp.where(sig > floor, root, 1.0),
                    0.0)
    hi = jax.lax.Precision.HIGHEST
    # the product reads w in its own dtype: no f32 copy of a big matrix
    if wide:                                        # vec = U_r
        w0 = vec * root[None, :]
        w1 = inv[:, None] * jnp.dot(vec.astype(w.dtype).T, w, precision=hi,
                                    preferred_element_type=jnp.float32)
    else:                                           # vec = V_r
        w0 = jnp.dot(w, vec.astype(w.dtype), precision=hi,
                     preferred_element_type=jnp.float32) * inv[None, :]
        w1 = root[:, None] * vec.T
    return SVDFactors(w0.astype(w.dtype), w1.astype(w.dtype))


def gram_svd_decompose(w: jax.Array, rank: int) -> SVDFactors:
    """The balanced factors of :func:`svd_decompose`, computed through
    the Gram matrix of the short side.

    ``W W^T`` (or ``W^T W``) is formed on ``w``'s device, its symmetric
    eigendecomposition runs on the host (LAPACK), and one more product
    on the device gives the long side.  The device runs only matmuls:
    XLA's SVD and eigh compile for many minutes per shape on a TPU, a
    matmul in seconds.  Leading (layer / expert) dims decompose one
    matrix at a time, which also bounds device temporaries to one
    matrix.  Accurate to f32 rounding of the Gram matrix — ample for
    factors stored in bf16 or f32.
    """
    if w.ndim > 2:
        parts = [gram_svd_decompose(w[i], rank) for i in range(w.shape[0])]
        return SVDFactors(jnp.stack([f.w0 for f in parts]),
                          jnp.stack([f.w1 for f in parts]))
    import scipy.linalg
    wide = w.shape[0] <= w.shape[1]
    lam, vec = scipy.linalg.eigh(np.asarray(_gram(w, wide)), driver="evd")
    r = min(rank, lam.shape[0])
    lam, vec = lam[::-1][:r], vec[:, ::-1][:, :r]   # descending
    sig = np.sqrt(np.maximum(lam, 0.0)).astype(np.float32)
    return _balanced(w, jnp.asarray(np.ascontiguousarray(vec)),
                     jnp.asarray(sig), wide)


def decompose_auto(w: jax.Array, rank: int, *, randomized_threshold: int = 4096,
                   key: jax.Array | None = None) -> SVDFactors:
    """Gram-matrix SVD (:func:`gram_svd_decompose`) for matrices whose
    short side is at most ``randomized_threshold``, randomized SVD for
    bigger ones at low rank."""
    c, s = int(w.shape[-2]), int(w.shape[-1])
    if min(c, s) > randomized_threshold and rank < min(c, s) // 4:
        return randomized_svd(w, rank, key=key)
    return gram_svd_decompose(w, rank)


def host_svd_decompose(w: np.ndarray, rank: int) -> tuple[np.ndarray, np.ndarray]:
    """NumPy twin for checkpoint-surgery paths that never touch devices."""
    u, s, vt = np.linalg.svd(w.astype(np.float32), full_matrices=False)
    r = min(rank, s.shape[-1])
    sq = np.sqrt(s[:r])
    return (u[:, :r] * sq[None, :]).astype(w.dtype), \
           (sq[:, None] * vt[:r, :]).astype(w.dtype)
