"""Seeded low-rank factor trees, built on the device in one jitted call.

A benchmark run needs factors of the right shapes, not a decomposition
of trained weights: speed and agreement with the reference do not
depend on where the numbers came from, and users decompose a model once,
offline.  So this module draws the decomposed tree directly:

* the structure is the program's own: ``jax.eval_shape`` of the model's
  ``init``, walked with ``core.surgery``'s path labels and targets, and
  every rank from ``core.rank_selection.select_rank``;
* every factor pair ``w0 (C, R)``, ``w1 (R, S)`` is Gaussian with
  ``w0 @ w1`` at the variance of the dense init (``1 / C``), every other
  leaf as the init draws it (embedding N(0, 0.02^2)), and norm scales
  ``1 + 0.1 N(0, 1)`` so that a reference that mis-applies them shows;
* the whole tree comes out of one jitted function of the seed, in the
  served dtype, without a dense tree or a host eigensolve.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

NORM_NOISE = 0.1
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer (wider than 32 bits)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def dense_structure(cfg) -> tuple[Any, Any]:
    """``(shapes, axes)`` of the dense init, with no array made."""
    from repro.models.api import get_model
    model = get_model(cfg)
    box = {}

    def init(key):
        params, axes = model.init(key)
        box["axes"] = axes
        return params

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return shapes, box["axes"]


def plan_tree(cfg, lrd) -> Any:
    """The decomposed tree as a nested dict whose leaves are
    ``(kind, shape, dtype, std)``: what :func:`build` draws."""
    from repro.core import rank_selection as rs
    from repro.core import surgery

    shapes, axes = dense_structure(cfg)
    targets = set(lrd.targets)
    ranks: dict = {}

    def leaf(kind, shape, dtype, std):
        return (kind, tuple(int(d) for d in shape), jnp.dtype(dtype).name,
                float(std))

    def walk(p, a, path):
        if surgery._is_linear_node(p):
            w = p["w"]
            c, s = int(w.shape[-2]), int(w.shape[-1])
            nb = surgery._batch_dims(a["w"])
            label = surgery.classify_path(path)
            std = EMBED_STD if label == "embed" else 1.0 / math.sqrt(c)
            if (label not in targets or surgery._is_conv(a["w"], nb)
                    or min(c, s) < lrd.min_dim):
                return {"w": leaf("normal", w.shape, w.dtype, std)}
            if (c, s) not in ranks:
                ranks[(c, s)] = rs.select_rank(
                    c, s, compression=lrd.compression, mode=lrd.rank_mode,
                    align=lrd.rank_align, rank_min_frac=lrd.rank_min_frac)
            r = ranks[(c, s)]
            if r == rs.ORG:
                return {"w": leaf("normal", w.shape, w.dtype, std)}
            lead = tuple(w.shape[:-2])
            return {"w0": leaf("normal", (*lead, c, r), w.dtype, std),
                    "w1": leaf("normal", (*lead, r, s), w.dtype,
                               1.0 / math.sqrt(r))}
        if isinstance(p, dict):
            return {k: walk(p[k], a[k], (*path, k)) for k in p}
        name = path[-1] if path else ""
        if name == "scale":
            return leaf("scale", p.shape, p.dtype, NORM_NOISE)
        if name == "bias":
            return leaf("zeros", p.shape, p.dtype, 0.0)
        fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
        return leaf("normal", p.shape, p.dtype, 1.0 / math.sqrt(fan_in))

    return walk(shapes, axes, ())


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[0], str)


def build(plan: Any, seed: int, device=None) -> Any:
    """Draw the tree of ``plan`` from ``seed`` in one jitted call on
    ``device`` (default: the first device)."""
    specs, treedef = jax.tree.flatten(plan, is_leaf=_is_spec)

    def make(key):
        out = []
        for i, (kind, shape, dtype, std) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "zeros":
                v = jnp.zeros(shape, jnp.float32)
            elif kind == "scale":
                v = 1.0 + std * jax.random.normal(k, shape, jnp.float32)
            else:
                v = std * jax.random.normal(k, shape, jnp.float32)
            out.append(v.astype(dtype))
        return out

    with jax.default_device(device or jax.devices()[0]):
        leaves = jax.jit(make)(seed_key(seed))
    return jax.tree.unflatten(treedef, leaves)


def ranks_of(plan: Any) -> dict[str, int]:
    """``{"/".join(path): rank}`` of every factor pair in ``plan``."""
    out = {}
    for path, node in _linear_nodes(plan):
        if "w0" in node:
            out[path] = node["w0"][1][-1]
    return out


def _linear_nodes(tree: Any, path: str = ""):
    if isinstance(tree, dict):
        if "w0" in tree or "w" in tree:
            yield path, tree
            return
        for k, v in tree.items():
            yield from _linear_nodes(v, f"{path}/{k}" if path else k)
