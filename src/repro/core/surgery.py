"""Whole-model LRD surgery — applies the paper's technique to a param tree.

``decompose_model(params, axes, lrd)`` walks the ``(params, axes)`` trees
produced by :class:`repro.layers.param.ParamBuilder`, classifies every
linear subtree (``{"w": ...}``) by its path, decides a rank per
:mod:`repro.core.rank_selection`, and rewrites the subtree in place:

    {"w": (.., C, S)}          dense
      -> {"w0": (.., C, R), "w1": (.., R, S)}                  SVD pair
      -> {"u": (.., N, C, r), "xc": (.., N, r, r),
          "v": (.., N, r, S)}                                  branched
      -> unchanged ("ORG")     when Algorithm 1 keeps the original layer

Stacked-layer weights (leading ``layers`` axis) and MoE expert banks
(leading ``experts`` axis) decompose batched — every layer in a stack
shares geometry, hence rank, which keeps ``lax.scan`` homogeneous.

4D conv weights (ResNet path) go through Tucker-2 instead:

    {"w": (k, k, C, S)} -> {"tucker_u": (C, R1), "core": (k, k, R1, R2),
                            "tucker_v": (R2, S)}
    or branched          -> {"u": (N, C, r1), "core": (N, k, k, r1, r2),
                             "v": (N, r2, S)}

Model code never changes: ``apply_linear`` / ``apply_conv`` dispatch on the
keys present.  The surgery also emits a :class:`SurgeryReport` with the
per-layer decisions and param/FLOP accounting.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import LRDConfig
from repro.core import cost_model as cm
from repro.core import rank_selection as rs
from repro.core.branching import branch_svd, branch_tucker, quantize_ranks
from repro.core.svd import decompose_auto, ratio_rank
from repro.core.tucker import ratio_ranks, tucker2_decompose
from repro.layers.param import BRANCH, CONV, EXPERTS, LAYERS, RANK

PyTree = Any


# ---------------------------------------------------------------------------
# Path classification
# ---------------------------------------------------------------------------

#: map from a path component pair (parent, leaf-ish) to a target label.
_LABELS: dict[tuple[str, str], str] = {
    ("attn", "q"): "attn_q", ("attn", "k"): "attn_k",
    ("attn", "v"): "attn_v", ("attn", "o"): "attn_o",
    ("cross_attn", "q"): "attn_q", ("cross_attn", "k"): "attn_k",
    ("cross_attn", "v"): "attn_v", ("cross_attn", "o"): "attn_o",
    ("mla", "o"): "attn_o",
    ("mla", "q_a"): "mla_qa", ("mla", "q_b"): "mla_qb",
    ("mla", "kv_a"): "mla_kva", ("mla", "kv_b"): "mla_kvb",
    ("mlp", "up"): "ffn_up", ("mlp", "gate"): "ffn_gate",
    ("mlp", "down"): "ffn_down",
    ("shared", "up"): "ffn_up", ("shared", "gate"): "ffn_gate",
    ("shared", "down"): "ffn_down",
    ("experts", "up"): "moe_up", ("experts", "gate"): "moe_gate",
    ("experts", "down"): "moe_down",
    ("ssm", "in_proj"): "ssm_in", ("ssm", "out_proj"): "ssm_out",
}


def classify_path(path: tuple[str, ...]) -> str:
    """Target label for a linear subtree at ``path`` (ends at the subtree)."""
    if not path:
        return "unknown"
    leaf = path[-1]
    parent = path[-2] if len(path) > 1 else ""
    if (parent, leaf) in _LABELS:
        return _LABELS[(parent, leaf)]
    if leaf == "unembed":
        return "unembed"
    if leaf == "embed":
        return "embed"
    if leaf == "router":
        return "router"
    if leaf.startswith("conv") or leaf == "downsample":
        return "conv"
    if leaf == "fc":
        return "fc"
    return leaf


@dataclasses.dataclass
class LayerDecision:
    path: str
    label: str
    kind: str                  # "svd" | "branched" | "tucker" | "org" | "skip"
    shape: tuple[int, ...]
    rank: int | tuple[int, int] | None
    params_before: int
    params_after: int
    flops_before: float        # per input row/pixel (forward)
    flops_after: float
    reason: str = ""


@dataclasses.dataclass
class SurgeryReport:
    decisions: list[LayerDecision] = dataclasses.field(default_factory=list)

    @property
    def params_before(self) -> int:
        return sum(d.params_before for d in self.decisions)

    @property
    def params_after(self) -> int:
        return sum(d.params_after for d in self.decisions)

    @property
    def decomposed(self) -> list[LayerDecision]:
        return [d for d in self.decisions if d.kind not in ("org", "skip")]

    def summary(self) -> dict:
        fb = sum(d.flops_before for d in self.decisions)
        fa = sum(d.flops_after for d in self.decisions)
        return {
            "layers_seen": len(self.decisions),
            "layers_decomposed": len(self.decomposed),
            "params_before": self.params_before,
            "params_after": self.params_after,
            "param_ratio": self.params_after / max(1, self.params_before),
            "flops_ratio": fa / max(1e-30, fb),
        }


# ---------------------------------------------------------------------------
# Per-subtree decomposition
# ---------------------------------------------------------------------------

def _is_linear_node(node: Any) -> bool:
    return (isinstance(node, dict) and set(node) == {"w"}
            and hasattr(node["w"], "ndim"))


def _batch_dims(ax: tuple) -> int:
    n = 0
    for a in ax:
        if a in (LAYERS, EXPERTS):
            n += 1
        else:
            break
    return n


def _is_conv(ax: tuple, nd_batch: int) -> bool:
    core = ax[nd_batch:]
    return len(core) == 4 and core[0] == CONV and core[1] == CONV


def _decide_rank(c: int, s: int, lrd: LRDConfig, m_tokens: int,
                 _cache: dict) -> int:
    key = (c, s)
    if key not in _cache:
        _cache[key] = rs.select_rank(
            c, s, compression=lrd.compression, mode=lrd.rank_mode,
            align=lrd.rank_align, rank_min_frac=lrd.rank_min_frac,
            m_tokens=m_tokens)
    return _cache[key]


def _decompose_linear(w: jax.Array, ax: tuple, lrd: LRDConfig,
                      m_tokens: int, cache: dict
                      ) -> tuple[dict | None, dict | None, str, Any]:
    """Returns (new_params, new_axes, kind, rank) or (None,..,"org"/reason)."""
    nb = _batch_dims(ax)
    c, s = int(w.shape[-2]), int(w.shape[-1])
    if min(c, s) < lrd.min_dim:
        return None, None, "skip", f"min_dim({min(c, s)}<{lrd.min_dim})"
    rank = _decide_rank(c, s, lrd, m_tokens, cache)
    if rank == rs.ORG:
        return None, None, "org", "algorithm1: dense layer faster"
    batch_ax = ax[:nb]
    in_ax, out_ax = ax[-2], ax[-1]
    n = lrd.branches
    if n > 1 and rank // n >= max(lrd.rank_align, 1):
        f = branch_svd(w, rank, n)
        params = {"u": f.u, "xc": f.xc, "v": f.v}
        axes = {"u": (*batch_ax, BRANCH, in_ax, RANK),
                "xc": (*batch_ax, BRANCH, RANK, RANK),
                "v": (*batch_ax, BRANCH, RANK, out_ax)}
        return params, axes, "branched", quantize_ranks(rank, rank, n)[0]
    f = decompose_auto(w, rank)
    params = {"w0": f.w0, "w1": f.w1}
    axes = {"w0": (*batch_ax, in_ax, RANK), "w1": (*batch_ax, RANK, out_ax)}
    return params, axes, "svd", rank


def _decompose_conv(w: jax.Array, ax: tuple, lrd: LRDConfig,
                    m_tokens: int) -> tuple[dict | None, dict | None, str, Any]:
    kh, kw, c, s = (int(d) for d in w.shape)
    if min(c, s) < lrd.min_dim // 4:     # convs are smaller than FC layers
        return None, None, "skip", f"min_dim({min(c, s)})"
    r1, r2 = ratio_ranks(c, s, kh, lrd.compression)
    if lrd.rank_mode == "aligned":
        r1 = rs.align_rank(r1, min(lrd.rank_align, max(8, c // 2)))
        r2 = rs.align_rank(r2, min(lrd.rank_align, max(8, s // 2)))
    elif lrd.rank_mode == "search":
        m_hw = int(m_tokens ** 0.5) or 1
        t_dense = cm.conv_time(m_hw, c, s, kh)
        beta = s / c
        timer = cm.make_model_timer(m_tokens, c, s, kind="tucker", k=kh,
                                    beta=beta)
        dec = rs.algorithm1(timer, t_dense, r1, max(1, int(r1 * lrd.rank_min_frac)),
                            step=1 if r1 <= 512 else 8)
        if dec.rank == rs.ORG:
            return None, None, "org", "algorithm1: dense conv faster"
        r1 = dec.rank
        r2 = max(1, int(round(beta * r1)))
    n = lrd.branches
    if n > 1 and min(r1, r2) // n >= 8:
        f = branch_tucker(w, r1, r2, n)
        params = {"u": f.u, "core": f.core, "v": f.v}
        axes = {"u": (BRANCH, ax[-2], RANK),
                "core": (BRANCH, CONV, CONV, RANK, RANK),
                "v": (BRANCH, RANK, ax[-1])}
        return params, axes, "branched_tucker", quantize_ranks(r1, r2, n)
    f = tucker2_decompose(w, r1, r2)
    params = {"tucker_u": f.u, "core": f.core, "tucker_v": f.v}
    axes = {"tucker_u": (ax[-2], RANK), "core": (CONV, CONV, RANK, RANK),
            "tucker_v": (RANK, ax[-1])}
    return params, axes, "tucker", (r1, r2)


def _count(tree: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def _fwd_flops(params: dict | jax.Array, conv: bool) -> float:
    """Forward FLOPs per row (linear) or per output pixel (conv)."""
    from repro.layers.param import linear_flops
    if conv:
        if isinstance(params, dict) and "w" in params:
            kh, kw, c, s = params["w"].shape[-4:]
            return 2.0 * kh * kw * c * s
        if "tucker_u" in params:
            c, r1 = params["tucker_u"].shape[-2:]
            kh, kw, _, r2 = params["core"].shape[-4:]
            s = params["tucker_v"].shape[-1]
            return 2.0 * (c * r1 + kh * kw * r1 * r2 + r2 * s)
        # branched tucker
        n, c, r1 = params["u"].shape[-3:]
        _, kh, kw, _, r2 = params["core"].shape[-5:]
        s = params["v"].shape[-1]
        return 2.0 * n * (c * r1 + kh * kw * r1 * r2 + r2 * s)
    # linear: reuse the layers accounting on the innermost 2 dims
    leaf = {k: v[(0,) * (v.ndim - (3 if k in ("u", "xc", "v") else 2))]
            if v.ndim > (3 if k in ("u", "xc", "v") else 2) else v
            for k, v in params.items()}
    return linear_flops(leaf, 1)


# ---------------------------------------------------------------------------
# The tree walker
# ---------------------------------------------------------------------------

def decompose_model(params: PyTree, axes: PyTree, lrd: LRDConfig, *,
                    m_tokens: int = 4096,
                    exclude: Callable[[str], bool] | None = None,
                    ) -> tuple[PyTree, PyTree, SurgeryReport]:
    """Apply LRD to every targeted linear/conv subtree. Pure function of the
    input trees; returns rewritten copies plus the decision report."""
    report = SurgeryReport()
    if not lrd.enabled:
        return params, axes, report
    targets = set(lrd.targets)
    rank_cache: dict = {}

    def walk(p: Any, a: Any, path: tuple[str, ...]) -> tuple[Any, Any]:
        if _is_linear_node(p):
            label = classify_path(path)
            w, ax = p["w"], a["w"]
            nb = _batch_dims(ax)
            conv = _is_conv(ax, nb)
            pstr = "/".join(path)
            if conv and int(w.shape[0]) == 1 and int(w.shape[1]) == 1:
                # 1x1 convs are FC layers (paper Fig. 1a): SVD, not Tucker
                label = "conv1x1"
                conv = False
                conv1x1 = True
            else:
                conv1x1 = False
            before_params, before_flops = _count(p), _fwd_flops(p, conv)
            if label not in targets:
                report.decisions.append(LayerDecision(
                    pstr, label, "skip", tuple(w.shape), None,
                    before_params, before_params, before_flops, before_flops,
                    "label not targeted"))
                return p, a
            if conv:
                np_, na, kind, rank = _decompose_conv(w, ax, lrd, m_tokens)
            elif conv1x1:
                w2 = w.reshape(w.shape[-2], w.shape[-1])
                np_, na, kind, rank = _decompose_linear(
                    w2, ax[-2:], lrd, m_tokens, rank_cache)
            else:
                np_, na, kind, rank = _decompose_linear(w, ax, lrd, m_tokens,
                                                        rank_cache)
            if np_ is None:
                report.decisions.append(LayerDecision(
                    pstr, label, kind, tuple(w.shape), None,
                    before_params, before_params, before_flops, before_flops,
                    str(rank)))
                return p, a
            report.decisions.append(LayerDecision(
                pstr, label, kind, tuple(w.shape), rank,
                before_params, _count(np_), before_flops,
                _fwd_flops(np_, conv)))
            return np_, na
        if isinstance(p, dict):
            new_p, new_a = {}, {}
            for k in p:
                if exclude is not None and exclude("/".join((*path, k))):
                    new_p[k], new_a[k] = p[k], a[k]
                    continue
                new_p[k], new_a[k] = walk(p[k], a[k], (*path, k))
            return new_p, new_a
        return p, a

    new_params, new_axes = walk(params, axes, ())
    return new_params, new_axes, report


# ---------------------------------------------------------------------------
# 2:4 sparsification pass (compound compression, after decomposition)
# ---------------------------------------------------------------------------

def sparsify_model(params: PyTree, axes: PyTree, lrd: LRDConfig, *,
                   mode: str | None = None) -> tuple[PyTree, PyTree]:
    """Magnitude-based 2:4 sparsification of the decomposed factors.

    The third compression axis, applied *after* :func:`decompose_model`:
    every ``lrd.sparse_targets`` factor whose input dim divides the
    group size is rewritten to the packed ``k_sp``/``k_idx``
    (+ ``k_scale``) convention of :mod:`repro.quant.sparse` — keeping,
    per group of 4 input rows, the 2 with the largest L1 row norm
    (mask shared across the output axis, so the index metadata costs
    one int8 per group instead of two bits per value).  ``mode``
    defaults to ``lrd.quantize``: when the factors are also being
    quantized the kept values pack straight to the narrow dtype
    (compound 2:4 x int8); otherwise they stay in the source dtype
    (reference-path only — no fused kernel serves bf16-sparse).

    Returns rewritten ``(params, axes)``; a no-op when
    ``lrd.sparsify == "none"``.
    """
    if lrd.sparsify == "none":
        return params, axes
    from repro.quant.sparse import sparsify_tree
    quant = lrd.quantize if mode is None else mode
    return sparsify_tree(params, pattern=lrd.sparsify,
                         mode=quant if quant != "none" else "none",
                         targets=lrd.sparse_targets, axes=axes)
