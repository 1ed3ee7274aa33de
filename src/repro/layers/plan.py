"""LinearPlan — the single execution-plan seam for every linear flavour.

The paper's central tension (its §1 "more layers = more latency"
complaint) is that decomposition shrinks *parameters* but doubles layer
*depth*: a dense ``y = x W`` becomes the chain ``y = (x W0) W1``
(Eq. 5), or the branched block-diagonal form of **Eq. 17**

    y = sum_j ((x @ u_j) @ xc_j) @ v_j

whose per-branch factors ``u_j (C, r1)``, ``xc_j (r1, r2)``,
``v_j (r2, S)`` are exactly the :class:`FactorSpec` entries of a
``kind="branched"`` plan (``u`` / ``xc`` / ``v`` carry the stacked
``(N, ., .)`` branch axis).  The Tucker-2 conv triple (paper Fig. 1b)
maps the same way: ``tucker_u`` / ``core`` / ``tucker_v`` are the three
FactorSpecs of a ``kind="tucker_conv"`` plan.

Before this module, every consumer re-derived "what kind of linear is
this and how should it run" by sniffing dict keys: ``apply_linear`` /
``apply_conv`` if-chains, the ``*_q``/``*_scale`` convention from
:mod:`repro.quant`, per-op VMEM-fit checks in :mod:`repro.kernels.ops`,
and ``parallel/sharding.py`` was blind to quantized keys entirely.  A
:class:`LinearPlan` centralizes that seam:

* **kind** — ``dense | lowrank | branched | tucker_conv |
  branched_tucker_conv``, classified once from the keys present
  (quantized or not);
* **per-factor** :class:`FactorSpec` — logical name, shape/dtype,
  whether the value lives as a plain array, a quantized
  ``k_q``/``k_scale`` pair, or a 2:4-packed ``k_sp``/``k_idx``
  (+ optional ``k_scale``) triple, and the freeze policy (paper §2.2:
  the teacher-derived factors receive no gradient);
* **kernel eligibility + VMEM fit** — :meth:`LinearPlan.kernel_for`
  decides fused-Pallas vs jnp-reference once, using the kernels' own
  footprint formulas (``repro.kernels.ops.kernel_fits``).  Leading batch
  dims are flattened by the kernel wrappers, so decode-shaped
  ``(B, 1, d)`` activations are eligible (the old ``x.ndim == 2`` gate
  is gone);
* **accounting** — ``param_count`` (logical weights; scales are *not*
  model parameters), ``quant_bytes`` (quantized storage incl. scales,
  reported separately), ``weight_bytes`` (HBM bytes the weight stream
  moves), ``flops_per_token``.

Plans are static metadata — no array refs — so they are built once per
distinct subtree geometry (an internal cache keyed on
``(key, shape, dtype)`` tuples) and are safe to build from
``ShapeDtypeStruct`` trees, traced values, or concrete arrays alike.
``build_plan_tree`` maps a whole param tree to its plans (the serve
engine does this at load time).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.quant.quantize import (IDX_SUFFIX as _IDX_SUFFIX,
                                  QUANT_SUFFIX as _QUANT_SUFFIX,
                                  SCALE_SUFFIX as _SCALE_SUFFIX,
                                  SP_SUFFIX as _SP_SUFFIX)

PyTree = Any

KIND_DENSE = "dense"
KIND_LOWRANK = "lowrank"
KIND_BRANCHED = "branched"
KIND_TUCKER_CONV = "tucker_conv"
KIND_BRANCHED_TUCKER_CONV = "branched_tucker_conv"

#: kinds executable by apply_linear / LinearPlan.execute
LINEAR_KINDS = (KIND_DENSE, KIND_LOWRANK, KIND_BRANCHED)
#: kinds executable only through apply_conv (spatial weights)
CONV_KINDS = (KIND_TUCKER_CONV, KIND_BRANCHED_TUCKER_CONV)

# Factor names per kind, in execution (chain) order, plus which of them
# the §2.2 freeze policy stops gradients through (the teacher-derived
# outer factors; the trainable core/xc keeps its gradient).
_KIND_FACTORS: dict[str, tuple[str, ...]] = {
    KIND_DENSE: ("w",),
    KIND_LOWRANK: ("w0", "w1"),
    KIND_BRANCHED: ("u", "xc", "v"),
    KIND_TUCKER_CONV: ("tucker_u", "core", "tucker_v"),
    KIND_BRANCHED_TUCKER_CONV: ("u", "core", "v"),
}
_KIND_FROZEN: dict[str, frozenset] = {
    KIND_DENSE: frozenset(),
    KIND_LOWRANK: frozenset({"w0"}),
    KIND_BRANCHED: frozenset({"u", "v"}),
    KIND_TUCKER_CONV: frozenset({"tucker_u", "tucker_v"}),
    KIND_BRANCHED_TUCKER_CONV: frozenset({"u", "v"}),
}


@dataclasses.dataclass(frozen=True)
class FactorSpec:
    """One factor of a (possibly decomposed, possibly quantized) linear.

    Static metadata only: the arrays themselves stay in the param tree
    and are fetched by :meth:`LinearPlan.value` at execution time.
    """

    name: str                      # logical key ("w0", "xc", "tucker_u", ...)
    shape: tuple[int, ...]         # logical (unquantized, dense) shape
    dtype: Any                     # value dtype (q/packed dtype when narrow)
    quantized: bool                # stored as name_q / name_scale pair
    frozen: bool                   # §2.2: stop_gradient under freeze policy
    scale_shape: tuple[int, ...] | None = None
    sparsity: str | None = None    # "2:4" when stored name_sp / name_idx
    idx_shape: tuple[int, ...] | None = None

    @property
    def density(self) -> float:
        """Kept fraction of the logical values (1.0 when dense)."""
        if self.sparsity is None:
            return 1.0
        keep, group = (int(t) for t in self.sparsity.split(":"))
        return keep / group

    @property
    def size(self) -> int:
        return int(math.prod(self.shape))

    @property
    def stored_size(self) -> int:
        """Values actually stored (the 2:4 packing keeps half of them)."""
        return int(round(self.size * self.density))

    @property
    def bytes(self) -> int:
        """HBM bytes this factor's storage occupies (incl. scale and
        sparse-index metadata)."""
        n = self.stored_size * jnp.dtype(self.dtype).itemsize
        if self.idx_shape is not None:
            n += int(math.prod(self.idx_shape))         # int8 indices
        if self.quantized and self.scale_shape is not None:
            n += int(math.prod(self.scale_shape)) * 4   # f32 scales
        return n


def _spec_from(p: dict, kind: str, name: str) -> FactorSpec:
    frozen = name in _KIND_FROZEN[kind]
    if name in p:
        v = p[name]
        return FactorSpec(name, tuple(int(d) for d in v.shape),
                          jnp.dtype(v.dtype), False, frozen)
    if name + _SP_SUFFIX in p:
        # 2:4-packed factor: slot-major (..., 2, G, S) values + index
        # metadata; the logical dense shape has 4G input rows.
        sp = p[name + _SP_SUFFIX]
        idx = p[name + _IDX_SUFFIX]
        scale = p.get(name + _SCALE_SUFFIX)
        shape = (*(int(d) for d in sp.shape[:-3]),
                 4 * int(sp.shape[-2]), int(sp.shape[-1]))
        return FactorSpec(name, shape, jnp.dtype(sp.dtype),
                          scale is not None, False,
                          tuple(int(d) for d in scale.shape)
                          if scale is not None else None,
                          sparsity="2:4",
                          idx_shape=tuple(int(d) for d in idx.shape))
    q = p[name + _QUANT_SUFFIX]
    scale = p[name + _SCALE_SUFFIX]
    # Quantized factors carry no gradient (serve-time transform), so the
    # freeze policy is moot — record them unfrozen.
    return FactorSpec(name, tuple(int(d) for d in q.shape),
                      jnp.dtype(q.dtype), True, False,
                      tuple(int(d) for d in scale.shape))


@dataclasses.dataclass(frozen=True)
class LinearPlan:
    """How one linear subtree executes: kind, factors, kernel decision."""

    kind: str
    factors: tuple[FactorSpec, ...]

    # -- factor access ------------------------------------------------------

    def factor(self, name: str) -> FactorSpec:
        for f in self.factors:
            if f.name == name:
                return f
        raise KeyError(name)

    def value(self, p: dict, name: str, dtype=None, *,
              freeze: bool = False) -> jax.Array:
        """Fetch factor ``name`` from tree ``p``: dequantizes a
        ``k_q``/``k_scale`` pair on the fly (to ``dtype``, default bf16
        — the serving activation dtype), expands a 2:4-packed
        ``k_sp``/``k_idx`` factor back to dense, and applies the §2.2
        freeze policy to plain factors."""
        spec = self.factor(name)
        if spec.sparsity is not None:
            from repro.quant.sparse import expand_sparse
            return expand_sparse(p[name + _SP_SUFFIX], p[name + _IDX_SUFFIX],
                                 p.get(name + _SCALE_SUFFIX), dtype)
        if spec.quantized:
            from repro.quant.quantize import dequantize_array
            return dequantize_array(p[name + _QUANT_SUFFIX],
                                    p[name + _SCALE_SUFFIX],
                                    dtype or jnp.bfloat16)
        v = p[name]
        if freeze and spec.frozen:
            v = lax.stop_gradient(v)
        return v

    # -- derived geometry ---------------------------------------------------

    @property
    def quantized(self) -> bool:
        """Any factor stored quantized."""
        return any(f.quantized for f in self.factors)

    @property
    def fully_quantized(self) -> bool:
        """Every factor quantized — the fused-q kernels need all of them."""
        return all(f.quantized for f in self.factors)

    @property
    def sparse(self) -> bool:
        """Any factor stored 2:4-packed."""
        return any(f.sparsity is not None for f in self.factors)

    @property
    def d_in(self) -> int:
        return self.factors[0].shape[-2]

    @property
    def d_out(self) -> int:
        return self.factors[-1].shape[-1]

    @property
    def branches(self) -> int:
        if self.kind in (KIND_BRANCHED, KIND_BRANCHED_TUCKER_CONV):
            return self.factors[0].shape[-3]
        return 1

    # -- accounting ---------------------------------------------------------

    @property
    def param_count(self) -> int:
        """Stored model parameters.  Quantized / 2:4-packed values count
        (they *are* the weights, in narrow storage, at the *kept* count
        for sparse factors); the f32 ``*_scale`` rows and int8 ``*_idx``
        position metadata are codebook bookkeeping, not parameters —
        counting them skewed the compression ratios."""
        return sum(f.stored_size for f in self.factors)

    @property
    def quant_bytes(self) -> int:
        """Bytes of quantized storage (narrow values + scales) —
        reported separately from ``param_count``."""
        return sum(f.bytes for f in self.factors if f.quantized)

    @property
    def weight_bytes(self) -> int:
        """HBM bytes the weight stream moves per full pass (the decode
        roofline's memory term)."""
        return sum(f.bytes for f in self.factors)

    def matmul_chain(self) -> list[tuple[int, int, int]]:
        """The matmul chain as ``(mult, k, n)`` triples — ``mult``
        repetitions of an ``(M, k) @ (k, n)`` — for the cost model."""
        s = {f.name: f.shape for f in self.factors}
        if self.kind == KIND_DENSE:
            kh = kw = 1
            if len(s["w"]) >= 4:                      # spatial conv weight
                kh, kw = s["w"][-4], s["w"][-3]
            return [(1, kh * kw * s["w"][-2], s["w"][-1])]
        if self.kind == KIND_LOWRANK:
            c, r = s["w0"][-2], s["w0"][-1]
            return [(1, c, r), (1, r, s["w1"][-1])]
        if self.kind == KIND_BRANCHED:
            n = self.branches
            c, r1 = s["u"][-2], s["u"][-1]
            r2 = s["xc"][-1]
            return [(n, c, r1), (n, r1, r2), (n, r2, s["v"][-1])]
        if self.kind == KIND_TUCKER_CONV:
            c, r1 = s["tucker_u"][-2], s["tucker_u"][-1]
            kh, kw, _, r2 = s["core"][-4:]
            return [(1, c, r1), (1, kh * kw * r1, r2),
                    (1, r2, s["tucker_v"][-1])]
        n = self.branches                             # branched tucker
        c, r1 = s["u"][-2], s["u"][-1]
        kh, kw, _, r2 = s["core"][-4:]
        return [(n, c, r1), (n, kh * kw * r1, r2), (n, r2, s["v"][-1])]

    def chain_factors(self) -> tuple[FactorSpec, ...]:
        """Per-matmul :class:`FactorSpec`, aligned with
        :meth:`matmul_chain` — the weight operand of each dot."""
        return tuple(self.factor(name)
                     for name in _KIND_FACTORS[self.kind])

    def chain_density(self) -> tuple[float, ...]:
        """Per-matmul kept fraction, aligned with :meth:`matmul_chain`
        (2:4 factors feed sparsity-capable MXUs at half the FLOPs)."""
        return tuple(f.density for f in self.chain_factors())

    @property
    def flops_per_token(self) -> float:
        """Forward matmul FLOPs per input row (per output pixel for
        spatial conv kinds), density-scaled for 2:4 factors."""
        return sum(2.0 * mult * k * n * d
                   for (mult, k, n), d in zip(self.matmul_chain(),
                                              self.chain_density()))

    # -- kernel dispatch ----------------------------------------------------

    def kernel_for(self, x_shape: tuple[int, ...], use_pallas: bool,
                   act_quantize: bool = False) -> str | None:
        """Which fused Pallas kernel (if any) executes this plan for an
        activation of ``x_shape``.

        The kernel wrappers flatten leading batch dims themselves, so
        any ``(..., d_in)`` activation is eligible — including
        decode-shaped ``(B, 1, d)`` — the fit decision runs on
        ``M = prod(leading dims)``.  Returns one of ``"lowrank"``,
        ``"lowrank_q"``, ``"lowrank_qa"``, ``"lowrank_sq"``,
        ``"branched"``, ``"branched_q"``, ``"branched_qa"``,
        ``"branched_sq"`` or ``None`` (jnp reference path).

        ``act_quantize`` asks for the activation-quantized int8 x int8
        kernels; they engage only on fully-int8 non-sparse plans (fp8
        weights and 2:4 layouts keep their own kernels) and fall back
        to the weight-only dispatch when ineligible — the runner sets
        it for prefill/chunk segments, never decode.
        """
        if not use_pallas or len(x_shape) < 2:
            return None
        from repro.kernels import ops as kops
        m = int(math.prod(x_shape[:-1]))
        for name in self.kernel_candidates(act_quantize):
            if kops.kernel_fits(name, m, **self.fit_geometry(name)):
                return name
        return None

    def kernel_candidates(self, act_quantize: bool = False
                          ) -> tuple[str, ...]:
        """The fused kernels that can execute this plan's storage
        layout, most preferred first; empty where only the jnp path can
        (dense and conv kinds, stacked factors, mixed layouts).  Which
        one runs is then a VMEM-fit question (:meth:`kernel_for`)."""
        if self.kind not in (KIND_LOWRANK, KIND_BRANCHED):
            return ()
        # Stacked (scan-dim) factors never reach the kernels directly.
        want_ndim = 2 if self.kind == KIND_LOWRANK else 3
        if any(len(f.shape) != want_ndim for f in self.factors):
            return ()
        base = "lowrank" if self.kind == KIND_LOWRANK else "branched"
        if self.sparse:
            # The fused sq kernels want the canonical compound layout:
            # every sparse factor also int8 (sp + idx + scale), and for
            # branched the small core plain-int8 (sparsity excluded from
            # its default targets).  Anything else — bf16-sparse
            # (mode="none") or a partial sparse_targets mix — expands
            # through the reference path.
            if self.kind == KIND_LOWRANK:
                ok = all(f.sparsity is not None and f.quantized
                         for f in self.factors)
            else:
                u, xc, v = (self.factor(n) for n in ("u", "xc", "v"))
                ok = (u.sparsity is not None and u.quantized
                      and v.sparsity is not None and v.quantized
                      and xc.quantized and xc.sparsity is None)
            return (base + "_sq",) if ok else ()
        # Mixed plain/quantized subtrees (partial quant_targets) take
        # the dequant reference path.
        if self.quantized and not self.fully_quantized:
            return ()
        names = []
        if (act_quantize and self.fully_quantized
                and all(jnp.dtype(f.dtype) == jnp.int8
                        for f in self.factors)):
            names.append(base + "_qa")
        names.append(base + ("_q" if self.fully_quantized else ""))
        return tuple(names)

    def fit_geometry(self, name: str) -> dict:
        """``kernel_fits`` keyword geometry of kernel ``name`` for this
        plan (M aside)."""
        chain = self.matmul_chain()
        q_bytes = (jnp.dtype(self.factors[0].dtype).itemsize
                   if self.fully_quantized else 1)
        if name.startswith("lowrank"):
            return dict(c=chain[0][1], r=chain[0][2], s=self.d_out,
                        q_bytes=q_bytes)
        return dict(c=chain[0][1], r1=chain[0][2], r2=chain[1][2],
                    s=self.d_out, q_bytes=q_bytes)

    def unstacked(self) -> "LinearPlan":
        """The plan one step of a layer scan executes: leading stack
        (layers / experts) dims dropped from every factor."""
        if self.kind in CONV_KINDS:
            return self
        nd = 2 if self.kind in (KIND_DENSE, KIND_LOWRANK) else 3
        return dataclasses.replace(self, factors=tuple(
            dataclasses.replace(f, shape=f.shape[-nd:])
            for f in self.factors))

    # -- execution ----------------------------------------------------------

    def execute(self, p: dict, x: jax.Array, *,
                freeze_factors: bool = False, use_pallas: bool = False,
                act_quantize: bool = False,
                accum_dtype=jnp.float32) -> jax.Array:
        """Apply this plan's linear op to ``x`` (..., d_in).

        Thin executor: one kernel-or-reference decision, then the
        matmul chain.  Spatial conv kinds execute through
        :func:`repro.layers.conv.apply_conv` instead.
        """
        if self.kind not in LINEAR_KINDS:
            raise ValueError(
                f"kind {self.kind!r} is a conv plan; use apply_conv")
        if self.kind == KIND_DENSE:
            return _matmul(x, self.value(p, "w", x.dtype,
                                         freeze=freeze_factors),
                           accum_dtype)
        kernel = self.kernel_for(x.shape, use_pallas, act_quantize)
        from repro.kernels import ops as kops
        if self.kind == KIND_LOWRANK:
            if kernel == "lowrank_qa":
                return kops.lowrank_matmul_qa(
                    x, p["w0_q"], p["w0_scale"], p["w1_q"], p["w1_scale"],
                    force_kernel=True)
            if kernel == "lowrank_sq":
                return kops.lowrank_matmul_sq(
                    x, p["w0_sp"], p["w0_idx"], p["w0_scale"],
                    p["w1_sp"], p["w1_idx"], p["w1_scale"],
                    force_kernel=True)
            if kernel == "lowrank_q":
                return kops.lowrank_matmul_q(
                    x, p["w0_q"], p["w0_scale"], p["w1_q"], p["w1_scale"],
                    force_kernel=True)
            w0 = self.value(p, "w0", x.dtype, freeze=freeze_factors)
            w1 = self.value(p, "w1", x.dtype, freeze=freeze_factors)
            if kernel == "lowrank":
                return kops.lowrank_matmul(x, w0, w1, force_kernel=True)
            h = _matmul(x, w0, accum_dtype)
            return _matmul(h, w1, accum_dtype)
        # branched: y = sum_j ((x @ u_j) @ xc_j) @ v_j   (paper Eq. 17)
        if kernel == "branched_qa":
            return kops.branched_matmul_qa(
                x, p["u_q"], p["u_scale"], p["xc_q"], p["xc_scale"],
                p["v_q"], p["v_scale"], force_kernel=True)
        if kernel == "branched_sq":
            return kops.branched_matmul_sq(
                x, p["u_sp"], p["u_idx"], p["u_scale"],
                p["xc_q"], p["xc_scale"],
                p["v_sp"], p["v_idx"], p["v_scale"], force_kernel=True)
        if kernel == "branched_q":
            return kops.branched_matmul_q(
                x, p["u_q"], p["u_scale"], p["xc_q"], p["xc_scale"],
                p["v_q"], p["v_scale"], force_kernel=True)
        u = self.value(p, "u", x.dtype, freeze=freeze_factors)
        xc = self.value(p, "xc", x.dtype, freeze=freeze_factors)
        v = self.value(p, "v", x.dtype, freeze=freeze_factors)
        if kernel == "branched":
            return kops.branched_matmul(x, u, xc, v, force_kernel=True)
        h = jnp.einsum("...d,ndr->n...r", x, u,
                       preferred_element_type=accum_dtype).astype(x.dtype)
        h = jnp.einsum("n...r,nrs->n...s", h, xc,
                       preferred_element_type=accum_dtype).astype(x.dtype)
        y = jnp.einsum("n...s,nso->...o", h, v,
                       preferred_element_type=accum_dtype)
        return y.astype(x.dtype)


def _matmul(x: jax.Array, w: jax.Array, accum_dtype) -> jax.Array:
    y = jnp.einsum("...d,do->...o", x, w, preferred_element_type=accum_dtype)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Plan construction (cached — built once per distinct subtree geometry)
# ---------------------------------------------------------------------------

def _has(p: dict, key: str) -> bool:
    return key in p or key + _QUANT_SUFFIX in p or key + _SP_SUFFIX in p


def classify(p: dict) -> str:
    """Kind of a linear/conv subtree from the keys present (quantized
    ``k_q``/``k_scale`` and 2:4-packed ``k_sp``/``k_idx`` trees classify
    as their dense originals)."""
    if _has(p, "w"):
        return KIND_DENSE
    if _has(p, "tucker_u"):
        return KIND_TUCKER_CONV
    if _has(p, "xc"):
        return KIND_BRANCHED
    if _has(p, "core"):
        return KIND_BRANCHED_TUCKER_CONV
    if _has(p, "w0"):
        return KIND_LOWRANK
    raise ValueError(f"not a linear param subtree: {sorted(p)}")


def is_linear_subtree(node: Any) -> bool:
    """Does this dict node hold the factors of one linear/conv op?"""
    if not isinstance(node, dict):
        return False
    for key in ("w", "w0", "xc", "tucker_u", "core", "u"):
        v = node.get(key, node.get(key + _QUANT_SUFFIX,
                                   node.get(key + _SP_SUFFIX)))
        if v is not None and hasattr(v, "shape"):
            return True
    return False


_PLAN_CACHE: dict[tuple, LinearPlan] = {}


def _cache_key(p: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(int(d) for d in v.shape), jnp.dtype(v.dtype).name)
        for k, v in p.items()))


def build_plan(p: dict) -> LinearPlan:
    """The plan for one linear subtree.  Static metadata only, cached on
    the subtree's ``(key, shape, dtype)`` geometry — safe under jit
    tracing and on ``ShapeDtypeStruct`` trees."""
    key = _cache_key(p)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        kind = classify(p)
        factors = tuple(_spec_from(p, kind, name)
                        for name in _KIND_FACTORS[kind])
        plan = LinearPlan(kind=kind, factors=factors)
        _PLAN_CACHE[key] = plan
    return plan


def build_plan_tree(params: PyTree) -> PyTree:
    """Map every linear/conv subtree of a param tree to its LinearPlan
    (other subtrees recurse; non-linear leaves map to ``None``).

    The serve engine calls this once at load so every plan (and its
    kernel decision) exists before the first token, and uses the result
    for weight-stream accounting."""
    def walk(node: Any) -> Any:
        if is_linear_subtree(node):
            return build_plan(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return None
    return walk(params)


def tree_summary(plan_tree: PyTree) -> dict:
    """Aggregate accounting over a ``build_plan_tree`` result."""
    plans = [x for x in jax.tree.leaves(
        plan_tree, is_leaf=lambda n: isinstance(n, LinearPlan))
        if isinstance(x, LinearPlan)]
    return {
        "linears": len(plans),
        "by_kind": {k: sum(1 for p in plans if p.kind == k)
                    for k in sorted({p.kind for p in plans})},
        "quantized": sum(1 for p in plans if p.quantized),
        "sparse": sum(1 for p in plans if p.sparse),
        "param_count": sum(p.param_count for p in plans),
        "weight_bytes": sum(p.weight_bytes for p in plans),
        "quant_bytes": sum(p.quant_bytes for p in plans),
    }


def kernel_census(plan_tree: PyTree, m: int, *, use_pallas: bool,
                  act_quantize: bool = False) -> dict:
    """How every linear of a ``build_plan_tree`` result executes for an
    activation of ``m`` rows: a count per fused kernel, ``"ref"`` for
    plans a kernel serves but whose geometry does not fit its VMEM
    budget (the jnp reference runs instead), and ``"jnp"`` for plans
    no kernel serves (dense kinds, or ``use_pallas`` off).  Stacked
    plans count once per scan step, at the per-step geometry; MoE
    expert banks (under an ``experts`` key) run as grouped einsums."""
    from repro.kernels import ops as kops
    out: dict[str, int] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            plan_tree, is_leaf=lambda n: isinstance(n, LinearPlan))[0]:
        if not isinstance(leaf, LinearPlan):
            continue
        plan = leaf.unstacked()
        reps = max(1, leaf.factors[0].size // plan.factors[0].size)
        experts = any(getattr(k, "key", None) == "experts" for k in path)
        names = (plan.kernel_candidates(act_quantize)
                 if use_pallas and not experts else ())
        path = "jnp" if not names else next(
            (n for n in names
             if kops.vmem_fits(n, m, **plan.fit_geometry(n))), "ref")
        out[path] = out.get(path, 0) + reps
    return dict(sorted(out.items()))
