"""CachePlan — the declarative execution-plan seam for every KV-cache family.

The serve stack grew three parallel cache layouts: plain GQA K/V pools,
int8-quantized GQA pools (:mod:`repro.quant.kv`), and the MLA latent
cache — and :mod:`repro.layers.attention` dispatched between them by
sniffing raw dict keys (``"k"`` vs ``"k_q"``/``"k_scale"`` vs ``"ckv"``)
in three places per segment kind.  Every new family multiplied the
branching, and the byte accounting in :mod:`repro.serve.pool` and
:mod:`repro.core.cost_model` re-derived the layouts by hand.

A :class:`CachePlan` is the cache twin of :class:`repro.layers.plan.
LinearPlan`: one plan per attention layer declaring

* **family** — ``gqa_f32 | gqa_int8 | mla_latent | mla_latent_int8 |
  gqa_paged_f32 | gqa_paged_int8`` (``*_f32``/unsuffixed families hold
  the model dtype, f32 *or* bf16; the name records "full width"; the
  paged families lay K/V out as fixed-size physical blocks addressed
  through per-stream block tables — see :class:`PagedGeometry` and
  :mod:`repro.serve.paging`);
* **leaves** — per-leaf :class:`CacheLeafSpec` (shape template, dtype,
  which axis is the sequence axis, and the quantized-pair ref tying a
  ``*_q`` value leaf to its ``*_scale`` row);
* **bytes** — ``bytes_per_token`` (per-position bytes of one stream),
  ``bytes_per_slot`` (per-slot constants: the f32 scale rows) and
  ``bytes_per_step(slots, seq)`` (the full-pool decode read) — the
  single source of truth behind :class:`repro.serve.pool.KVPoolManager`
  accounting and the roofline's ``kv_bytes`` term;
* **executors** — the write path for all three segment kinds
  (:meth:`write_prefill`, :meth:`write_chunk`, :meth:`write_decode`)
  and the cache-coupled decode attention (:meth:`attend_decode` for GQA
  families, :meth:`attend_decode_latent` for the MLA absorbed form,
  which dispatches the fused int8 kernels behind the shared
  ``ops.kernel_fits`` gate).

Every write executor takes either one layer's cache or, given a traced
``layer`` index, the whole stack ``(L, ...)`` that the model's layer
scan carries: the write then lands at ``[layer, ...]`` of the stacked
buffer in place, and attention reads its layer out of the stack once
(:meth:`CachePlan.layer_view`).  A layer's cache taken out of the stack
and written back whole would cost a copy of the layer each way.

``apply_attention`` / ``apply_mla`` are thin executors over the plan:
they own projections, RoPE, and the prefill softmax (which runs on the
full-precision values computed in-layer, never on the cache), while the
plan owns every layout-dependent decision.  :func:`plan_from_cache` is
the ONE place left that classifies a cache dict by its keys — the
fallback when a caller does not thread a plan explicitly.

Plans are static metadata (no array refs), cached per geometry, and safe
to close over inside ``jax.jit``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.quant import kv as kvq

PyTree = Any

FAMILY_GQA = "gqa_f32"
FAMILY_GQA_INT8 = "gqa_int8"
FAMILY_MLA = "mla_latent"
FAMILY_MLA_INT8 = "mla_latent_int8"
FAMILY_GQA_PAGED = "gqa_paged_f32"
FAMILY_GQA_PAGED_INT8 = "gqa_paged_int8"

FAMILIES = (FAMILY_GQA, FAMILY_GQA_INT8, FAMILY_MLA, FAMILY_MLA_INT8,
            FAMILY_GQA_PAGED, FAMILY_GQA_PAGED_INT8)

#: sequence-axis position (from the right) of every per-position cache
#: leaf, by key — K/V pools are (..., S, KH, hd), latents (..., S, r).
#: Scale rows have no sequence axis.  The pool's slot scatter and the
#: plans' leaf specs both read this one map.
SEQ_AXIS: dict[str, int] = {
    "k": -3, "v": -3, "k_q": -3, "v_q": -3,
    "ckv": -2, "krope": -2, "ckv_q": -2, "krope_q": -2,
}

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class PagedGeometry:
    """Static geometry of a paged KV pool.

    Device K/V leaves are laid out ``(num_blocks + 1, block_size, ...)``
    — the batch axis indexes *physical blocks*, not streams.  Physical
    block id ``num_blocks`` is a reserved garbage block: idle slots'
    block-table rows point at it, so their (discarded) decode scatters
    and reads never touch live data.  A per-layer ``block_tables`` leaf
    ``(slots, blocks_per_slot) int32`` maps each stream's logical block
    index to its physical block.
    """

    block_size: int       #: tokens per KV block
    num_blocks: int       #: usable blocks (device arrays hold +1 dummy)
    slots: int            #: concurrent streams (block-table rows)
    blocks_per_slot: int  #: max_seq // block_size (table row width)

    @property
    def dummy_block(self) -> int:
        return self.num_blocks

    @property
    def max_seq(self) -> int:
        return self.block_size * self.blocks_per_slot


@dataclasses.dataclass(frozen=True)
class CacheLeafSpec:
    """One leaf of a per-layer cache dict.  Static metadata only."""

    name: str                       # cache key ("k", "k_q", "ckv_scale", ...)
    tail_shape: tuple[int, ...]     # dims after (batch[, seq]): (KH, D) / (r,)
    dtype: Any
    seq_axis: int | None            # from the right; None = per-slot constant
    scale_of: str | None = None     # "k_scale" -> scales the "k_q" leaf

    def shape(self, batch: int, seq_len: int) -> tuple[int, ...]:
        if self.seq_axis is None:
            return (batch, *self.tail_shape)
        return (batch, seq_len, *self.tail_shape)

    @property
    def bytes_per_position(self) -> int:
        """Bytes one position of one stream occupies (0 for scale rows)."""
        if self.seq_axis is None:
            return 0
        return int(math.prod(self.tail_shape)) * jnp.dtype(self.dtype).itemsize

    @property
    def bytes_per_slot(self) -> int:
        """Per-slot constant bytes (scale rows; 0 for per-position leaves)."""
        if self.seq_axis is not None:
            return 0
        return int(math.prod(self.tail_shape)) * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class CachePlan:
    """How one attention layer's cache is laid out, costed, and executed."""

    family: str
    leaves: tuple[CacheLeafSpec, ...]
    #: paged families carry their block geometry; slot families None.
    paged: PagedGeometry | None = None

    # -- contract -----------------------------------------------------------

    @property
    def quantized(self) -> bool:
        return self.family in (FAMILY_GQA_INT8, FAMILY_MLA_INT8,
                               FAMILY_GQA_PAGED_INT8)

    @property
    def mla(self) -> bool:
        return self.family in (FAMILY_MLA, FAMILY_MLA_INT8)

    def leaf(self, name: str) -> CacheLeafSpec:
        for l in self.leaves:
            if l.name == name:
                return l
        raise KeyError(name)

    @property
    def quant_pairs(self) -> dict[str, str]:
        """``{value_leaf: scale_leaf}`` refs for the quantized leaves."""
        return {l.scale_of: l.name for l in self.leaves if l.scale_of}

    # -- construction -------------------------------------------------------

    def _leaf_shape(self, l: CacheLeafSpec, batch: int,
                    seq_len: int) -> tuple[int, ...]:
        # Paged block tables are (slots, blocks_per_slot) regardless of
        # the pool's (num_blocks + 1, block_size) leaf geometry.
        if self.paged is not None and l.name == "block_tables":
            return (self.paged.slots, self.paged.blocks_per_slot)
        return l.shape(batch, seq_len)

    def spec(self, batch: int, seq_len: int) -> dict:
        return {l.name: jax.ShapeDtypeStruct(
                    self._leaf_shape(l, batch, seq_len), l.dtype)
                for l in self.leaves}

    def init(self, batch: int, seq_len: int) -> dict:
        """Zero-initialized cache (zero scales dequantize to zeros).
        Paged block tables initialize to the reserved dummy block so an
        unallocated stream can never alias live data."""
        out = {}
        for l in self.leaves:
            shape = self._leaf_shape(l, batch, seq_len)
            if self.paged is not None and l.name == "block_tables":
                out[l.name] = jnp.full(shape, self.paged.dummy_block,
                                       l.dtype)
            else:
                out[l.name] = jnp.zeros(shape, l.dtype)
        return out

    # -- accounting (single source of truth for pool / roofline) ------------

    @property
    def bytes_per_token(self) -> int:
        """Per-position cache bytes of ONE stream, this layer."""
        return sum(l.bytes_per_position for l in self.leaves)

    @property
    def bytes_per_slot(self) -> int:
        """Per-slot constant bytes (f32 scale rows), this layer.  For
        paged families "slot" means one physical block (the scale rows
        are per-block); the int32 block tables are metadata, not KV."""
        return sum(l.bytes_per_slot for l in self.leaves
                   if l.name != "block_tables")

    @property
    def bytes_per_block(self) -> int:
        """KV bytes of ONE physical block (paged families only):
        ``block_size`` positions of values plus the per-block scale
        rows."""
        if self.paged is None:
            raise ValueError(f"{self.family} is not a paged family")
        return (self.paged.block_size * self.bytes_per_token
                + self.bytes_per_slot)

    def bytes_per_step(self, slots: int, seq_len: int) -> int:
        """HBM bytes this layer's pool streams per decode step — decode
        reads every slot's full ``seq_len`` (masked, not skipped).  The
        paged kernel streams one block per table entry (cold entries
        alias the dummy block) plus the tables themselves."""
        if self.paged is not None:
            nblk = seq_len // self.paged.block_size
            return slots * (nblk * self.bytes_per_block
                            + nblk * jnp.dtype(jnp.int32).itemsize)
        return slots * (seq_len * self.bytes_per_token + self.bytes_per_slot)

    # -- write executors ----------------------------------------------------
    # ``new`` carries the layer's full-precision values under their
    # LOGICAL names: {"k", "v"} (B, S, KH, D) for GQA families,
    # {"ckv"} (B, S, r) + {"krope"} (B, S, rope) for MLA families
    # (decode passes one-token values without the S axis).  ``layer``
    # (traced scalar) marks ``cache`` as the stack of every layer's
    # leaves; None marks one layer's cache.

    def layer_view(self, cache: dict, layer: jax.Array | None) -> dict:
        """Layer ``layer`` of a stacked cache (``cache`` itself when
        ``layer`` is None): what decode attention reads."""
        return {k: kvq.layer_of(v, layer) for k, v in cache.items()}

    def _mask_new(self, new: dict, start_pos, prompt_len) -> dict:
        """Zero rows at absolute positions ``>= prompt_len`` (bucket-pad
        tail) so they can neither land garbage in the pool nor inflate
        the int8 running-max scales."""
        if prompt_len is None:
            return new
        out = {}
        for key, x in new.items():
            sq = x.shape[1]
            pad = (1,) * (-SEQ_AXIS[key] - 1)
            pm = (start_pos + jnp.arange(sq) < prompt_len).reshape(
                (1, sq, *pad))
            out[key] = jnp.where(pm, x, 0.0)
        return out

    def write_prefill(self, cache: dict, new: dict,
                      prompt_len: jax.Array | None = None,
                      layer: jax.Array | None = None) -> dict:
        """Whole-prompt write at position 0 (quantize-on-insert for the
        int8 families, one-shot scales over the real prompt)."""
        if self.paged is not None:
            raise ValueError(
                "paged pools take no sequential prefill writes — serve "
                "stages prompts in a contiguous stream cache and the "
                "pool manager scatters whole blocks at insert")
        if not self.quantized:
            return {k: _put_seq(cache[k], v, 0, layer)
                    for k, v in new.items()}
        new = self._mask_new(new, 0, prompt_len)
        out = {}
        for key, x in new.items():
            q, scale = kvq.quantize_kv_prefill(x)
            out[key + "_q"] = _put_seq(cache[key + "_q"], q, 0, layer)
            out[key + "_scale"] = kvq.with_layer(cache[key + "_scale"],
                                                 scale, layer)
        return out

    def write_chunk(self, cache: dict, new: dict, start_pos: jax.Array,
                    prompt_len: jax.Array | None = None,
                    layer: jax.Array | None = None) -> tuple[dict, dict]:
        """Chunk write at a sequence offset.  Returns ``(new_cache,
        views)`` where ``views`` holds the layer's full-precision
        whole-pool attend views under the logical names (the written
        pool for full-width families, the dequantized pool for int8 —
        serve stages chunked prompts full-precision instead, for
        exactness).
        Pad rows beyond ``prompt_len`` (the chunk's real end) are zeroed
        at the write for BOTH dtypes: a later chunk's bucket is not
        guaranteed to overwrite them before they become attendable.
        """
        if self.paged is not None:
            raise ValueError(
                "paged pools take no chunk writes — chunked prefill "
                "stages into a contiguous stream cache")
        new = self._mask_new(new, start_pos, prompt_len)
        if not self.quantized:
            out = {k: _put_seq(cache[k], v, start_pos, layer)
                   for k, v in new.items()}
            # the view is the layer as read before the write, with the
            # chunk put in: read from the written stack, the stack would
            # take attention's (KH, S) order, relaid out whole at entry
            # and back at exit
            views = {k: lax.dynamic_update_slice_in_dim(
                         kvq.layer_of(cache[k], layer), v, start_pos, 1)
                     for k, v in new.items()}
            return out, views
        out, views = {}, {}
        for key, x in new.items():
            kq, ks = key + "_q", key + "_scale"
            q, scale = kvq.kv_write_chunk(kvq.layer_of(cache[kq], layer),
                                          kvq.layer_of(cache[ks], layer),
                                          x, start_pos)
            out[kq] = kvq.with_layer(cache[kq], q, layer)
            out[ks] = kvq.with_layer(cache[ks], scale, layer)
            views[key] = kvq.dequantize_kv(q, scale, x.dtype)
        return out, views

    def write_decode(self, cache: dict, new: dict, cache_pos: jax.Array,
                     layer: jax.Array | None = None) -> dict:
        """One-token scatter at per-slot positions ``cache_pos`` (B,).
        ``new`` values carry no S axis: (B, KH, D) / (B, r).  Int8
        families take the incremental running-max scale update
        (:func:`repro.quant.kv.kv_write_token`)."""
        if self.paged is not None:
            return self._write_decode_paged(cache, new, cache_pos, layer)
        bidx = jnp.arange(cache_pos.shape[0])
        if not self.quantized:
            return {k: cache[k].at[kvq.layer_index(layer, bidx,
                                                   cache_pos)].set(v)
                    for k, v in new.items()}
        out = {}
        for key, x in new.items():
            q, scale = kvq.kv_write_token(cache[key + "_q"],
                                          cache[key + "_scale"], x,
                                          cache_pos, layer)
            out[key + "_q"] = q
            out[key + "_scale"] = scale
        return out

    def _write_decode_paged(self, cache: dict, new: dict,
                            cache_pos: jax.Array,
                            layer: jax.Array | None) -> dict:
        """One-token scatter through the block tables: the target block
        is ``tables[slot, pos // bs]`` and the row ``pos % bs``.  Slots
        whose table row still points at the dummy block (idle) write
        garbage into the dummy — harmless by construction.  Int8 takes
        the running-max scale update on the ONE gathered block, then
        scatters block + scale row back (a requant touches only that
        block, never the shared prefix blocks — which are never the
        write target: decode always lands past the shared prefix)."""
        geom = self.paged
        bt = kvq.layer_of(cache["block_tables"], layer)
        bidx = jnp.arange(cache_pos.shape[0])
        blk = jnp.minimum(cache_pos // geom.block_size,
                          geom.blocks_per_slot - 1)
        phys = bt[bidx, blk]                              # (B,) physical ids
        # a position past the table (slot-pool scatters drop it as OOB)
        # must land in the dummy, not clamp into the slot's last block
        phys = jnp.where(cache_pos < geom.max_seq, phys, geom.dummy_block)
        row = cache_pos % geom.block_size
        out = {"block_tables": cache["block_tables"]}
        if not self.quantized:
            for key, x in new.items():
                out[key] = cache[key].at[
                    kvq.layer_index(layer, phys, row)].set(
                        x.astype(cache[key].dtype))
            return out
        at = kvq.layer_index(layer, phys)
        for key, x in new.items():
            kq, ks = key + "_q", key + "_scale"
            blk = cache[kq][at]                           # (B, bs, KH, D)
            sc = cache[ks][at]                            # (B, KH, D)
            blk, sc = kvq.kv_write_token(blk, sc, x, row)
            out[kq] = cache[kq].at[at].set(blk)
            out[ks] = cache[ks].at[at].set(sc)
        return out

    # -- decode attention (the cache-coupled read) --------------------------

    def decode_path(self, num_heads: int, batch: int, seq_len: int,
                    use_pallas: bool) -> str:
        """How decode attention over this cache runs: the fused
        kernel's ``kernel_fits`` name, ``"ref"`` when that kernel does
        not fit its VMEM budget (the ops wrapper then runs the oracle),
        or ``"jnp"`` where no kernel serves (``use_pallas`` off, or a
        full-width slot / MLA pool)."""
        from repro.kernels import ops as kops
        if not use_pallas or (not self.quantized and self.paged is None):
            return "jnp"
        if self.mla:
            lora = self.leaf("ckv_q").tail_shape[-1]
            rope = self.leaf("krope_q").tail_shape[-1]
            name = "decode_latent_q"
            fits = kops.vmem_fits(name, batch, c=lora, s=seq_len,
                                  r=num_heads, r1=rope)
        else:
            kh, d = self.leaf("k_q" if self.quantized else "k").tail_shape
            g = num_heads // kh
            if self.paged is not None:
                name, bs = "decode_attn_paged", self.paged.block_size
                fits = kops.vmem_fits(name, batch, c=d, s=bs, r=g, kh=kh,
                                      bn=bs)
            else:
                name = "decode_attn_q"
                fits = kops.vmem_fits(name, batch, c=d, s=seq_len, r=g,
                                      kh=kh)
        return name if fits else "ref"

    def attend_decode(self, q: jax.Array, cache: dict,
                      cache_pos: jax.Array, *, softcap: float = 0.0,
                      use_pallas: bool = False) -> jax.Array:
        """GQA decode: one query row vs the whole pool.  q (B, 1, H, D)
        -> (B, 1, H, D).  Int8 pools run the fused kernel under
        ``use_pallas`` (VMEM-fit fallback inside the ops wrapper) or the
        jnp dequant oracle — a full-precision pool copy never lands in
        HBM on the kernel path."""
        if self.mla:
            raise ValueError("latent families attend via "
                             "attend_decode_latent")
        if self.paged is not None:
            from repro.kernels import ops as kops
            from repro.kernels import ref as kref
            bt = cache["block_tables"]
            if not self.quantized:
                fn = kops.decode_attention_paged if use_pallas \
                    else kref.decode_attention_paged_ref
                return fn(q, cache["k"], cache["v"], bt, cache_pos,
                          softcap=softcap)
            fn = kops.decode_attention_paged_q if use_pallas \
                else kref.decode_attention_paged_q_ref
            return fn(q, cache["k_q"], cache["k_scale"], cache["v_q"],
                      cache["v_scale"], bt, cache_pos, softcap=softcap)
        if not self.quantized:
            skv = cache["k"].shape[1]
            valid = jnp.arange(skv)[None, :] <= cache_pos[:, None]  # (B,S)
            return gqa_decode_attention(q, cache["k"], cache["v"], valid,
                                        softcap)
        from repro.kernels import ops as kops
        from repro.kernels import ref as kref
        fn = kops.decode_attention_q if use_pallas \
            else kref.decode_attention_q_ref
        return fn(q, cache["k_q"], cache["k_scale"], cache["v_q"],
                  cache["v_scale"], cache_pos, softcap=softcap)

    def attend_decode_latent(self, q_lat: jax.Array, q_rope: jax.Array,
                             cache: dict, cache_pos: jax.Array, *,
                             scale: float,
                             use_pallas: bool = False) -> jax.Array:
        """MLA absorbed decode: latent-space queries vs the latent pool.
        q_lat (B, 1, H, r); q_rope (B, 1, H, rope) -> context latents
        (B, 1, H, r) — attention runs entirely against the cached
        latents, per-head K/V are never materialized.  Int8 pools run
        the fused latent kernel (ckv/krope scales folded into the
        latent query rows, ckv scales into the context output) under
        ``use_pallas``, else the dequant oracle."""
        if not self.mla:
            raise ValueError("GQA families attend via attend_decode")
        if not self.quantized:
            cc, cr = cache["ckv"], cache["krope"]
            s = (jnp.einsum("bqhl,bsl->bhqs", q_lat, cc,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhr,bsr->bhqs", q_rope, cr,
                              preferred_element_type=jnp.float32)) * scale
            valid = jnp.arange(cc.shape[1])[None, :] <= cache_pos[:, None]
            s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
            attn = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
            return jnp.einsum("bhqs,bsl->bqhl", attn, cc)
        from repro.kernels import ops as kops
        from repro.kernels import ref as kref
        fn = kops.decode_attention_latent_q if use_pallas \
            else kref.decode_attention_latent_q_ref
        return fn(q_lat, q_rope, cache["ckv_q"], cache["ckv_scale"],
                  cache["krope_q"], cache["krope_scale"], cache_pos,
                  scale=scale)


def _put_seq(buf: jax.Array, x: jax.Array, start,
             layer: jax.Array | None) -> jax.Array:
    """Write ``x (B, S', ...)`` into ``buf`` at sequence offset ``start``
    — of layer ``layer`` of a stacked ``buf``, in place."""
    if layer is None:
        return lax.dynamic_update_slice_in_dim(buf, x, start, 1)
    idx = (layer, 0, start) + (0,) * (x.ndim - 2)
    return lax.dynamic_update_slice(buf, x[None], idx)


def gqa_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         valid: jax.Array, softcap: float) -> jax.Array:
    """Full-width GQA decode attention: q (B, 1, H, D) vs k/v
    (B, S, KH, D), slot validity (B, S) masked into the f32 logits."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, sq, kh, h // kh, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(hd))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return o.reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# Plan construction (cached — one plan object per geometry)
# ---------------------------------------------------------------------------

_PLAN_CACHE: dict[tuple, CachePlan] = {}


def _check_quantize(quantize: str | None) -> bool:
    if quantize in (None, "none"):
        return False
    if quantize not in kvq.KV_MODES:
        raise ValueError(
            f"unknown kv quant mode {quantize!r} (want one of "
            f"{kvq.KV_MODES})")
    return True


def gqa_plan(num_kv_heads: int, head_dim: int, dtype,
             quantize: str | None = None) -> CachePlan:
    """The plan for one GQA/MHA attention layer's K/V cache."""
    q = _check_quantize(quantize)
    key = ("gqa", num_kv_heads, head_dim, jnp.dtype(dtype).name, q)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        tail = (num_kv_heads, head_dim)
        if q:
            leaves = []
            for name in ("k", "v"):
                leaves.append(CacheLeafSpec(name + "_q", tail, jnp.int8,
                                            SEQ_AXIS[name + "_q"]))
                leaves.append(CacheLeafSpec(name + "_scale", tail,
                                            jnp.float32, None,
                                            scale_of=name + "_q"))
            plan = CachePlan(FAMILY_GQA_INT8, tuple(leaves))
        else:
            plan = CachePlan(FAMILY_GQA, tuple(
                CacheLeafSpec(n, tail, jnp.dtype(dtype), SEQ_AXIS[n])
                for n in ("k", "v")))
        _PLAN_CACHE[key] = plan
    return plan


def gqa_paged_plan(num_kv_heads: int, head_dim: int, dtype,
                   quantize: str | None = None, *,
                   geometry: PagedGeometry) -> CachePlan:
    """The plan for one GQA layer's *paged* K/V pool.  Value leaves are
    ``(num_blocks + 1, block_size, KH, D)`` — batch axis = physical
    block — plus a ``(slots, blocks_per_slot)`` int32 ``block_tables``
    leaf.  The int8 family blocks quantized values and their scale rows
    together: one ``(KH, D)`` f32 scale row per physical block, so a
    shared prefix block travels with its own scales."""
    q = _check_quantize(quantize)
    key = ("gqa_paged", num_kv_heads, head_dim, jnp.dtype(dtype).name, q,
           geometry)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        tail = (num_kv_heads, head_dim)
        leaves = []
        if q:
            for name in ("k", "v"):
                leaves.append(CacheLeafSpec(name + "_q", tail, jnp.int8,
                                            SEQ_AXIS[name + "_q"]))
                leaves.append(CacheLeafSpec(name + "_scale", tail,
                                            jnp.float32, None,
                                            scale_of=name + "_q"))
            family = FAMILY_GQA_PAGED_INT8
        else:
            leaves = [CacheLeafSpec(n, tail, jnp.dtype(dtype), SEQ_AXIS[n])
                      for n in ("k", "v")]
            family = FAMILY_GQA_PAGED
        leaves.append(CacheLeafSpec("block_tables", (), jnp.int32, None))
        plan = CachePlan(family, tuple(leaves), paged=geometry)
        _PLAN_CACHE[key] = plan
    return plan


def mla_plan(kv_lora_rank: int, qk_rope_dim: int, dtype,
             quantize: str | None = None) -> CachePlan:
    """The plan for one MLA layer's latent cache.  The latent *is* the
    rank-compressed K/V factor; the int8 family compresses it again with
    per-(slot, channel) scales (no head axis — all heads share the one
    latent stream)."""
    q = _check_quantize(quantize)
    key = ("mla", kv_lora_rank, qk_rope_dim, jnp.dtype(dtype).name, q)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        dims = {"ckv": (kv_lora_rank,), "krope": (qk_rope_dim,)}
        if q:
            leaves = []
            for name, tail in dims.items():
                leaves.append(CacheLeafSpec(name + "_q", tail, jnp.int8,
                                            SEQ_AXIS[name + "_q"]))
                leaves.append(CacheLeafSpec(name + "_scale", tail,
                                            jnp.float32, None,
                                            scale_of=name + "_q"))
            plan = CachePlan(FAMILY_MLA_INT8, tuple(leaves))
        else:
            plan = CachePlan(FAMILY_MLA, tuple(
                CacheLeafSpec(n, tail, jnp.dtype(dtype), SEQ_AXIS[n])
                for n, tail in dims.items()))
        _PLAN_CACHE[key] = plan
    return plan


def build_cache_plan(cfg, dtype, kv_quantize: str | None = None,
                     paged: PagedGeometry | None = None) -> CachePlan:
    """The per-attention-layer plan for a model config (``cfg.mla``
    selects the latent families; a ``paged`` geometry selects the paged
    GQA families)."""
    if paged is not None:
        if cfg.mla:
            raise ValueError("paged KV pools serve the GQA families "
                             "only (no paged MLA latent cache yet)")
        return gqa_paged_plan(cfg.num_kv_heads, cfg.resolved_head_dim,
                              dtype, kv_quantize, geometry=paged)
    if cfg.mla:
        return mla_plan(cfg.kv_lora_rank, cfg.qk_rope_dim, dtype,
                        kv_quantize)
    return gqa_plan(cfg.num_kv_heads, cfg.resolved_head_dim, dtype,
                    kv_quantize)


def plan_from_cache(cache: dict, dtype=jnp.float32) -> CachePlan:
    """Classify a per-layer cache dict into its plan — the ONE remaining
    key-sniffing point, used when a caller has no plan threaded (direct
    layer-level use; the serve stack always threads plans).  Geometry
    comes from the leaf shapes; ``dtype`` is only needed for int8
    families (full-width leaves carry theirs)."""
    if "block_tables" in cache:
        val = cache.get("k", cache.get("k_q"))
        nb1, bs, kh, hd = val.shape[-4:]
        slots, bpslot = cache["block_tables"].shape[-2:]
        geom = PagedGeometry(bs, nb1 - 1, slots, bpslot)
        if "k_q" in cache:
            return gqa_paged_plan(kh, hd, dtype, "int8", geometry=geom)
        return gqa_paged_plan(kh, hd, cache["k"].dtype, None,
                              geometry=geom)
    if "ckv_q" in cache:
        return mla_plan(cache["ckv_q"].shape[-1], cache["krope_q"].shape[-1],
                        dtype, "int8")
    if "ckv" in cache:
        return mla_plan(cache["ckv"].shape[-1], cache["krope"].shape[-1],
                        cache["ckv"].dtype, None)
    if "k_q" in cache:
        kh, hd = cache["k_q"].shape[-2:]
        return gqa_plan(kh, hd, dtype, "int8")
    if "k" in cache:
        kh, hd = cache["k"].shape[-2:]
        return gqa_plan(kh, hd, cache["k"].dtype, None)
    raise ValueError(f"not a KV cache dict: {sorted(cache)}")
