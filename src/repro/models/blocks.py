"""Per-family transformer/SSM blocks built on the LRD-transparent layers.

Every block has ``init_*(pb, cfg)`` building a *single layer's* params
(stacked by the model via ``jax.vmap``) and ``apply_*`` operating on one
layer's params.  Cache pytrees are per-layer dicts stacked by the model.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.layers import attention as attn
from repro.layers import cache as cache_mod
from repro.layers import ssm as ssm_mod
from repro.layers.mlp import apply_mlp, init_mlp
from repro.layers.moe import MoEOpts, apply_moe, init_moe
from repro.layers.norm import (init_layer_norm, init_rms_norm, layer_norm,
                               rms_norm)
from repro.layers.param import ParamBuilder, shard_act, BATCH, SEQ, EMBED


class BlockOpts(NamedTuple):
    freeze_factors: bool = False
    use_pallas: bool = False
    act_quantize: bool = False

    def attn(self, softcap: float = 0.0) -> attn.AttnOpts:
        return attn.AttnOpts(self.freeze_factors, self.use_pallas, softcap,
                             self.act_quantize)

    def moe(self) -> MoEOpts:
        return MoEOpts(self.freeze_factors, self.use_pallas,
                       self.act_quantize)

    def ssm(self) -> ssm_mod.SSMOpts:
        return ssm_mod.SSMOpts(self.freeze_factors, self.use_pallas,
                               self.act_quantize)

    def kw(self) -> dict:
        return dict(freeze_factors=self.freeze_factors,
                    use_pallas=self.use_pallas,
                    act_quantize=self.act_quantize)


def _norm_fns(cfg):
    if cfg.family == "encoder":
        return init_layer_norm, layer_norm
    return init_rms_norm, rms_norm


# ---------------------------------------------------------------------------
# Decoder / encoder block (dense FFN or MoE; GQA or MLA or merged attention)
# ---------------------------------------------------------------------------

def init_block(pb: ParamBuilder, cfg, *, moe: bool) -> None:
    init_norm, _ = _norm_fns(cfg)
    init_norm(pb, "attn_norm", cfg.d_model)
    if cfg.mla:
        attn.init_mla(pb, "mla", cfg)
    else:
        attn.init_attention(pb, "attn", cfg.d_model, cfg.num_heads,
                            cfg.num_kv_heads, cfg.resolved_head_dim)
    init_norm(pb, "mlp_norm", cfg.d_model)
    if moe:
        init_moe(pb, "moe", cfg.d_model, cfg.resolved_moe_d_ff,
                 cfg.moe_num_experts, cfg.moe_num_shared, cfg.act)
    else:
        init_mlp(pb, "mlp", cfg.d_model, cfg.d_ff, cfg.act)


def apply_block(p: dict, x: jax.Array, cfg, *, positions, cache=None,
                cache_pos=None, prompt_len=None, start_pos=None,
                cache_plan=None, layer=None, opts: BlockOpts = BlockOpts()
                ) -> tuple[jax.Array, Any, jax.Array]:
    """Pre-norm block.  Returns (x', new_cache, aux_loss).

    ``start_pos`` (scalar) marks a chunked prefill: x covers prompt
    positions ``[start_pos, start_pos + S)`` and K/V land at the offset
    in the existing cache slot (see ``attention.apply_attention``).
    ``cache_plan`` is the layer's :class:`repro.layers.cache.CachePlan`
    (classified from the cache keys when None).  ``layer`` (traced
    scalar) marks ``cache`` as the stack of every layer's caches, which
    the attention writes in place and returns whole.
    """
    _, norm = _norm_fns(cfg)
    causal = not cfg.is_encoder
    with jax.named_scope(tracing.NORM):
        h = norm(p["attn_norm"], x, cfg.norm_eps)
    if "mla" in p:
        a, new_cache = attn.apply_mla(
            p["mla"], h, cfg, positions=positions, causal=causal,
            cache=cache, cache_pos=cache_pos, prompt_len=prompt_len,
            start_pos=start_pos, plan=cache_plan, layer=layer,
            opts=opts.attn(cfg.attn_logit_softcap))
    elif "merged" in p:
        a = attn.apply_merged_attention(
            p["merged"], h, positions=positions, causal=causal,
            opts=opts.attn(cfg.attn_logit_softcap))
        new_cache = None
    else:
        a, new_cache = attn.apply_attention(
            p["attn"], h, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta, positions=positions, causal=causal,
            cache=cache, cache_pos=cache_pos, prompt_len=prompt_len,
            start_pos=start_pos, plan=cache_plan, layer=layer,
            opts=opts.attn(cfg.attn_logit_softcap))
    x = x + a
    with jax.named_scope(tracing.NORM):
        h = norm(p["mlp_norm"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(tracing.MLP):
        if "moe" in p:
            f, aux = apply_moe(p["moe"], h, top_k=cfg.moe_top_k,
                               capacity_factor=cfg.moe_capacity_factor,
                               act=cfg.act, opts=opts.moe(),
                               dispatch_groups=cfg.moe_dispatch_groups)
        else:
            f = apply_mlp(p["mlp"], h, cfg.act, **opts.kw())
    x = x + f
    x = shard_act(x, BATCH, SEQ, EMBED)
    return x, new_cache, aux


def block_cache_spec(cfg, batch: int, seq_len: int, dtype,
                     kv_quantize: str | None = None, paged=None) -> dict:
    # One declarative seam for every family: gqa_f32 | gqa_int8 |
    # mla_latent | mla_latent_int8 | gqa_paged_* (the MLA latent —
    # itself the paper's rank-compressed K/V factor — quantizes like
    # any other pool now; a PagedGeometry selects the paged layout,
    # where batch/seq_len mean (num_blocks + 1, block_size)).
    return cache_mod.build_cache_plan(cfg, dtype, kv_quantize,
                                      paged).spec(batch, seq_len)


def init_block_cache(cfg, batch: int, seq_len: int, dtype,
                     kv_quantize: str | None = None, paged=None) -> dict:
    return cache_mod.build_cache_plan(cfg, dtype, kv_quantize,
                                      paged).init(batch, seq_len)


# ---------------------------------------------------------------------------
# Cross-attention block (VLM): self-style block + gated cross attention
# ---------------------------------------------------------------------------

def init_cross_block(pb: ParamBuilder, cfg) -> None:
    init_norm, _ = _norm_fns(cfg)
    init_norm(pb, "norm", cfg.d_model)
    kv_dim = cfg.vision_d_model or cfg.d_model
    attn.init_cross_attention(pb, "cross_attn", cfg.d_model, cfg.num_heads,
                              cfg.num_kv_heads, cfg.resolved_head_dim, kv_dim)
    init_norm(pb, "mlp_norm", cfg.d_model)
    init_mlp(pb, "mlp", cfg.d_model, cfg.d_ff, cfg.act)


def cross_block_kv(p: dict, image_feats: jax.Array, cfg, *,
                   opts: BlockOpts = BlockOpts()) -> dict:
    return attn.cross_attn_kv(p["cross_attn"], image_feats,
                              num_kv_heads=cfg.num_kv_heads,
                              head_dim=cfg.resolved_head_dim,
                              opts=opts.attn())


def cross_kv_all(cross_stacked: dict, image_feats: jax.Array, cfg, *,
                 opts: BlockOpts = BlockOpts()) -> dict:
    """K/V for every stacked cross block: {"k","v"} (n_super, B, T, KH, hd)."""
    def body(_, p_l):
        return None, cross_block_kv(p_l, image_feats, cfg, opts=opts)
    _, kvs = jax.lax.scan(body, None, cross_stacked)
    return kvs


def apply_cross_block(p: dict, x: jax.Array, cfg, *,
                      image_feats: jax.Array | None = None,
                      kv: dict | None = None,
                      opts: BlockOpts = BlockOpts()) -> jax.Array:
    _, norm = _norm_fns(cfg)
    h = norm(p["norm"], x, cfg.norm_eps)
    a = attn.apply_cross_attention(
        p["cross_attn"], h, image_feats, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
        kv=kv, opts=opts.attn())
    x = x + a
    h = norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + apply_mlp(p["mlp"], h, cfg.act, **opts.kw())
    return x


# ---------------------------------------------------------------------------
# SSM (mamba2) block
# ---------------------------------------------------------------------------

def init_ssm_block(pb: ParamBuilder, cfg) -> None:
    init_rms_norm(pb, "norm", cfg.d_model)
    ssm_mod.init_ssm(pb, "ssm", ssm_mod.dims_from_config(cfg))


def apply_ssm_block(p: dict, x: jax.Array, cfg, *, state=None,
                    decode: bool = False, opts: BlockOpts = BlockOpts()
                    ) -> tuple[jax.Array, Any]:
    dims = ssm_mod.dims_from_config(cfg)
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    if decode:
        y, new_state = ssm_mod.apply_ssm_decode(
            p["ssm"], h, dims, state, opts=opts.ssm(), norm_eps=cfg.norm_eps)
    else:
        y, new_state = ssm_mod.apply_ssm(
            p["ssm"], h, dims, state=state, opts=opts.ssm(),
            norm_eps=cfg.norm_eps)
    x = x + y
    x = shard_act(x, BATCH, SEQ, EMBED)
    return x, new_state
