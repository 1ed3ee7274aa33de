"""LM trunk assembly for all assigned architectures.

One :class:`LMModel` covers the six LM families by composing the blocks in
:mod:`repro.models.blocks` into *stacked segments* scanned with
``lax.scan`` (compile time stays flat in depth — mandatory at 48-100
layers):

* dense / encoder:   one stack of L blocks.
* moe:               optional unstacked first dense block (deepseek), then
                     a stack of MoE blocks.
* vlm:               self-attn stack reshaped ``(n_super, every-1, ...)``
                     interleaved with a cross-attn stack ``(n_super, ...)``
                     — scan over super-blocks, inner scan over self layers.
* ssm:               one stack of mamba2 blocks.
* hybrid (zamba2):   mamba2 stack with a *shared* attention block applied
                     every ``hybrid_attn_every`` layers (scan over
                     super-groups; the shared block's params are reused,
                     each application has its own KV cache slot).

Caches are pytrees stacked along each segment's scan axis, so prefill and
decode run under the same scans.  The flat stack of dense / encoder / moe
blocks carries its stacked cache through the scan and each layer writes
its new rows into it in place (see :meth:`LMModel._trunk`); the vlm,
ssm and hybrid scans take their caches as ``xs`` and stack each layer's
new cache as ``ys``, and the moe ``first`` block, outside the scan, takes
its own per-layer cache.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro import tracing
from repro.configs.base import ModelConfig
from repro.layers import cache as cache_mod
from repro.layers import ssm as ssm_mod
from repro.layers.norm import init_layer_norm, init_rms_norm, layer_norm, rms_norm
from repro.layers.param import (
    ParamBuilder, apply_linear, init_linear, shard_act,
    BATCH, SEQ, EMBED, VOCAB, LAYERS,
)
from repro.models import blocks as B

PyTree = Any
CE_CHUNK_SEQ = 512      # logits computed per seq-chunk to bound activation


def _axes_tuple_leaf(x):
    return isinstance(x, tuple)


def _stack_axes(axes: PyTree) -> PyTree:
    return jax.tree.map(lambda a: (LAYERS, *a), axes,
                        is_leaf=_axes_tuple_leaf)


class LMModel:
    """init / apply / loss / cache management for one architecture."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.padded_vocab = (-cfg.vocab_size) % 128 + cfg.vocab_size \
            if cfg.pad_vocab else cfg.vocab_size
        f = cfg.family
        if f == "vlm":
            assert cfg.cross_attn_every > 1
            assert cfg.num_layers % cfg.cross_attn_every == 0, cfg.num_layers
            self.n_super = cfg.num_layers // cfg.cross_attn_every
            self.n_self_per = cfg.cross_attn_every - 1
        if f == "hybrid":
            self.n_groups = cfg.num_layers // cfg.hybrid_attn_every
            self.n_trailing = cfg.num_layers % cfg.hybrid_attn_every

    # -- init ---------------------------------------------------------------

    def _build_one(self, build_fn):
        def fn(key):
            pb = ParamBuilder(key, self.dtype)
            build_fn(pb)
            return pb.params
        return fn

    def _init_stack(self, key, n, build_fn):
        fn = self._build_one(build_fn)
        params = jax.vmap(fn)(jax.random.split(key, n))
        axes = ParamBuilder(jax.random.PRNGKey(0), self.dtype)
        build_fn(axes)
        return params, _stack_axes(axes.axes)

    def init(self, key: jax.Array) -> tuple[PyTree, PyTree]:
        cfg = self.cfg
        pb = ParamBuilder(key, self.dtype)
        keys = jax.random.split(jax.random.fold_in(key, 1), 8)

        if cfg.family != "encoder":
            pb.child("embed").param(
                "w", (self.padded_vocab, cfg.d_model), (VOCAB, EMBED),
                init="embed", scale=0.02)
        elif cfg.frontend_dim and cfg.frontend_dim != cfg.d_model:
            init_linear(pb, "frontend_proj", cfg.frontend_dim, cfg.d_model,
                        EMBED, EMBED)

        f = cfg.family
        if f in ("dense", "encoder"):
            p, a = self._init_stack(
                keys[0], cfg.num_layers,
                lambda b: B.init_block(b, cfg, moe=False))
            pb.attach("blocks", p, a)
        elif f == "moe":
            n_first = cfg.moe_first_dense
            if n_first:
                first = ParamBuilder(keys[1], self.dtype)
                B.init_block(first, cfg, moe=False)
                pb.attach("first", first.params, first.axes)
            p, a = self._init_stack(
                keys[0], cfg.num_layers - n_first,
                lambda b: B.init_block(b, cfg, moe=True))
            pb.attach("blocks", p, a)
        elif f == "vlm":
            p, a = self._init_stack(
                keys[0], self.n_super * self.n_self_per,
                lambda b: B.init_block(b, cfg, moe=False))
            pb.attach("blocks", p, a)
            p, a = self._init_stack(
                keys[1], self.n_super, lambda b: B.init_cross_block(b, cfg))
            pb.attach("cross", p, a)
        elif f == "ssm":
            p, a = self._init_stack(keys[0], cfg.num_layers,
                                    lambda b: B.init_ssm_block(b, cfg))
            pb.attach("blocks", p, a)
        elif f == "hybrid":
            p, a = self._init_stack(keys[0], cfg.num_layers,
                                    lambda b: B.init_ssm_block(b, cfg))
            pb.attach("blocks", p, a)
            shared = ParamBuilder(keys[2], self.dtype)
            B.init_block(shared, cfg, moe=False)
            pb.attach("shared_attn", shared.params, shared.axes)
        else:
            raise ValueError(f"LMModel does not handle family {f!r}")

        if cfg.family == "encoder":
            init_layer_norm(pb, "final_norm", cfg.d_model)
        else:
            init_rms_norm(pb, "final_norm", cfg.d_model)
        if not cfg.tie_embeddings:
            init_linear(pb, "unembed", cfg.d_model, self.padded_vocab,
                        EMBED, VOCAB)
        return pb.params, pb.axes

    # -- embedding / head -----------------------------------------------------

    def embed(self, params: PyTree, batch: dict) -> jax.Array:
        with jax.named_scope(tracing.EMBED):
            return self._embed(params, batch)

    def _embed(self, params: PyTree, batch: dict) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "encoder":
            x = batch["frames"].astype(self.dtype)
            if "frontend_proj" in params:
                x = apply_linear(params["frontend_proj"], x)
            return x
        tok = batch["tokens"]
        emb = params["embed"]["w"]
        x = emb[tok].astype(self.dtype)
        return shard_act(x, BATCH, SEQ, EMBED)

    def logits(self, params: PyTree, x: jax.Array,
               opts: B.BlockOpts = B.BlockOpts()) -> jax.Array:
        with jax.named_scope(tracing.UNEMBED):
            return self._logits(params, x, opts)

    def _logits(self, params: PyTree, x: jax.Array,
                opts: B.BlockOpts) -> jax.Array:
        cfg = self.cfg
        norm = layer_norm if cfg.family == "encoder" else rms_norm
        h = norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            w = params["embed"]["w"]
            out = jnp.einsum("bsd,vd->bsv", h, w,
                             preferred_element_type=jnp.float32)
        else:
            out = apply_linear(params["unembed"], h, **opts.kw(),
                               accum_dtype=jnp.float32)
        out = out.astype(jnp.float32)
        if self.padded_vocab != cfg.vocab_size:
            # mask padded vocab columns (they hold real weights but are
            # not tokens): large-negative so softmax/argmax ignore them
            mask = jnp.arange(self.padded_vocab) < cfg.vocab_size
            out = jnp.where(mask[None, None, :], out, -1e30)
        return out

    # -- trunk ---------------------------------------------------------------

    def trunk(self, params: PyTree, x: jax.Array, **kw
              ) -> tuple[jax.Array, PyTree, jax.Array]:
        """:meth:`_trunk` under the ``layers`` scope."""
        with jax.named_scope(tracing.LAYERS):
            return self._trunk(params, x, **kw)

    def _trunk(self, params: PyTree, x: jax.Array, *, positions, cache=None,
               cache_pos=None, batch=None, opts=B.BlockOpts(),
               remat: str = "none", prompt_len=None, start_pos=None,
               cache_plan=None) -> tuple[jax.Array, PyTree, jax.Array]:
        """Run all blocks. Returns (x, new_cache, aux_loss_sum).

        ``prompt_len`` (scalar, prefill only) marks how many leading
        positions are real tokens when the prompt is right-padded — the
        quantized-KV prefill masks pad positions out of its scale
        reduction (see ``apply_attention``).

        ``start_pos`` (scalar) switches prefill into *chunk* mode: x
        covers prompt positions ``[start_pos, start_pos + S)`` and each
        block's K/V lands at the offset in the existing cache slot.
        Attention-cached families only (the serve scheduler gates
        chunked admission accordingly).

        ``cache_plan`` is the per-layer :class:`repro.layers.cache.
        CachePlan` the serve runner threads down; classified from the
        cache keys when None (direct callers)."""
        cfg = self.cfg
        f = cfg.family
        decode = cache_pos is not None

        def wrap(fn):
            if remat == "none" or decode:
                return fn
            policy = (jax.checkpoint_policies.nothing_saveable
                      if remat == "full"
                      else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            return jax.checkpoint(fn, policy=policy)

        aux_total = jnp.zeros((), jnp.float32)
        new_cache: dict | None = {} if cache is not None else None

        def scan_attn_stack(x, stack_p, stack_cache):
            """Scan the stacked blocks with the stacked cache in the
            carry: layer ``l`` writes its new K/V rows at ``[l, ...]``
            of the carried buffer in place and attends over layer ``l``
            read back from it.  Taken as ``xs`` and returned as ``ys``,
            each layer's cache would be copied out of the stack and
            written back whole, every step."""
            n = jax.tree.leaves(stack_p)[0].shape[0]

            def body(carry, xs):
                h, aux, c = carry
                p_l, layer = xs
                h, c, a = B.apply_block(p_l, h, cfg, positions=positions,
                                        cache=c, cache_pos=cache_pos,
                                        prompt_len=prompt_len,
                                        start_pos=start_pos,
                                        cache_plan=cache_plan, layer=layer,
                                        opts=opts)
                return (h, aux + a, c), None
            (x, aux, ncs), _ = lax.scan(
                wrap(body), (x, aux_total * 0, stack_cache),
                (stack_p, jnp.arange(n)))
            return x, ncs, aux

        if f in ("dense", "encoder", "moe"):
            if f == "moe" and "first" in params:
                c0 = None if cache is None else cache["first"]
                x, nc0, a0 = B.apply_block(
                    params["first"], x, cfg, positions=positions, cache=c0,
                    cache_pos=cache_pos, prompt_len=prompt_len,
                    start_pos=start_pos, cache_plan=cache_plan, opts=opts)
                aux_total = aux_total + a0
                if new_cache is not None:
                    new_cache["first"] = nc0
            bc = None if cache is None else cache["blocks"]
            x, ncs, aux = scan_attn_stack(x, params["blocks"], bc)
            aux_total = aux_total + aux
            if new_cache is not None:
                new_cache["blocks"] = ncs

        elif f == "vlm":
            ns, npr = self.n_super, self.n_self_per
            self_p = jax.tree.map(
                lambda t: t.reshape(ns, npr, *t.shape[1:]), params["blocks"])
            if cache is None:        # train: no caches
                img = batch["image_embeds"].astype(self.dtype)

                def super_train(carry, xs):
                    h, aux = carry
                    sp, cp = xs
                    def inner(c2, p_l):
                        hh, aa = c2
                        hh, _, a = B.apply_block(p_l, hh, cfg,
                                                 positions=positions,
                                                 opts=opts)
                        return (hh, aa + a), None
                    (h, aux), _ = lax.scan(wrap(inner), (h, aux), sp)
                    h = B.apply_cross_block(cp, h, cfg, image_feats=img,
                                            opts=opts)
                    return (h, aux), None

                (x, aux_total), _ = lax.scan(wrap(super_train),
                                             (x, aux_total),
                                             (self_p, params["cross"]))
            else:
                if decode:
                    img_kv = cache["cross_kv"]
                else:
                    img = batch["image_embeds"].astype(self.dtype)
                    img_kv = B.cross_kv_all(params["cross"], img, cfg,
                                            opts=opts)

                def super_body(carry, xs):
                    h, aux = carry
                    sp, cp, sc, kv_l = xs
                    def inner(c2, xs2):
                        hh, aa = c2
                        p_l, c_l = xs2
                        hh, nc, a = B.apply_block(
                            p_l, hh, cfg, positions=positions, cache=c_l,
                            cache_pos=cache_pos, prompt_len=prompt_len,
                            start_pos=start_pos, cache_plan=cache_plan,
                            opts=opts)
                        return (hh, aa + a), nc
                    (h, aux), ncs = lax.scan(wrap(inner), (h, aux), (sp, sc))
                    h = B.apply_cross_block(cp, h, cfg, kv=kv_l, opts=opts)
                    return (h, aux), ncs

                (x, aux_total), ncs = lax.scan(
                    wrap(super_body), (x, aux_total),
                    (self_p, params["cross"], cache["self"], img_kv))
                new_cache["self"] = ncs
                new_cache["cross_kv"] = img_kv

        elif f == "ssm":
            bc = None if cache is None else cache["blocks"]
            def body(carry, xs):
                h = carry
                p_l, s_l = xs
                h, ns = B.apply_ssm_block(p_l, h, cfg, state=s_l,
                                          decode=decode, opts=opts)
                return h, ns
            if cache is None:
                def body_nc(h, p_l):
                    h, _ = B.apply_ssm_block(p_l, h, cfg, opts=opts)
                    return h, None
                x, _ = lax.scan(wrap(body_nc), x, params["blocks"])
            else:
                x, ncs = lax.scan(wrap(body), x, (params["blocks"], bc))
                new_cache["blocks"] = ncs

        elif f == "hybrid":
            x, new_cache, aux_total = self._hybrid_trunk(
                params, x, positions=positions, cache=cache,
                cache_pos=cache_pos, cache_plan=cache_plan, opts=opts,
                wrap=wrap)
        else:
            raise ValueError(f)
        return x, new_cache, aux_total

    def _hybrid_trunk(self, params, x, *, positions, cache, cache_pos, opts,
                      wrap, cache_plan=None):
        cfg = self.cfg
        every = cfg.hybrid_attn_every
        ng, nt = self.n_groups, self.n_trailing
        decode = cache_pos is not None
        shared_p = params["shared_attn"]
        new_cache = {} if cache is not None else None
        aux = jnp.zeros((), jnp.float32)

        grouped = jax.tree.map(
            lambda t: t[:ng * every].reshape(ng, every, *t.shape[1:]),
            params["blocks"])
        trailing = jax.tree.map(lambda t: t[ng * every:], params["blocks"])

        def group_body(carry, xs):
            h, a = carry
            if cache is None:
                gp, = xs
                def inner(hh, p_l):
                    hh, _ = B.apply_ssm_block(p_l, hh, cfg, opts=opts)
                    return hh, None
                h, _ = lax.scan(wrap(inner), h, gp)
                h, _, a2 = B.apply_block(shared_p, h, cfg,
                                         positions=positions, opts=opts)
                return (h, a + a2), None
            gp, gs, sc = xs
            def inner(hh, xs2):
                p_l, s_l = xs2
                hh, ns = B.apply_ssm_block(p_l, hh, cfg, state=s_l,
                                           decode=decode, opts=opts)
                return hh, ns
            h, nss = lax.scan(wrap(inner), h, (gp, gs))
            h, nc, a2 = B.apply_block(shared_p, h, cfg, positions=positions,
                                      cache=sc, cache_pos=cache_pos,
                                      cache_plan=cache_plan, opts=opts)
            return (h, a + a2), (nss, nc)

        if cache is None:
            (x, aux), _ = lax.scan(wrap(group_body), (x, aux), (grouped,))
            if nt:
                def tail(hh, p_l):
                    hh, _ = B.apply_ssm_block(p_l, hh, cfg, opts=opts)
                    return hh, None
                x, _ = lax.scan(wrap(tail), x, trailing)
            return x, None, aux

        gs = jax.tree.map(
            lambda t: t[:ng * every].reshape(ng, every, *t.shape[1:]),
            cache["blocks"])
        ts = jax.tree.map(lambda t: t[ng * every:], cache["blocks"])
        (x, aux), (nss, ncs) = lax.scan(
            wrap(group_body), (x, aux), (grouped, gs, cache["shared"]))
        new_states = jax.tree.map(
            lambda t: t.reshape(ng * every, *t.shape[2:]), nss)
        if nt:
            def tail(hh, xs2):
                p_l, s_l = xs2
                hh, ns = B.apply_ssm_block(p_l, hh, cfg, state=s_l,
                                           decode=decode, opts=opts)
                return hh, ns
            x, tns = lax.scan(wrap(tail), x, (trailing, ts))
            new_states = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), new_states, tns)
        new_cache["blocks"] = new_states
        new_cache["shared"] = ncs
        return x, new_cache, aux

    # -- top-level steps ------------------------------------------------------

    def forward(self, params: PyTree, batch: dict, *,
                opts: B.BlockOpts = B.BlockOpts(), remat: str = "none"
                ) -> tuple[jax.Array, jax.Array]:
        """Full-sequence forward (training). Returns (logits_fn input x, aux).

        Note: returns the *pre-head* activations; loss() applies the head in
        chunks to bound the logits materialization.
        """
        x = self.embed(params, batch)
        bsz, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))
        x, _, aux = self.trunk(params, x, positions=positions, batch=batch,
                               opts=opts, remat=remat)
        return x, aux

    def loss(self, params: PyTree, batch: dict, *,
             opts: B.BlockOpts = B.BlockOpts(), remat: str = "none"
             ) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, aux = self.forward(params, batch, opts=opts, remat=remat)
        if cfg.family == "encoder":
            labels = batch["labels"]
            valid = jnp.ones_like(labels, dtype=bool)
        else:
            tok = batch["tokens"]
            labels = jnp.concatenate(
                [tok[:, 1:], jnp.zeros_like(tok[:, :1])], axis=1)
            valid = jnp.concatenate(
                [jnp.ones_like(tok[:, 1:], bool),
                 jnp.zeros_like(tok[:, :1], bool)], axis=1)
        ce, n_tok = self._chunked_ce(params, x, labels, valid, opts)
        loss = ce / jnp.maximum(n_tok, 1.0)
        total = loss + 0.01 * aux
        return total, {"ce": loss, "aux": aux, "tokens": n_tok}

    def _chunked_ce(self, params, x, labels, valid, opts):
        """Cross-entropy with seq-chunked logits (never materializes B,S,V)."""
        bsz, s, d = x.shape
        chunk = min(CE_CHUNK_SEQ, s)
        n = s // chunk if s % chunk == 0 else 1
        chunk = s // n
        xs = jnp.moveaxis(x.reshape(bsz, n, chunk, d), 1, 0)
        ls = jnp.moveaxis(labels.reshape(bsz, n, chunk), 1, 0)
        vs = jnp.moveaxis(valid.reshape(bsz, n, chunk), 1, 0)

        def body(carry, inp):
            ce_sum, tok_sum = carry
            xc, lc, vc = inp
            logits = self.logits(params, xc, opts)          # (B,chunk,V) f32
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None],
                                       axis=-1)[..., 0]
            ce = jnp.where(vc, logz - gold, 0.0)
            return (ce_sum + ce.sum(), tok_sum + vc.sum()), None

        # checkpoint: logits recompute in backward — never stored as
        # per-chunk scan residuals (B,chunk,V f32 would dominate memory)
        (ce, n_tok), _ = lax.scan(
            jax.checkpoint(body,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xs, ls, vs))
        return ce, n_tok

    # -- caches ----------------------------------------------------------------

    def _cache_tree(self, batch: int, seq_len: int, make_leaf,
                    kv_quantize: str | None = None,
                    paged=None) -> PyTree:
        cfg = self.cfg
        f = cfg.family
        dt = self.dtype
        def kv(n=None, inner=None):
            spec = B.block_cache_spec(cfg, batch, seq_len, dt, kv_quantize,
                                      paged)
            lead = tuple(d for d in (n, inner) if d is not None)
            tree = jax.tree.map(
                lambda s: make_leaf((*lead, *s.shape), s.dtype), spec)
            if paged is not None and "block_tables" in tree:
                bt = tree["block_tables"]
                if not isinstance(bt, jax.ShapeDtypeStruct):
                    # unallocated table rows must alias the dummy block,
                    # never physical block 0 (zeros would)
                    tree["block_tables"] = jnp.full(
                        bt.shape, paged.dummy_block, bt.dtype)
            return tree
        if f in ("dense", "moe"):
            out = {"blocks": kv(cfg.num_layers - cfg.moe_first_dense)}
            if f == "moe" and cfg.moe_first_dense:
                out["first"] = kv()
            return out
        if f == "vlm":
            t_img = cfg.num_image_tokens
            hd = cfg.resolved_head_dim
            kvshape = (self.n_super, batch, t_img, cfg.num_kv_heads, hd)
            return {
                "self": kv(self.n_super, self.n_self_per),
                "cross_kv": {"k": make_leaf(kvshape, dt),
                             "v": make_leaf(kvshape, dt)},
            }
        dims = ssm_mod.dims_from_config(cfg)
        sspec = ssm_mod.ssm_state_spec(batch, dims, dt)
        states = jax.tree.map(
            lambda s: make_leaf((cfg.num_layers, *s.shape), s.dtype), sspec)
        if f == "ssm":
            return {"blocks": states}
        if f == "hybrid":
            return {"blocks": states,
                    "shared": jax.tree.map(
                        lambda s: make_leaf((self.n_groups, *s.shape),
                                            s.dtype),
                        B.block_cache_spec(cfg, batch, seq_len, dt,
                                           kv_quantize))}
        raise ValueError(f)

    def cache_spec(self, batch: int, seq_len: int,
                   kv_quantize: str | None = None, paged=None) -> PyTree:
        return self._cache_tree(batch, seq_len, jax.ShapeDtypeStruct,
                                kv_quantize, paged)

    def init_cache(self, batch: int, seq_len: int,
                   kv_quantize: str | None = None, paged=None) -> PyTree:
        """For paged pools ``batch``/``seq_len`` are the leaf geometry
        ``(num_blocks + 1, block_size)``; block-table leaves take their
        ``(slots, blocks_per_slot)`` shape from the geometry and
        initialize to the dummy block."""
        return self._cache_tree(batch, seq_len,
                                lambda s, d: jnp.zeros(s, d), kv_quantize,
                                paged)

    def cache_plan(self, kv_quantize: str | None = None, paged=None
                   ) -> cache_mod.CachePlan:
        """The per-attention-layer :class:`repro.layers.cache.CachePlan`
        (one geometry for all of this model's attention layers)."""
        return cache_mod.build_cache_plan(self.cfg, self.dtype, kv_quantize,
                                          paged)

    def cache_plans(self, kv_quantize: str | None = None, paged=None
                    ) -> list[cache_mod.CachePlan]:
        """One plan per cached attention layer — the declarative source
        the serve pool and roofline derive ALL byte accounting from
        (recurrent SSM state is not a per-token KV stream: no plans)."""
        cfg = self.cfg
        f = cfg.family
        if f in ("dense", "moe"):
            n = cfg.num_layers
        elif f == "vlm":
            n = self.n_super * self.n_self_per
        elif f == "hybrid":
            n = self.n_groups
        else:                     # ssm / encoder: no attention KV pools
            return []
        return [self.cache_plan(kv_quantize, paged)] * n

    # -- prefill / decode -------------------------------------------------------

    def prefill(self, params: PyTree, batch: dict, cache: PyTree, *,
                last_pos: jax.Array | None = None, cache_plan=None,
                opts: B.BlockOpts = B.BlockOpts()
                ) -> tuple[jax.Array, PyTree]:
        """Fill the cache with a full prompt; returns (last-pos logits, cache).

        ``last_pos`` (scalar) is the index of the prompt's final *real*
        token — pass it when the prompt is right-padded (e.g. the serve
        engine's power-of-2 length buckets) so the returned logits are
        the real last token's, not the pad tail's.  Causal attention
        already keeps padded positions from influencing real ones
        (recurrent/MoE-capacity families must prefill unpadded — the
        engine does not bucket them), and the trunk masks pad positions
        out of the quantized-KV scale reduction.
        """
        x = self.embed(params, batch)
        bsz, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))
        prompt_len = None if last_pos is None else last_pos + 1
        x, new_cache, _ = self.trunk(params, x, positions=positions,
                                     cache=cache, batch=batch, opts=opts,
                                     prompt_len=prompt_len,
                                     cache_plan=cache_plan)
        if last_pos is None:
            xl = x[:, -1:, :]
        else:
            xl = lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        logits = self.logits(params, xl, opts)
        return logits, new_cache

    def prefill_chunk(self, params: PyTree, batch: dict, cache: PyTree, *,
                      start_pos: jax.Array, prompt_len: jax.Array,
                      cache_plan=None, opts: B.BlockOpts = B.BlockOpts()
                      ) -> tuple[jax.Array, PyTree]:
        """Continue a prefill one chunk at a time (continuous batching).

        ``batch["tokens"]`` (1, C) holds prompt positions
        ``[start_pos, start_pos + C)`` of a prompt whose
        ``[0, start_pos)`` K/V prefix is already written into ``cache``;
        the chunk's K/V lands at the offset and attention covers the
        whole causal prefix, so running a prompt chunk-by-chunk writes
        a cache (and produces last-token logits) identical to one-shot
        :meth:`prefill`.  The chunk may be right-padded (length
        bucketing): pass ``prompt_len`` as the chunk's real *end*
        position — ``min(prompt length, start_pos + real chunk len)`` —
        and pad rows beyond it are zeroed at the K/V write, so they can
        never corrupt mid-prompt positions or int8 scales, and
        causality hides them from every real query.

        Returns ``(logits, cache)`` with logits (1, 1, V) taken at the
        prompt's last *real* position when it falls inside this chunk
        (the final chunk; callers ignore the value for earlier chunks,
        where it is clamped to the chunk's last row).

        Attention-cached families only — recurrent state (SSM/hybrid)
        advances through pad tokens and MoE capacity routing is not
        pad-inert, so the serve scheduler prefills those families whole.
        """
        x = self.embed(params, batch)
        bsz, c = x.shape[:2]
        positions = jnp.broadcast_to(
            start_pos + jnp.arange(c)[None, :], (bsz, c))
        x, new_cache, _ = self.trunk(params, x, positions=positions,
                                     cache=cache, batch=batch, opts=opts,
                                     prompt_len=prompt_len,
                                     start_pos=start_pos,
                                     cache_plan=cache_plan)
        lp = jnp.clip(prompt_len - 1 - start_pos, 0, c - 1)
        xl = lax.dynamic_slice_in_dim(x, lp, 1, axis=1)
        logits = self.logits(params, xl, opts)
        return logits, new_cache

    def decode_step(self, params: PyTree, tokens: jax.Array,
                    positions: jax.Array, cache: PyTree, *,
                    cache_plan=None, opts: B.BlockOpts = B.BlockOpts()
                    ) -> tuple[jax.Array, PyTree]:
        """One token per sequence. tokens (B,1); positions (B,) absolute."""
        cfg = self.cfg
        if cfg.family == "encoder":
            raise ValueError("encoder-only model has no decode step")
        batch = {"tokens": tokens}
        x = self.embed(params, batch)
        pos2d = positions[:, None]
        x, new_cache, _ = self.trunk(params, x, positions=pos2d,
                                     cache=cache, cache_pos=positions,
                                     batch=batch, opts=opts,
                                     cache_plan=cache_plan)
        logits = self.logits(params, x, opts)
        return logits, new_cache
