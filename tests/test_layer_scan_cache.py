"""The layer scan's carried cache against the per-layer form it replaced.

``LMModel`` scans its stacked blocks with the stacked KV cache in the
scan's carry: each layer writes its new rows into the stack in place and
attends over its own layer read back from it.  The reference here is
the plain per-layer form: the scan takes the stacked cache as ``xs``, so
each layer's cache is sliced out, written and attended through the
block as one layer's cache, and the written layers are stacked again as
``ys``.  (A Python loop over the layers would compile to other fusions,
whose rounding differs in the last bit even in float32.)  Both run the
same chunked prefill and then a few decode steps, for every cache
family the flat scan serves (bf16 slot, int8 slot, paged bf16, paged
int8, MLA latent); the logits and the whole cache must come out equal,
bit for bit.  One decode token is scaled up so that int8 channel maxima
grow mid-decode and the history requant runs inside the scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.layers import cache as cache_mod
from repro.models import blocks as B
from repro.models.api import get_model
from repro.quant import kv as kvq

GQA = ModelConfig(name="scan-gqa", num_layers=3, d_model=64, num_heads=4,
                  num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128,
                  act="gelu", dtype="bfloat16")
MLA = ModelConfig(name="scan-mla", num_layers=3, d_model=64, num_heads=4,
                  num_kv_heads=4, d_ff=128, vocab_size=128, act="gelu",
                  mla=True, kv_lora_rank=32, qk_rope_dim=16, qk_nope_dim=16,
                  v_head_dim=16, dtype="bfloat16")

MAX_SEQ, CHUNK, BLOCK = 32, 8, 8
PROMPTS = (19, 11)            # one prompt per slot, chunked by CHUNK
DECODE_STEPS = 4
BIG_STEP = 2                  # the decode step fed the scaled-up token
BIG_TOKEN = 5

#: family -> (config, pool kv_quantize, paged)
FAMILIES = {
    "bf16": (GQA, None, False),
    "int8": (GQA, "int8", False),
    "paged_bf16": (GQA, None, True),
    "paged_int8": (GQA, "int8", True),
    "mla": (MLA, None, False),
}


def _loop_trunk(cfg, params, x, cache, plan, **kw):
    """The reference: slice, write and attend, stack, layer by layer."""
    def body(h, xs):
        p_l, c_l = xs
        h, c_l, _ = B.apply_block(p_l, h, cfg, cache=c_l, cache_plan=plan,
                                  **kw)
        return h, c_l
    x, layers = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    return x, {"blocks": layers}


def _loop_chunk(model, params, tokens, cache, start, end, plan):
    c = tokens.shape[1]
    x = model.embed(params, {"tokens": tokens})
    positions = start + jnp.arange(c)[None, :]
    x, cache = _loop_trunk(model.cfg, params, x, cache, plan,
                           positions=positions, prompt_len=end,
                           start_pos=start)
    last = jnp.clip(end - 1 - start, 0, c - 1)
    return model.logits(params, x[:, last][:, None]), cache


def _loop_decode(model, params, tokens, positions, cache, plan):
    x = model.embed(params, {"tokens": tokens})
    x, cache = _loop_trunk(model.cfg, params, x, cache, plan,
                           positions=positions[:, None],
                           cache_pos=positions)
    return model.logits(params, x), cache


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _to_blocks(slot_pool, plan):
    """A slot pool ``(L, B, S, ...)`` laid out as the paged plan's
    blocks (slot ``b``'s block ``j`` at physical ``b * nblk + j``, the
    dummy block last), int8 values quantized per block."""
    geom = plan.paged
    out = {}
    for name in ("k", "v"):
        x = slot_pool[name]
        n_l, b, s = x.shape[:3]
        blocks = x.reshape(n_l, b * s // BLOCK, BLOCK, *x.shape[3:])
        blocks = jnp.concatenate([blocks, jnp.zeros_like(blocks[:, :1])], 1)
        if plan.quantized:
            q, scale = jax.vmap(kvq.quantize_kv_prefill)(blocks)
            out[name + "_q"], out[name + "_scale"] = q, scale
        else:
            out[name] = blocks
    tables = jnp.arange(geom.num_blocks).reshape(geom.slots,
                                                  geom.blocks_per_slot)
    out["block_tables"] = jnp.broadcast_to(
        tables, (x.shape[0], *tables.shape)).astype(jnp.int32)
    return {"blocks": out}


def _prefill(model, params, prompts, stream_q):
    """Chunked prefill of every prompt into its own stream cache, the
    carried and the loop form side by side; returns both pools."""
    plan = model.cache_plan(stream_q)
    pools = {"scan": [], "loop": []}
    for toks in prompts:
        caches = {f: model.init_cache(1, MAX_SEQ, kv_quantize=stream_q)
                  for f in pools}
        for start in range(0, toks.shape[1], CHUNK):
            chunk = toks[:, start:start + CHUNK]
            end = jnp.asarray(start + chunk.shape[1])
            s = jnp.asarray(start)
            got, caches["scan"] = jax.jit(
                lambda c, ch, s, e: model.prefill_chunk(
                    params, {"tokens": ch}, c, start_pos=s, prompt_len=e,
                    cache_plan=plan))(caches["scan"], chunk, s, end)
            want, caches["loop"] = jax.jit(
                lambda c, ch, s, e: _loop_chunk(model, params, ch, c, s, e,
                                                plan))(
                caches["loop"], chunk, s, end)
            _assert_same((got, caches["scan"]), (want, caches["loop"]))
        for f in pools:
            pools[f].append(caches[f])
    return {f: jax.tree.map(lambda *t: jnp.concatenate(t, 1), *pools[f])
            for f in pools}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_carried_cache_matches_the_per_layer_loop(family):
    cfg, kv_quantize, paged = FAMILIES[family]
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    emb = params["embed"]["w"]
    params["embed"]["w"] = emb.at[BIG_TOKEN].set(emb[BIG_TOKEN] * 64)
    rng = np.random.default_rng(1)
    prompts = [jnp.asarray(rng.integers(6, cfg.vocab_size, (1, n)),
                           jnp.int32) for n in PROMPTS]
    slots = len(PROMPTS)

    # paged pools stage prompts full-precision, as serve does
    pools = _prefill(model, params, prompts, None if paged else kv_quantize)
    geom = None
    if paged:
        geom = cache_mod.PagedGeometry(BLOCK, slots * MAX_SEQ // BLOCK,
                                       slots, MAX_SEQ // BLOCK)
    plan = model.cache_plan(kv_quantize, paged=geom)
    if paged:
        pools = {f: _to_blocks(p["blocks"], plan) for f, p in pools.items()}

    scan = jax.jit(lambda t, p, c: model.decode_step(params, t, p, c,
                                                     cache_plan=plan))
    loop = jax.jit(lambda t, p, c: _loop_decode(model, params, t, p, c,
                                                plan))
    positions = jnp.asarray(PROMPTS, jnp.int32)
    grew = False
    for step in range(DECODE_STEPS):
        tok = BIG_TOKEN if step == BIG_STEP else 7 + step
        tokens = jnp.full((slots, 1), tok, jnp.int32)
        before = pools["scan"]
        got, pools["scan"] = scan(tokens, positions, pools["scan"])
        want, pools["loop"] = loop(tokens, positions, pools["loop"])
        _assert_same((got, pools["scan"]), (want, pools["loop"]))
        if kv_quantize and step == BIG_STEP:
            grew = bool(jnp.any(pools["scan"]["blocks"]["k_scale"]
                                > before["blocks"]["k_scale"]))
        positions = positions + 1
    assert grew or not kv_quantize, "no int8 channel maximum grew"
