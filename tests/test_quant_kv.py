"""Runtime KV quantization: round-trip bounds, fused decode kernel
parity, prefill bucketing, and end-to-end int8-KV serving."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.layers import attention as attn
from repro.quant import kv as kvq
from repro.quant.quantize import INT8_QMAX


def _rand_kv(key, b, s, kh, d, scale=1.0):
    return jax.random.normal(key, (b, s, kh, d), jnp.float32) * scale


# ---------------------------------------------------------------------------
# Quantize / dequantize round trips
# ---------------------------------------------------------------------------

class TestKVRoundTrip:
    def test_prefill_round_trip_error_bound(self, rng):
        x = _rand_kv(rng, 2, 32, 4, 64)
        q, scale = kvq.quantize_kv_prefill(x)
        back = kvq.dequantize_kv(q, scale)
        # symmetric int8: per-channel max abs error <= scale / 2
        bound = jnp.broadcast_to(scale[:, None] / 2 + 1e-8, x.shape)
        assert bool(jnp.all(jnp.abs(back - x) <= bound))
        rel = float(jnp.linalg.norm(back - x) / jnp.linalg.norm(x))
        assert rel < 1e-2

    def test_incremental_write_matches_one_shot(self, rng):
        """Decode-style token-by-token writes stay within one extra LSB
        of the one-shot prompt quantization."""
        b, s, kh, d = 2, 24, 2, 32
        x = _rand_kv(rng, b, s, kh, d)
        cache = jnp.zeros((b, s, kh, d), jnp.int8)
        scale = jnp.zeros((b, kh, d), jnp.float32)
        for t in range(s):
            cache, scale = kvq.kv_write_token(
                cache, scale, x[:, t], jnp.full((b,), t, jnp.int32))
        back = kvq.dequantize_kv(cache, scale)
        # running-max scale equals the one-shot scale after all writes
        _, scale_ref = kvq.quantize_kv_prefill(x)
        np.testing.assert_allclose(np.asarray(scale), np.asarray(scale_ref),
                                   rtol=1e-6)
        # rescale-in-place costs at most ~1 LSB on top of the half-LSB
        bound = jnp.broadcast_to(1.5 * scale[:, None] + 1e-8, x.shape)
        assert bool(jnp.all(jnp.abs(back - x) <= bound))

    def test_write_token_noop_when_scale_unchanged(self, rng):
        """A new token under the running max must not perturb history."""
        b, s, kh, d = 1, 8, 2, 16
        x = _rand_kv(rng, b, s, kh, d)
        cache, scale = kvq.quantize_kv_prefill(x)
        small = x[:, 0] * 1e-3          # well inside the existing scale
        cache2, scale2 = kvq.kv_write_token(cache, scale, small,
                                            jnp.full((b,), s - 1, jnp.int32))
        np.testing.assert_array_equal(np.asarray(scale2), np.asarray(scale))
        np.testing.assert_array_equal(np.asarray(cache2[:, :-1]),
                                      np.asarray(cache[:, :-1]))

    def test_zero_cache_dequantizes_to_zero(self):
        c = kvq.init_kv_cache_q(2, 16, 2, 8)
        assert c["k_q"].dtype == jnp.int8
        back = kvq.dequantize_kv(c["k_q"], c["k_scale"])
        assert float(jnp.abs(back).max()) == 0.0

    def test_values_clip_to_qmax(self, rng):
        x = _rand_kv(rng, 1, 4, 1, 8, scale=100.0)
        q, _ = kvq.quantize_kv_prefill(x)
        assert int(jnp.abs(q.astype(jnp.int32)).max()) <= INT8_QMAX

    def test_bytes_per_step_ratio(self):
        f32 = kvq.kv_bytes_per_step(4, 64, 2, 64)
        int8 = kvq.kv_bytes_per_step(4, 64, 2, 64, quantize="int8")
        assert f32 / int8 >= 3.5
        # int8 = 1 byte/elt + the f32 scale rows
        n = 4 * 64 * 2 * 64
        assert int8 == 2 * n + 2 * 4 * 2 * 64 * 4

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError):
            kvq.kv_cache_spec_q(1, 8, 1, 8, mode="int4")


# ---------------------------------------------------------------------------
# Fused decode-attention kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------

DECODE_SHAPES = [
    # b, s, h, kh, d, bs
    (2, 64, 8, 2, 64, 32),        # GQA group of 4
    (3, 100, 4, 4, 128, 64),      # MHA, unaligned S -> padding path
    (1, 16, 8, 1, 64, 128),       # MQA, S smaller than one block
    (4, 256, 4, 2, 64, 128),      # multi-block online softmax
]


class TestDecodeAttentionQKernel:
    @pytest.mark.parametrize("b,s,h,kh,d,bs", DECODE_SHAPES)
    def test_kernel_matches_ref(self, b, s, h, kh, d, bs, rng):
        ks = jax.random.split(jax.random.fold_in(rng, b * s), 4)
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32) * 0.5
        k_q, k_s = kvq.quantize_kv_prefill(_rand_kv(ks[1], b, s, kh, d))
        v_q, v_s = kvq.quantize_kv_prefill(_rand_kv(ks[2], b, s, kh, d))
        cache_pos = jax.random.randint(ks[3], (b,), 1, s - 1)
        got = ops.decode_attention_q(q, k_q, k_s, v_q, v_s, cache_pos,
                                     bs=bs, force_kernel=True)
        want = ref.decode_attention_q_ref(q, k_q, k_s, v_q, v_s, cache_pos)
        assert got.shape == want.shape == (b, 1, h, d)
        assert float(jnp.abs(got - want).max()) <= 1e-2

    def test_kernel_matches_ref_softcap(self, rng):
        b, s, h, kh, d = 2, 64, 4, 2, 64
        ks = jax.random.split(rng, 4)
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
        k_q, k_s = kvq.quantize_kv_prefill(_rand_kv(ks[1], b, s, kh, d))
        v_q, v_s = kvq.quantize_kv_prefill(_rand_kv(ks[2], b, s, kh, d))
        cache_pos = jnp.asarray([s - 1, 7])
        got = ops.decode_attention_q(q, k_q, k_s, v_q, v_s, cache_pos,
                                     softcap=30.0, force_kernel=True)
        want = ref.decode_attention_q_ref(q, k_q, k_s, v_q, v_s, cache_pos,
                                          softcap=30.0)
        assert float(jnp.abs(got - want).max()) <= 1e-2

    def test_ref_matches_f32_attention_on_dequantized_pool(self, rng):
        """The oracle itself == the engine's f32 decode attention run on
        the dequantized pool (same masking semantics)."""
        b, s, h, kh, d = 2, 32, 4, 2, 16
        ks = jax.random.split(rng, 4)
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
        k_q, k_s = kvq.quantize_kv_prefill(_rand_kv(ks[1], b, s, kh, d))
        v_q, v_s = kvq.quantize_kv_prefill(_rand_kv(ks[2], b, s, kh, d))
        cache_pos = jnp.asarray([5, s - 1])
        got = ref.decode_attention_q_ref(q, k_q, k_s, v_q, v_s, cache_pos)
        kd, vd = kvq.dequantize_kv(k_q, k_s), kvq.dequantize_kv(v_q, v_s)
        valid = jnp.arange(s)[None, :] <= cache_pos[:, None]
        want = attn._decode_attention(q, kd, vd, valid, 0.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_padding_positions_do_not_leak(self, rng):
        """S not a bs multiple: the wrapper pads, the validity mask must
        neutralize the padded tail."""
        b, s, h, kh, d = 1, 48, 2, 2, 64
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
        k_q, k_s = kvq.quantize_kv_prefill(_rand_kv(ks[1], b, s, kh, d))
        v_q, v_s = kvq.quantize_kv_prefill(_rand_kv(ks[2], b, s, kh, d))
        pos = jnp.asarray([s - 1])
        got = ops.decode_attention_q(q, k_q, k_s, v_q, v_s, pos,
                                     bs=32, force_kernel=True)
        want = ref.decode_attention_q_ref(q, k_q, k_s, v_q, v_s, pos)
        assert float(jnp.abs(got - want).max()) <= 1e-2

    def test_vmem_fallback_dispatch(self):
        assert ops.kernel_fits("decode_attn_q", 4, c=64, s=128, r=4)
        # an absurd GQA group * head_dim blows the budget -> ref path
        assert not ops.kernel_fits("decode_attn_q", 4, c=4096, s=128,
                                   r=4096, bn=4096)


# ---------------------------------------------------------------------------
# Attention-layer integration (quantized cache dict drives the branch)
# ---------------------------------------------------------------------------

class TestAttentionKVQuantized:
    def test_cache_spec_variants(self):
        spec = attn.kv_cache_spec(2, 16, 2, 8, jnp.float32, "int8")
        assert set(spec) == {"k_q", "k_scale", "v_q", "v_scale"}
        assert spec["k_q"].dtype == jnp.int8
        plain = attn.kv_cache_spec(2, 16, 2, 8, jnp.float32)
        assert set(plain) == {"k", "v"}
        init = attn.init_kv_cache(2, 16, 2, 8, jnp.float32, "int8")
        assert kvq.is_quantized_kv(init)
        assert not kvq.is_quantized_kv(attn.init_kv_cache(
            2, 16, 2, 8, jnp.float32))

    def test_prefill_then_decode_close_to_f32(self, rng):
        """One attention layer, prefill + 3 decode steps, int8 cache vs
        f32 cache: outputs agree to quantization error."""
        from repro.layers.param import ParamBuilder
        d_model, h, kh, hd = 32, 4, 2, 8
        pb = ParamBuilder(rng, jnp.float32)
        attn.init_attention(pb, "a", d_model, h, kh, hd)
        p = pb.params["a"]
        b, s_prompt, s_max = 2, 5, 16
        x = jax.random.normal(jax.random.fold_in(rng, 1),
                              (b, s_prompt, d_model), jnp.float32) * 0.3
        pos = jnp.broadcast_to(jnp.arange(s_prompt)[None], (b, s_prompt))
        kw = dict(num_heads=h, num_kv_heads=kh, head_dim=hd,
                  rope_theta=1e4, positions=pos)
        caches = {}
        for mode in (None, "int8"):
            cache = attn.init_kv_cache(b, s_max, kh, hd, jnp.float32, mode)
            o, cache = attn.apply_attention(p, x, cache=cache, **kw)
            outs = [o]
            for t in range(3):
                cp = jnp.full((b,), s_prompt + t, jnp.int32)
                xt = jax.random.normal(jax.random.fold_in(rng, 10 + t),
                                       (b, 1, d_model), jnp.float32) * 0.3
                o, cache = attn.apply_attention(
                    p, xt, cache=cache, cache_pos=cp,
                    **{**kw, "positions": cp[:, None]})
                outs.append(o)
            caches[mode] = (outs, cache)
        for of, oq in zip(*[caches[m][0] for m in (None, "int8")]):
            assert float(jnp.abs(of - oq).max()) < 5e-2
        assert caches["int8"][1]["k_q"].dtype == jnp.int8


# ---------------------------------------------------------------------------
# End-to-end serving + admission bucketing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import registry
    from repro.configs.base import ParallelConfig, RunConfig
    from repro.models.api import get_model

    # f32 model dtype: the comparison isolates KV quantization error
    # (bf16 rounding would otherwise flip near-tied greedy argmaxes).
    cfg = dataclasses.replace(registry.get("llama3.2-1b").smoke,
                              dtype="float32")
    run = RunConfig(model=cfg, parallel=ParallelConfig())
    m = get_model(cfg)
    params, _ = m.init(jax.random.PRNGKey(0))
    return run, m, params


def _run_engine(run, params, *, kv_quantize=None, lrd=None, slots=2,
                prompts=((1, 2, 3), (4, 5, 6, 7), (2,)), n=6):
    from repro.serve.engine import Request, ServeEngine
    run2 = run if lrd is None else dataclasses.replace(run, lrd=lrd)
    eng = ServeEngine(run2, params, slots=slots, max_seq=64,
                      kv_quantize=kv_quantize)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng, [r.output for r in reqs]


class TestServeKVQuantized:
    def test_int8_kv_greedy_matches_f32(self, serve_setup):
        run, m, params = serve_setup
        _, out_f = _run_engine(run, params)
        eng, out_q = _run_engine(run, params, kv_quantize="int8")
        assert out_f == out_q
        # the pool stayed int8 after prefill inserts + decode scatters
        leaves = jax.tree_util.tree_flatten_with_path(eng.cache)[0]
        dtypes = {str(getattr(p[-1], "key", p[-1])): l.dtype
                  for p, l in leaves}
        assert dtypes["k_q"] == jnp.int8 and dtypes["v_q"] == jnp.int8
        assert dtypes["k_scale"] == jnp.float32

    def test_int8_kv_with_pallas_kernel(self, serve_setup):
        """lrd.use_pallas routes decode through the fused kernel
        (interpret mode on CPU) — outputs match the jnp oracle path."""
        from repro.configs.base import LRDConfig
        run, m, params = serve_setup
        _, out_ref = _run_engine(run, params, kv_quantize="int8",
                                 prompts=((1, 2, 3),), n=3)
        _, out_k = _run_engine(run, params, kv_quantize="int8",
                               lrd=LRDConfig(use_pallas=True),
                               prompts=((1, 2, 3),), n=3)
        assert out_ref == out_k

    def test_kv_bytes_accounting(self, serve_setup):
        run, m, params = serve_setup
        eng_f, _ = _run_engine(run, params)
        eng_q, _ = _run_engine(run, params, kv_quantize="int8")
        bf = eng_f.plan_summary["kv_bytes_per_step"]
        bq = eng_q.plan_summary["kv_bytes_per_step"]
        assert bf / bq >= 3.5

    def test_config_knob_drives_engine(self, serve_setup):
        from repro.configs.base import LRDConfig
        run, m, params = serve_setup
        lrd = dataclasses.replace(LRDConfig(), kv_quantize="int8")
        eng, out = _run_engine(run, params, lrd=lrd, prompts=((1, 2, 3),),
                               n=2)
        assert eng.kv_quantize == "int8"
        assert kvq.is_quantized_kv(
            jax.tree.leaves(eng.cache, is_leaf=kvq.is_quantized_kv)[0])


class TestPrefillBucketing:
    def test_bucket_lengths(self, serve_setup):
        from repro.serve.engine import ServeEngine
        run, m, params = serve_setup
        eng = ServeEngine(run, params, slots=1, max_seq=64)
        assert eng._bucket_len(1) == 8 and eng._bucket_len(8) == 8
        assert eng._bucket_len(9) == 16 and eng._bucket_len(33) == 64
        assert eng._bucket_len(60) == 64    # capped at max_seq

    def test_no_retrace_within_bucket(self, serve_setup):
        run, m, params = serve_setup
        eng, _ = _run_engine(run, params,
                             prompts=((1, 2, 3), (4, 5, 6, 7), (2, 3)), n=2)
        # lengths 3, 4, 2 all land in the 8-bucket: ONE compiled prefill
        assert eng._jit_prefill._cache_size() == 1
        # admit rounds of varying size pad to (slots, V): the sampler
        # shares the decode path's single compiled shape
        assert eng._jit_sample_all._cache_size() == 1

    def test_padded_tail_masked_in_pool(self, serve_setup):
        """Bucket padding beyond the prompt must land as zeros in the
        inserted slot (int8 pool: dequantizes to exact zero)."""
        run, m, params = serve_setup
        eng, _ = _run_engine(run, params, kv_quantize="int8",
                             prompts=((1, 2, 3),), n=1, slots=1)
        k_q = eng.cache["blocks"]["k_q"]          # (L, slots, S, KH, D)
        n_written = 3 + 1                         # prompt + 1 decode step
        tail = k_q[:, :, n_written:]
        assert int(jnp.abs(tail.astype(jnp.int32)).max()) == 0

    def test_recurrent_family_not_bucketed(self):
        """SSM state advances through pad tokens, so ssm/hybrid prompts
        must prefill unpadded — and still serve correctly."""
        from repro.configs import registry
        from repro.configs.base import ParallelConfig, RunConfig
        from repro.models.api import get_model
        from repro.serve.engine import Request, ServeEngine
        cfg = registry.get("mamba2-2.7b").smoke
        run = RunConfig(model=cfg, parallel=ParallelConfig())
        m = get_model(cfg)
        params, _ = m.init(jax.random.PRNGKey(0))
        eng = ServeEngine(run, params, slots=1, max_seq=32)
        assert eng._bucket_len(3) == 3 and eng._bucket_len(9) == 9
        # pure-SSM model: recurrent state is not a KV stream
        assert eng.plan_summary["kv_bytes_per_step"] == 0
        prompt = [5, 9, 2]
        req = Request(uid=0, prompt=prompt, max_new_tokens=4)
        eng.add_request(req)
        eng.run_until_done()
        toks = list(prompt)
        for _ in range(4):
            x, _ = m.forward(params, {"tokens": jnp.asarray([toks])})
            logits = m.logits(params, x)
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert req.output == toks[len(prompt):]

    def test_prompt_len_masks_quantization_scales(self, rng):
        """Padded prefill with prompt_len produces the same int8 cache
        (values AND scales) as the unpadded prompt — bucket padding
        cannot inflate the per-channel scales."""
        from repro.layers.param import ParamBuilder
        d_model, h, kh, hd = 32, 4, 2, 8
        pb = ParamBuilder(rng, jnp.float32)
        attn.init_attention(pb, "a", d_model, h, kh, hd)
        p = pb.params["a"]
        n, bucket, s_max = 3, 8, 16
        x = jax.random.normal(jax.random.fold_in(rng, 2),
                              (1, bucket, d_model), jnp.float32)
        def prefill(xx, plen):
            s = xx.shape[1]
            pos = jnp.arange(s)[None, :]
            cache = attn.init_kv_cache(1, s_max, kh, hd, jnp.float32, "int8")
            _, c = attn.apply_attention(
                p, xx, num_heads=h, num_kv_heads=kh, head_dim=hd,
                rope_theta=1e4, positions=pos, cache=cache,
                prompt_len=plen)
            return c
        padded = prefill(x, jnp.asarray(n))
        exact = prefill(x[:, :n], None)
        np.testing.assert_array_equal(np.asarray(padded["k_scale"]),
                                      np.asarray(exact["k_scale"]))
        np.testing.assert_array_equal(np.asarray(padded["k_q"][:, :n]),
                                      np.asarray(exact["k_q"][:, :n]))
        assert int(jnp.abs(
            padded["k_q"][:, n:].astype(jnp.int32)).max()) == 0

    def test_bucketed_outputs_match_unpadded_reference(self, serve_setup):
        """Greedy outputs equal the repeated-full-forward reference even
        though the prompt was padded to a bucket."""
        run, m, params = serve_setup
        prompt = [5, 9, 2]                        # length 3 -> bucket 8
        _, outs = _run_engine(run, params, prompts=(tuple(prompt),), n=5)
        toks = list(prompt)
        for _ in range(5):
            x, _ = m.forward(params, {"tokens": jnp.asarray([toks])})
            logits = m.logits(params, x)
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert outs[0] == toks[len(prompt):]


class TestTokenMatchRegression:
    """Fixed-seed pin of the int8-KV greedy agreement at slots=2 /
    s_max=64: 0.9688, i.e. 31 of 32 tokens.  A silent drop here means
    a KV-quant accuracy regression that the allclose tests are too
    loose to catch."""

    PINNED = 31 / 32                  # 0.9688, unrounded

    def test_int8_kv_decode_token_match_pinned(self, serve_setup):
        # 4 requests on (2 slots, 64 positions) whose prompt lengths
        # straddle two power-of-2 buckets.
        run, m, params = serve_setup
        prompts = tuple(tuple([(i % 7) + 1] * (3 + (i % 8)))
                        for i in range(4))
        _, out_f = _run_engine(run, params, prompts=prompts, n=8)
        _, out_q = _run_engine(run, params, kv_quantize="int8",
                               prompts=prompts, n=8)
        flat_f = [t for o in out_f for t in o]
        flat_q = [t for o in out_q for t in o]
        assert len(flat_f) == len(flat_q) == 32
        match = sum(a == b for a, b in zip(flat_f, flat_q)) / len(flat_f)
        assert match >= self.PINNED - 1e-9, (
            f"int8-KV token_match regressed: {match:.4f} < {self.PINNED}")
