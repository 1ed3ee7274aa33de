"""Sharding-rule unit tests on a tiny host mesh (no forced device count)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.configs.base import ParallelConfig
from repro.launch.mesh import make_mesh
from repro.layers.param import (EMBED, EXPERTS, FFN, LAYERS, QKV, RANK,
                                VOCAB)
from repro.parallel import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    # single CPU device: (1, 1) mesh — rule resolution is shape-logic only
    return make_mesh((1, 1), ("data", "model"))


def spec_of(mesh, axes, shape, parallel):
    tree_p = {"x": jax.ShapeDtypeStruct(shape, jnp.float32)}
    tree_a = {"x": axes}
    s = shd.make_param_shardings(mesh, tree_p, tree_a, parallel)
    return s["x"].spec


class TestParamRules:
    def test_megatron_pattern(self, mesh):
        par = ParallelConfig()
        assert spec_of(mesh, (EMBED, FFN), (64, 128), par) == P(None, "model")
        assert spec_of(mesh, (FFN, EMBED), (128, 64), par) == P("model")

    def test_fsdp_2d(self, mesh):
        par = ParallelConfig(fsdp=True)
        assert spec_of(mesh, (EMBED, FFN), (64, 128), par) \
            == P("data", "model")

    def test_rank_inherits_fsdp(self, mesh):
        par = ParallelConfig(fsdp=True)
        # w1 of an expert bank: (EXPERTS, RANK, FFN)
        got = spec_of(mesh, (EXPERTS, RANK, FFN), (4, 8, 128), par)
        assert got == P("model", "data")  # EP + rank-FSDP; FFN loses model

    def test_rank_replicated_by_default(self, mesh):
        par = ParallelConfig()
        assert spec_of(mesh, (EMBED, RANK), (64, 8), par) == P()

    def test_shard_rank_variant(self, mesh):
        par = ParallelConfig(shard_rank=True)
        assert spec_of(mesh, (EMBED, RANK), (64, 8), par) == P(None, "model")
        # conflict: output dim wins the model axis over rank
        assert spec_of(mesh, (RANK, FFN), (8, 128), par) == P(None, "model")

    def test_indivisible_replicates_with_note(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        par = ParallelConfig()
        notes = []
        tree_p = {"x": jax.ShapeDtypeStruct((7, 13), jnp.float32)}
        tree_a = {"x": (VOCAB, EMBED)}
        # fake a mesh dim >1 via a purpose-built check: use model size 1 ->
        # always divisible; so instead check the note machinery directly
        from repro.parallel.sharding import _spec_for

        class FakeMesh:
            shape = {"data": 16, "model": 16}
        got = _spec_for((VOCAB, EMBED), (50280, 64), {VOCAB: "model",
                                                      EMBED: None},
                        FakeMesh(), notes, "embed/w")
        assert got == P()
        assert notes and "not divisible" in notes[0]

    def test_layer_stack_axis_never_sharded(self, mesh):
        par = ParallelConfig(fsdp=True)
        got = spec_of(mesh, (LAYERS, EMBED, QKV), (4, 64, 128), par)
        assert got == P(None, "data", "model")


class TestQuantizedParamRules:
    """Quant-aware sharding: trees rewritten by quantize_tree *after* the
    axes were built still resolve — ``k_q`` inherits ``k``'s spec,
    ``k_scale`` shards on the out dim (or replicates)."""

    def _shardings(self, mesh, params, axes, par, **quant_kw):
        from repro.quant import quantize_tree
        qp = quantize_tree(params, **quant_kw)
        return qp, shd.make_param_shardings(mesh, qp, axes, par)

    def test_svd_pair_q_inherits_base_spec(self, mesh):
        par = ParallelConfig(fsdp=True)
        params = {"up": {"w0": jnp.ones((64, 8)), "w1": jnp.ones((8, 128))}}
        axes = {"up": {"w0": (EMBED, RANK), "w1": (RANK, FFN)}}
        qp, s = self._shardings(mesh, params, axes, par)
        assert set(qp["up"]) == {"w0_q", "w0_scale", "w1_q", "w1_scale"}
        base = shd.make_param_shardings(mesh, params, axes, par)
        assert s["up"]["w0_q"].spec == base["up"]["w0"].spec
        assert s["up"]["w1_q"].spec == base["up"]["w1"].spec
        # scales: input axis collapsed to 1 -> out dim shards, rest None
        assert s["up"]["w1_scale"].spec == P(None, "model")
        assert s["up"]["w0_scale"].spec == P(None, "data")  # rank FSDP-shards

    def test_branched_q_inherits_base_spec(self, mesh):
        from repro.layers.param import BRANCH
        par = ParallelConfig(fsdp=True, shard_rank=True)
        params = {"u": jnp.ones((4, 64, 8)), "xc": jnp.ones((4, 8, 8)),
                  "v": jnp.ones((4, 8, 128))}
        axes = {"u": (BRANCH, EMBED, RANK), "xc": (BRANCH, RANK, RANK),
                "v": (BRANCH, RANK, FFN)}
        qp, s = self._shardings(mesh, params, axes, par)
        base = shd.make_param_shardings(mesh, params, axes, par)
        for k in ("u", "xc", "v"):
            assert s[k + "_q"].spec == base[k].spec, k
        assert s["v_scale"].spec == P(None, None, "model")

    def test_partial_quant_targets_mixed_tree(self, mesh):
        par = ParallelConfig(fsdp=True)
        params = {"w0": jnp.ones((64, 8)), "w1": jnp.ones((8, 128))}
        axes = {"w0": (EMBED, RANK), "w1": (RANK, FFN)}
        qp, s = self._shardings(mesh, params, axes, par, targets=("w0",))
        assert set(qp) == {"w0_q", "w0_scale", "w1"}
        base = shd.make_param_shardings(mesh, params, axes, par)
        assert s["w0_q"].spec == base["w0"].spec
        assert s["w1"].spec == base["w1"].spec

    def test_quantize_tree_rewrites_axes_tree(self):
        from repro.layers.param import NONE
        from repro.quant import quantize_tree, scale_axes
        params = {"up": {"w0": jnp.ones((64, 8)), "w1": jnp.ones((8, 128))},
                  "norm": {"scale": jnp.ones((64,))}}
        axes = {"up": {"w0": (EMBED, RANK), "w1": (RANK, FFN)},
                "norm": {"scale": (EMBED,)}}
        qp, qa = quantize_tree(params, axes=axes)
        assert qa["up"]["w0_q"] == (EMBED, RANK)
        assert qa["up"]["w0_scale"] == (NONE, RANK)
        assert qa["up"]["w1_scale"] == scale_axes((RANK, FFN)) == (NONE, FFN)
        assert qa["norm"]["scale"] == (EMBED,)          # untouched
        # rewritten axes resolve without the alignment fallback too
        mesh = make_mesh((1, 1), ("data", "model"))
        s = shd.make_param_shardings(mesh, qp, qa, ParallelConfig(fsdp=True))
        assert s["up"]["w1_scale"].spec == P(None, "model")

    def test_unresolvable_key_raises(self, mesh):
        from repro.quant import align_quantized_axes
        with pytest.raises(KeyError):
            align_quantized_axes({"mystery": jnp.ones((2, 2))},
                                 {"w0": (EMBED, RANK)})

    def test_quantize_tree_missing_axes_entry_raises(self):
        from repro.quant import quantize_tree
        params = {"w0": jnp.ones((64, 8)), "w1": jnp.ones((8, 128))}
        with pytest.raises(KeyError, match="w1"):
            quantize_tree(params, axes={"w0": (EMBED, RANK)})


class TestCacheRules:
    def test_kv_cache_seq_over_model(self, mesh):
        par = ParallelConfig()
        spec = {"k": jax.ShapeDtypeStruct((4, 8, 128, 2, 16), jnp.bfloat16)}
        got = shd.cache_shardings(mesh, spec, par, batch=8, seq_len=128)
        assert got["k"].spec == P(None, "data", "model")

    def test_b1_decode_seq_both_axes(self):
        # abstract 16x16 mesh: B=1 is NOT divisible by data -> the seq dim
        # takes both axes (the long_500k decode layout)
        mesh = jax.sharding.AbstractMesh(
            (16, 16), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        par = ParallelConfig(decode_seq_shard=True)
        spec = {"k": jax.ShapeDtypeStruct((2, 1, 512, 2, 16), jnp.bfloat16)}
        got = shd.cache_shardings(mesh, spec, par, batch=1, seq_len=512)
        assert got["k"].spec == P(None, None, ("data", "model"))

    def test_ssm_state_heads_over_model(self, mesh):
        par = ParallelConfig()
        spec = {"ssm": jax.ShapeDtypeStruct((4, 8, 16, 8, 4), jnp.float32)}
        got = shd.cache_shardings(mesh, spec, par, batch=8, seq_len=999)
        assert got["ssm"].spec == P(None, "data", "model")


class TestActivationRules:
    def test_batch_and_ffn(self, mesh):
        par = ParallelConfig()
        rule = shd.activation_resolver(mesh, par)
        from repro.layers.param import BATCH, SEQ
        s = rule((BATCH, SEQ, FFN), (8, 16, 64))
        assert s.spec == P("data", None, "model")

    def test_seq_shard_toggle(self, mesh):
        from repro.layers.param import BATCH, SEQ
        on = shd.activation_resolver(mesh, ParallelConfig(seq_shard=True))
        off = shd.activation_resolver(mesh, ParallelConfig())
        assert on((BATCH, SEQ, EMBED), (8, 16, 64)).spec \
            == P("data", "model")
        assert off((BATCH, SEQ, EMBED), (8, 16, 64)).spec == P("data")
