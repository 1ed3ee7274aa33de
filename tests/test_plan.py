"""LinearPlan: classification, kernel eligibility, accounting, execution.

The plan is the one seam every consumer dispatches through; these tests
pin its contract — including the satellite fix that decode-shaped
``(B, 1, d)`` activations reach the fused kernels (the old
``x.ndim == 2`` gate is gone).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.layers import plan as lplan
from repro.layers.param import (apply_linear, linear_flops, linear_out_dim,
                                linear_param_count, linear_quant_bytes)
from repro.quant import quantize_tree


def _lowrank(rng, c=128, r=32, s=64):
    ks = jax.random.split(rng, 2)
    return {"w0": jax.random.normal(ks[0], (c, r)) * 0.1,
            "w1": jax.random.normal(ks[1], (r, s)) * 0.1}


def _branched(rng, n=4, c=128, r1=16, r2=16, s=64):
    ks = jax.random.split(rng, 3)
    return {"u": jax.random.normal(ks[0], (n, c, r1)) * 0.1,
            "xc": jax.random.normal(ks[1], (n, r1, r2)) * 0.1,
            "v": jax.random.normal(ks[2], (n, r2, s)) * 0.1}


class TestClassification:
    def test_kinds(self, rng):
        assert lplan.build_plan({"w": jnp.zeros((8, 16))}).kind == "dense"
        assert lplan.build_plan(_lowrank(rng)).kind == "lowrank"
        assert lplan.build_plan(_branched(rng)).kind == "branched"
        tk = {"tucker_u": jnp.zeros((16, 4)), "core": jnp.zeros((3, 3, 4, 4)),
              "tucker_v": jnp.zeros((4, 16))}
        assert lplan.build_plan(tk).kind == "tucker_conv"
        bt = {"u": jnp.zeros((2, 16, 4)), "core": jnp.zeros((2, 3, 3, 4, 4)),
              "v": jnp.zeros((2, 4, 16))}
        assert lplan.build_plan(bt).kind == "branched_tucker_conv"

    def test_quantized_trees_keep_kind(self, rng):
        for tree, kind in ((_lowrank(rng), "lowrank"),
                           (_branched(rng), "branched")):
            plan = lplan.build_plan(quantize_tree(tree))
            assert plan.kind == kind
            assert plan.fully_quantized and plan.quantized

    def test_partial_quant_is_not_fully_quantized(self, rng):
        plan = lplan.build_plan(quantize_tree(_lowrank(rng),
                                              targets=("w0",)))
        assert plan.quantized and not plan.fully_quantized

    def test_not_a_linear_raises(self):
        with pytest.raises(ValueError):
            lplan.build_plan({"scale": jnp.ones((8,))})

    def test_plans_cached_per_geometry(self, rng):
        a, b = _lowrank(rng), _lowrank(jax.random.fold_in(rng, 1))
        assert lplan.build_plan(a) is lplan.build_plan(b)

    def test_builds_from_shape_structs(self):
        p = {"w0": jax.ShapeDtypeStruct((64, 8), jnp.float32),
             "w1": jax.ShapeDtypeStruct((8, 64), jnp.float32)}
        plan = lplan.build_plan(p)
        assert plan.kind == "lowrank" and plan.d_out == 64


class TestKernelEligibility:
    def test_decode_shaped_activations_are_eligible(self, rng):
        """Satellite: (B, 1, d) decode activations reach the kernels —
        the wrappers flatten leading dims, the plan no longer gates on
        x.ndim == 2."""
        plan = lplan.build_plan(_lowrank(rng))
        assert plan.kernel_for((4, 1, 128), True) == "lowrank"
        assert plan.kernel_for((2, 3, 128), True) == "lowrank"
        assert plan.kernel_for((16, 128), True) == "lowrank"
        assert plan.kernel_for((16, 128), False) is None

    def test_quantized_kernel_names(self, rng):
        assert lplan.build_plan(quantize_tree(_lowrank(rng))) \
            .kernel_for((8, 1, 128), True) == "lowrank_q"
        assert lplan.build_plan(quantize_tree(_branched(rng))) \
            .kernel_for((8, 1, 128), True) == "branched_q"

    def test_partial_quant_takes_reference_path(self, rng):
        plan = lplan.build_plan(quantize_tree(_lowrank(rng),
                                              targets=("w1",)))
        assert plan.kernel_for((16, 128), True) is None

    def test_stacked_factors_not_eligible(self):
        p = {"w0": jnp.zeros((4, 64, 8)), "w1": jnp.zeros((4, 8, 64))}
        assert lplan.build_plan(p).kernel_for((16, 64), True) is None

    def test_oversize_falls_back(self):
        p = {"w0": jnp.zeros((16384, 4096)), "w1": jnp.zeros((4096, 8192))}
        assert lplan.build_plan(p).kernel_for((1 << 20, 16384), True) is None

    def test_dense_and_conv_have_no_kernel(self, rng):
        assert lplan.build_plan({"w": jnp.zeros((64, 64))}) \
            .kernel_for((8, 64), True) is None


class TestKernelCensus:
    """The engine's plan summary says which linears run a kernel, which
    fall back to the jnp reference because they do not fit, and which
    no kernel serves — for stacked (scanned) plans too."""

    def _tree(self, rng):
        return {"blocks": {"up": {"w0": jnp.zeros((4, 128, 32)),
                                  "w1": jnp.zeros((4, 32, 64))}},
                "head": _lowrank(rng),
                "embed": {"w": jnp.zeros((64, 128))}}

    def test_counts_per_scan_step(self, rng):
        plans = lplan.build_plan_tree(self._tree(rng))
        assert lplan.kernel_census(plans, 8, use_pallas=True) == \
            {"jnp": 1, "lowrank": 5}
        assert lplan.kernel_census(plans, 8, use_pallas=False) == \
            {"jnp": 6}

    def test_expert_banks_run_jnp(self):
        bank = {"w0": jnp.zeros((2, 4, 128, 32)),
                "w1": jnp.zeros((2, 4, 32, 64))}
        plans = lplan.build_plan_tree({"moe": {"experts": {"up": bank}}})
        assert lplan.kernel_census(plans, 8, use_pallas=True) == {"jnp": 8}

    def test_unfit_kernel_counts_as_ref(self, rng, monkeypatch):
        from repro.kernels import ops
        monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
        plans = lplan.build_plan_tree(self._tree(rng))
        assert lplan.kernel_census(plans, 8, use_pallas=True) == \
            {"jnp": 1, "ref": 5}

    def test_quantized_prefill_prefers_act_quant_kernel(self, rng):
        plans = lplan.build_plan_tree({"head": quantize_tree(_lowrank(rng))})
        assert lplan.kernel_census(plans, 64, use_pallas=True,
                                   act_quantize=True) == {"lowrank_qa": 1}
        assert lplan.kernel_census(plans, 64, use_pallas=True) == \
            {"lowrank_q": 1}


class TestExecution:
    @pytest.mark.parametrize("quant", [False, True])
    def test_lowrank_pallas_matches_reference_3d(self, quant, rng):
        p = _lowrank(rng)
        if quant:
            p = quantize_tree(p)
        x = jax.random.normal(jax.random.fold_in(rng, 7), (4, 1, 128)) * 0.1
        y_ref = apply_linear(p, x)
        y_pl = apply_linear(p, x, use_pallas=True)
        assert y_pl.shape == y_ref.shape == (4, 1, 64)
        np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("quant", [False, True])
    def test_branched_pallas_matches_reference_3d(self, quant, rng):
        p = _branched(rng)
        if quant:
            p = quantize_tree(p)
        x = jax.random.normal(jax.random.fold_in(rng, 8), (4, 1, 128)) * 0.1
        y_ref = apply_linear(p, x)
        y_pl = apply_linear(p, x, use_pallas=True)
        np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_freeze_policy_stops_outer_factor_grads(self, rng):
        p = _lowrank(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 9), (8, 128)) * 0.1

        def loss(p, freeze):
            return jnp.sum(apply_linear(p, x, freeze_factors=freeze) ** 2)

        g = jax.grad(loss)(p, True)
        assert float(jnp.abs(g["w0"]).max()) == 0.0     # frozen
        assert float(jnp.abs(g["w1"]).max()) > 0.0      # trainable

    def test_conv_kind_raises_in_apply_linear(self):
        tk = {"tucker_u": jnp.zeros((16, 4)), "core": jnp.zeros((3, 3, 4, 4)),
              "tucker_v": jnp.zeros((4, 16))}
        with pytest.raises(ValueError):
            apply_linear(tk, jnp.zeros((2, 16)))

    def test_quantized_tucker_conv_executes(self, rng):
        from repro.layers.conv import apply_conv, conv_out_channels
        ks = jax.random.split(rng, 3)
        p = {"tucker_u": jax.random.normal(ks[0], (16, 8)) * 0.1,
             "core": jax.random.normal(ks[1], (3, 3, 8, 8)) * 0.1,
             "tucker_v": jax.random.normal(ks[2], (8, 16)) * 0.1}
        x = jax.random.normal(jax.random.fold_in(rng, 3), (2, 8, 8, 16))
        y = apply_conv(p, x)
        yq = apply_conv(quantize_tree(p), x)
        assert conv_out_channels(quantize_tree(p)) == 16
        rel = float(jnp.linalg.norm(yq - y) / jnp.linalg.norm(y))
        assert rel <= 5e-2, rel


class TestAccounting:
    def test_param_count_excludes_scales(self, rng):
        """Satellite: *_scale leaves are not model parameters."""
        p = _lowrank(rng)
        pq = quantize_tree(p)
        want = sum(int(v.size) for v in p.values())
        assert linear_param_count(p) == want
        assert linear_param_count(pq) == want       # q values count, scales not
        assert linear_quant_bytes(p) == 0
        assert linear_quant_bytes(pq) > 0

    def test_flops_and_out_dim_invariant_under_quant(self, rng):
        for p in (_lowrank(rng), _branched(rng)):
            pq = quantize_tree(p)
            assert linear_out_dim(pq) == linear_out_dim(p)
            assert linear_flops(pq, 11) == linear_flops(p, 11)

    def test_weight_bytes_drop_under_quant(self, rng):
        p = _branched(rng)
        plain = lplan.build_plan(p)
        quant = lplan.build_plan(quantize_tree(p))
        assert quant.weight_bytes < plain.weight_bytes

    def test_tree_summary(self, rng):
        tree = {"a": {"up": _lowrank(rng)},
                "b": {"proj": quantize_tree(_branched(rng))},
                "norm": {"scale": jnp.ones((8,))}}
        plans = lplan.build_plan_tree(tree)
        s = lplan.tree_summary(plans)
        assert s["linears"] == 2 and s["quantized"] == 1
        assert s["by_kind"] == {"branched": 1, "lowrank": 1}
        assert s["quant_bytes"] > 0

    def test_plan_layer_time_quant_aware(self, rng):
        from repro.core.cost_model import plan_layer_time
        p = _lowrank(rng, c=2048, r=256, s=2048)
        t_bf16 = plan_layer_time(lplan.build_plan(p), 1)
        t_int8 = plan_layer_time(lplan.build_plan(quantize_tree(p)), 1)
        assert t_int8 < t_bf16        # decode (m=1) is weight-stream-bound

    def test_plan_layer_time_act_quant_mxu_rate(self, rng):
        """Satellite cross-check: at compute-bound prefill m, an int8
        plan with ``act_quantize`` runs at the int8 x int8 MXU rate —
        half the modelled time — while weight-only int8 (dequantized in
        VMEM, wide MXU operands) and bf16 plans are unchanged."""
        from repro.analysis.hw_specs import DEFAULT
        from repro.core.cost_model import plan_layer_time
        p = _lowrank(rng, c=2048, r=256, s=2048)
        qplan = lplan.build_plan(quantize_tree(p))
        m = 1 << 15                   # deep into the compute-bound regime
        t_wq = plan_layer_time(qplan, m)
        t_qa = plan_layer_time(qplan, m, act_quantize=True)
        assert t_qa == pytest.approx(t_wq / DEFAULT.int8_mxu_mult)
        # bf16 plan: flag is inert (dispatch mirror rejects it)
        fplan = lplan.build_plan(p)
        assert plan_layer_time(fplan, m, act_quantize=True) \
            == plan_layer_time(fplan, m)

    def test_plan_layer_time_act_quant_narrows_stream(self, rng):
        """Memory-bound side: under qa the activation stream is int8
        values + one f32 scale per row, so the modelled time drops when
        m is small enough to be stream-bound on activations."""
        from repro.core.cost_model import plan_layer_time
        p = _lowrank(rng, c=4096, r=64, s=4096)
        qplan = lplan.build_plan(quantize_tree(p))
        m = 4096                      # act stream rivals weight stream
        t_wq = plan_layer_time(qplan, m, act_bytes=4)
        t_qa = plan_layer_time(qplan, m, act_bytes=4, act_quantize=True)
        assert t_qa < t_wq

    def test_peak_flops_dtype_aware(self):
        from repro.analysis.hw_specs import DEFAULT
        assert DEFAULT.peak_flops(1) \
            == DEFAULT.peak_flops_bf16 * DEFAULT.int8_mxu_mult
        assert DEFAULT.peak_flops(2) == DEFAULT.peak_flops_bf16
        assert DEFAULT.peak_flops(4) == DEFAULT.peak_flops_bf16
