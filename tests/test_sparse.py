"""2:4 factor sparsity: packing round trips, fused sparse-int8 kernel
parity, plan/dispatch contract, accounting, sharding, and end-to-end
compound-compressed serving."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.quant import (IDX_SUFFIX, SCALE_SUFFIX, SP_SUFFIX, quantize_array,
                         quantize_tree)
from repro.quant.sparse import (desparsify_tree, expand_sparse, is_sparse,
                                sparsify_array, sparsify_tree)


def _factors(key, c=32, r=16, s=48, scale=0.05):
    k0, k1 = jax.random.split(key)
    return (jax.random.normal(k0, (c, r)) * scale,
            jax.random.normal(k1, (r, s)) * scale)


# ---------------------------------------------------------------------------
# Packing round trips
# ---------------------------------------------------------------------------

class TestSparsifyArray:
    def test_shapes_and_dtypes(self, rng):
        w, _ = _factors(rng)
        sp, idx, scale = sparsify_array(w)
        assert sp.shape == (2, 8, 16) and sp.dtype == jnp.int8
        assert idx.shape == (2, 8, 1) and idx.dtype == jnp.int8
        assert scale.shape == (1, 16) and scale.dtype == jnp.float32

    def test_keeps_top2_by_row_l1(self, rng):
        w, _ = _factors(rng)
        dense = np.asarray(expand_sparse(*sparsify_array(w),
                                         dtype=jnp.float32))
        wn = np.asarray(w)
        score = np.abs(wn).sum(-1).reshape(-1, 4)      # (C/4, 4) L1 norms
        for g in range(score.shape[0]):
            kept = set(np.argsort(-score[g])[:2])
            for j in range(4):
                row = dense[4 * g + j]
                if j in kept:
                    # kept row round-trips within int8 quant error
                    assert np.abs(row - wn[4 * g + j]).max() < 2e-3
                else:
                    np.testing.assert_array_equal(row, 0.0)

    def test_mode_none_keeps_dtype_no_scale(self, rng):
        w = _factors(rng)[0].astype(jnp.bfloat16)
        sp, idx, scale = sparsify_array(w, mode="none")
        assert scale is None and sp.dtype == jnp.bfloat16
        dense = np.asarray(expand_sparse(sp, idx), np.float32)
        wn = np.asarray(w, np.float32)
        kept = np.abs(dense) > 0
        np.testing.assert_array_equal(dense[kept], wn[kept])

    def test_idx_ascending_within_group(self, rng):
        _, idx, _ = sparsify_array(_factors(rng)[0])
        i = np.asarray(idx)                            # (2, G, 1)
        assert (i >= 0).all() and (i <= 3).all()
        assert (i[0] < i[1]).all()

    def test_indivisible_input_dim_raises(self, rng):
        w = jax.random.normal(rng, (30, 8))
        with pytest.raises((ValueError, AssertionError)):
            sparsify_array(w)


class TestSparsifyTree:
    def test_key_rewrite_and_targets(self, rng):
        w0, w1 = _factors(rng)
        tree = {"ffn": {"w0": w0, "w1": w1}}
        sp = sparsify_tree(tree, mode="int8")
        node = sp["ffn"]
        assert set(node) == {"w0_sp", "w0_idx", "w0_scale",
                             "w1_sp", "w1_idx", "w1_scale"}
        assert is_sparse(node)
        only_w0 = sparsify_tree(tree, mode="int8", targets=("w0",))["ffn"]
        assert "w1" in only_w0 and "w0_sp" in only_w0

    def test_idempotent_and_quant_compose(self, rng):
        w0, w1 = _factors(rng)
        tree = {"w0": w0, "w1": w1, "xc": jnp.ones((8, 8))}
        sp = sparsify_tree(tree, mode="int8")          # xc not targeted
        again = sparsify_tree(sp, mode="int8")
        assert jax.tree.structure(sp) == jax.tree.structure(again)
        # quantize_tree after: picks up the plain xc, skips packed nodes
        q = quantize_tree(sp, mode="int8")
        assert "xc_q" in q and "w0_sp" in q and "w0_q" not in q

    def test_skips_indivisible_input_dim(self, rng):
        tree = {"w0": jax.random.normal(rng, (30, 8))}
        sp = sparsify_tree(tree, mode="int8")
        assert "w0" in sp and "w0_sp" not in sp

    def test_desparsify_round_trip(self, rng):
        w0, w1 = _factors(rng)
        sp = sparsify_tree({"w0": w0, "w1": w1}, mode="int8")
        dense = desparsify_tree(sp, dtype=jnp.float32)
        assert set(dense) == {"w0", "w1"}
        assert dense["w0"].shape == w0.shape
        # half the rows are exact zeros
        zeros = (np.asarray(dense["w0"]) == 0).all(-1).sum()
        assert zeros == w0.shape[0] // 2


# ---------------------------------------------------------------------------
# Fused kernel vs ref.py oracle (interpret mode) + fallback dispatch
# ---------------------------------------------------------------------------

def _sq_lowrank_args(rng, c=32, r=16, s=48, m=24, lead=()):
    w0, w1 = _factors(rng, c, r, s)
    x = (jax.random.normal(jax.random.fold_in(rng, 9), (*lead, m, c))
         * 0.1).astype(jnp.bfloat16)
    return (x, *sparsify_array(w0), *sparsify_array(w1))


def _sq_branched_args(rng, n=2, c=32, r1=8, r2=8, s=48, m=24):
    ks = jax.random.split(jax.random.fold_in(rng, 3), 4)
    u = jax.random.normal(ks[0], (n, c, r1)) * 0.05
    xc = jax.random.normal(ks[1], (n, r1, r2)) * 0.05
    v = jax.random.normal(ks[2], (n, r2, s)) * 0.05
    x = (jax.random.normal(ks[3], (m, c)) * 0.1).astype(jnp.bfloat16)
    return (x, *sparsify_array(u), *quantize_array(xc), *sparsify_array(v))


class TestSparseKernels:
    TOL = 1e-2                        # the acceptance bound; observed 0

    @pytest.mark.parametrize("m,lead", [(24, ()), (1, (3,)), (8, (2, 2))])
    def test_lowrank_sq_matches_oracle(self, rng, m, lead):
        args = _sq_lowrank_args(rng, m=m, lead=lead)
        got = ops.lowrank_matmul_sq(*args, force_kernel=True)
        want = ref.lowrank_matmul_sq_ref(*args)
        assert got.shape == want.shape
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) <= self.TOL

    def test_lowrank_sq_padding_path(self, rng):
        # S=40 < DEFAULT_BN and M=5 not a multiple of any block: both
        # pads trigger inside the wrapper.
        args = _sq_lowrank_args(rng, c=32, r=16, s=40, m=5)
        got = ops.lowrank_matmul_sq(*args, force_kernel=True)
        want = ref.lowrank_matmul_sq_ref(*args)
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) <= self.TOL

    @pytest.mark.parametrize("m", [24, 1])
    def test_branched_sq_matches_oracle(self, rng, m):
        args = _sq_branched_args(rng, m=m)
        got = ops.branched_matmul_sq(*args, force_kernel=True)
        want = ref.branched_matmul_sq_ref(*args)
        assert got.shape == want.shape
        assert float(jnp.abs(got.astype(jnp.float32)
                             - want.astype(jnp.float32)).max()) <= self.TOL

    def test_kernel_fits_rejection_falls_back_bit_exact(self, rng,
                                                        monkeypatch):
        """VMEM gate closed -> the ops wrappers dispatch the unfused
        reference path, bit-identical to calling it directly."""
        monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
        lr = _sq_lowrank_args(rng)
        assert not ops.kernel_fits("lowrank_sq", 24, c=32, r=16, s=48)
        np.testing.assert_array_equal(
            np.asarray(ops.lowrank_matmul_sq(*lr)),
            np.asarray(ref.lowrank_matmul_sq_ref(*lr)))
        br = _sq_branched_args(rng)
        assert not ops.kernel_fits("branched_sq", 24, c=32, r1=8, r2=8,
                                   s=48)
        np.testing.assert_array_equal(
            np.asarray(ops.branched_matmul_sq(*br)),
            np.asarray(ref.branched_matmul_sq_ref(*br)))

    def test_plan_execute_respects_closed_gate(self, rng, monkeypatch):
        """kernel_for returns None under a closed gate and execute still
        produces the reference result (dense-fallback dispatch)."""
        from repro.layers import plan as lplan
        w0, w1 = _factors(rng)
        tree = sparsify_tree({"w0": w0, "w1": w1}, mode="int8")
        x = (jax.random.normal(rng, (8, 32)) * 0.1).astype(jnp.bfloat16)
        p = lplan.build_plan(tree)
        open_y = p.execute(tree, x, use_pallas=True)
        monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
        assert p.kernel_for(x.shape, True) is None
        closed_y = p.execute(tree, x, use_pallas=True)
        ref_y = p.execute(tree, x, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(closed_y),
                                      np.asarray(ref_y))
        assert float(jnp.abs(open_y.astype(jnp.float32)
                             - ref_y.astype(jnp.float32)).max()) <= self.TOL


# ---------------------------------------------------------------------------
# Plan contract + accounting
# ---------------------------------------------------------------------------

class TestSparsePlan:
    def _lowrank_tree(self, rng, mode="int8", c=32, r=16, s=48):
        w0, w1 = _factors(rng, c, r, s)
        return sparsify_tree({"w0": w0, "w1": w1}, mode=mode)

    def test_classification_and_spec(self, rng):
        from repro.layers import plan as lplan
        tree = self._lowrank_tree(rng)
        p = lplan.build_plan(tree)
        assert p.kind == lplan.KIND_LOWRANK and p.sparse and p.quantized
        f = p.factor("w0")
        assert f.sparsity == "2:4" and f.shape == (32, 16)
        assert f.density == 0.5 and f.idx_shape == (2, 8, 1)
        assert p.d_in == 32 and p.d_out == 48

    def test_kernel_names(self, rng):
        from repro.layers import plan as lplan
        p = lplan.build_plan(self._lowrank_tree(rng))
        assert p.kernel_for((8, 32), True) == "lowrank_sq"
        assert p.kernel_for((8, 32), False) is None

        btree = sparsify_tree(
            {"u": jax.random.normal(rng, (2, 32, 8)) * 0.05,
             "xc": jax.random.normal(rng, (2, 8, 8)) * 0.05,
             "v": jax.random.normal(rng, (2, 8, 48)) * 0.05},
            mode="int8")
        btree = quantize_tree(btree, mode="int8")      # xc -> int8
        bp = lplan.build_plan(btree)
        assert bp.kernel_for((8, 32), True) == "branched_sq"

    def test_mixed_and_unquantized_sparse_take_reference(self, rng):
        from repro.layers import plan as lplan
        # bf16-sparse (mode="none"): no fused kernel serves it
        p_none = lplan.build_plan(self._lowrank_tree(rng, mode="none"))
        assert p_none.kernel_for((8, 32), True) is None
        # partial sparse_targets: w0 packed, w1 plain
        w0, w1 = _factors(rng)
        mixed = sparsify_tree({"w0": w0, "w1": w1}, mode="int8",
                              targets=("w0",))
        p_mixed = lplan.build_plan(mixed)
        assert p_mixed.kernel_for((8, 32), True) is None
        # both still execute (reference expand path)
        x = (jax.random.normal(rng, (4, 32)) * 0.1).astype(jnp.bfloat16)
        assert p_mixed.execute(mixed, x, use_pallas=True).shape == (4, 48)

    def test_param_count_excludes_idx_and_scale(self, rng):
        from repro.layers import plan as lplan
        tree = self._lowrank_tree(rng)
        p = lplan.build_plan(tree)
        # packed values only: half the logical counts
        assert p.param_count == (32 * 16 + 16 * 48) // 2
        # the tree-walk twin: *_idx and *_scale leaves are metadata,
        # *_sp values count
        walked = sum(
            int(leaf.size)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
            if not str(getattr(path[-1], "key", path[-1])).endswith(
                (SCALE_SUFFIX, IDX_SUFFIX)))
        assert walked == p.param_count
        # and the suffix constants really partition the tree's keys
        keys = {str(getattr(pth[-1], "key", pth[-1]))
                for pth, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}
        assert {k for k in keys if k.endswith(SP_SUFFIX)} \
            == {"w0" + SP_SUFFIX, "w1" + SP_SUFFIX}

    def test_weight_bytes_formula(self, rng):
        from repro.layers import plan as lplan
        c, r, s = 32, 16, 48
        p = lplan.build_plan(self._lowrank_tree(rng, c=c, r=r, s=s))
        packed = (c * r + r * s) // 2                  # int8 kept values
        idx = c // 2 + r // 2                          # one int8 per group
        scales = 4 * (r + s)                           # f32 rows
        assert p.weight_bytes == packed + idx + scales
        assert p.quant_bytes == p.weight_bytes

    def test_chain_density_and_cost_model(self, rng):
        from repro.core import cost_model as cm
        from repro.layers import plan as lplan
        w0, w1 = _factors(rng)
        sq = lplan.build_plan(self._lowrank_tree(rng))
        q = lplan.build_plan(quantize_tree({"w0": w0, "w1": w1},
                                           mode="int8"))
        assert sq.chain_density() == (0.5, 0.5)
        assert q.chain_density() == (1.0, 1.0)
        assert sq.flops_per_token == q.flops_per_token / 2
        # memory-bound decode: fewer weight bytes -> strictly faster
        assert cm.plan_layer_time(sq, 1) < cm.plan_layer_time(q, 1)

    def test_tree_summary_counts_sparse(self, rng):
        from repro.layers import plan as lplan
        tree = {"a": self._lowrank_tree(rng),
                "b": {"w0": _factors(rng)[0], "w1": _factors(rng)[1]}}
        summary = lplan.tree_summary(lplan.build_plan_tree(tree))
        assert summary["linears"] == 2 and summary["sparse"] == 1


# ---------------------------------------------------------------------------
# apply_linear + sharding + engine end to end
# ---------------------------------------------------------------------------

class TestSparseEndToEnd:
    def test_apply_linear_matches_desparsified_dense(self, rng):
        from repro.layers.param import apply_linear
        w0, w1 = _factors(rng)
        sp = sparsify_tree({"w0": w0, "w1": w1}, mode="int8")
        dense = desparsify_tree(sp, dtype=jnp.float32)
        x = (jax.random.normal(rng, (6, 32)) * 0.1).astype(jnp.bfloat16)
        y_dense = apply_linear({k: v.astype(jnp.bfloat16)
                                for k, v in dense.items()}, x)
        for use_pallas in (False, True):
            y_sp = apply_linear(sp, x, use_pallas=use_pallas)
            assert float(jnp.abs(y_sp.astype(jnp.float32)
                                 - y_dense.astype(jnp.float32)).max()) < 1e-2

    def test_align_quantized_axes_covers_sparse_leaves(self, rng):
        from repro.quant import align_quantized_axes
        w0, w1 = _factors(rng)
        axes = {"w0": ("embed", "rank"), "w1": ("rank", "ffn")}
        sp, sp_axes = sparsify_tree({"w0": w0, "w1": w1}, mode="int8",
                                    axes=axes)
        aligned = align_quantized_axes(sp, axes)
        assert set(aligned) == set(sp)
        assert aligned == sp_axes
        # packed values: out-dim axis survives, packed axes replicate
        assert aligned["w0_sp"] == (None, "embed", "rank")
        assert aligned["w0_idx"] == (None, "embed", None)
        assert aligned["w0_scale"] == (None, "rank")

    def test_engine_compound_compression(self, rng):
        from repro.configs import registry
        from repro.configs.base import LRDConfig, ParallelConfig, RunConfig
        from repro.core.surgery import decompose_model, sparsify_model
        from repro.models.api import get_model
        from repro.serve.engine import Request, ServeEngine

        cfg = dataclasses.replace(registry.get("llama3.2-1b").smoke,
                                  dtype="float32")
        lrd = LRDConfig(enabled=True, compression=2.0, rank_mode="aligned",
                        rank_align=8, min_dim=32)
        run = RunConfig(model=cfg, parallel=ParallelConfig(), lrd=lrd)
        m = get_model(cfg)
        params, axes = m.init(jax.random.PRNGKey(0))
        params, axes, _ = decompose_model(params, axes, run.lrd)

        # surgery-level pass rewrites params AND axes coherently
        lrd_sp = dataclasses.replace(lrd, sparsify="2:4", quantize="int8")
        p2, a2 = sparsify_model(params, axes, lrd_sp)
        flat_p = jax.tree_util.tree_flatten_with_path(p2)[0]
        sp_keys = {str(getattr(pth[-1], "key", pth[-1]))
                   for pth, _ in flat_p}
        assert any(k.endswith(SP_SUFFIX) for k in sp_keys)
        assert jax.tree.structure(p2) == jax.tree.structure(
            a2, is_leaf=lambda n: isinstance(n, tuple))

        def serve(eng):
            reqs = [Request(uid=i, prompt=[i + 1, 2, 3], max_new_tokens=4)
                    for i in range(3)]
            for r in reqs:
                eng.add_request(r)
            eng.run_until_done()
            assert all(r.done and len(r.output) == 4 for r in reqs)
            return [r.output for r in reqs]

        eng_q = ServeEngine(run, params, slots=2, max_seq=64,
                            quantize="int8")
        eng_sq = ServeEngine(run, params, slots=2, max_seq=64,
                             quantize="int8", sparsify="2:4")
        assert eng_sq.sparsify == "2:4"
        assert eng_sq.plan_summary["sparse"] > 0
        assert (eng_sq.plan_summary["weight_bytes"]
                < eng_q.plan_summary["weight_bytes"])
        serve(eng_q)
        out_sq = serve(eng_sq)
        # the sq engine serves exactly what its expanded-dense twin would
        dense_tw = desparsify_tree(
            ServeEngine(run, params, slots=2, max_seq=64,
                        sparsify="2:4").params, dtype=jnp.float32)
        out_dense = serve(ServeEngine(run, dense_tw, slots=2, max_seq=64,
                                      quantize="int8"))
        assert len(out_sq) == len(out_dense) == 3

    def test_config_knob_drives_engine(self, rng):
        from repro.configs import registry
        from repro.configs.base import LRDConfig, ParallelConfig, RunConfig
        from repro.core.surgery import decompose_model
        from repro.models.api import get_model
        from repro.serve.engine import ServeEngine

        cfg = dataclasses.replace(registry.get("llama3.2-1b").smoke,
                                  dtype="float32")
        lrd = LRDConfig(enabled=True, compression=2.0, rank_mode="aligned",
                        rank_align=8, min_dim=32, sparsify="2:4",
                        quantize="int8")
        run = RunConfig(model=cfg, parallel=ParallelConfig(), lrd=lrd)
        m = get_model(cfg)
        params, axes = m.init(jax.random.PRNGKey(0))
        params, _, _ = decompose_model(params, axes, lrd)
        eng = ServeEngine(run, params, slots=2, max_seq=64)
        assert eng.sparsify == "2:4" and eng.quantize == "int8"
        assert eng.plan_summary["sparse"] > 0
