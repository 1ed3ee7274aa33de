"""Unit + property tests for the paper's decompositions (Eq. 1-6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import svd, tucker


class TestSVD:
    def test_full_rank_exact(self, rng):
        w = jax.random.normal(rng, (64, 48))
        f = svd.svd_decompose(w, 48)
        np.testing.assert_allclose(np.asarray(svd.reconstruct(f)),
                                   np.asarray(w), atol=1e-4)

    def test_factor_shapes(self, rng):
        f = svd.svd_decompose(jax.random.normal(rng, (64, 48)), 16)
        assert f.w0.shape == (64, 16) and f.w1.shape == (16, 48)

    def test_balanced_factors(self, rng):
        """Eq. 3: both factors carry sqrt(sigma) — comparable norms."""
        w = jax.random.normal(rng, (64, 64))
        f = svd.svd_decompose(w, 32)
        n0 = float(jnp.linalg.norm(f.w0))
        n1 = float(jnp.linalg.norm(f.w1))
        assert 0.5 < n0 / n1 < 2.0

    def test_truncation_is_best_rank_r(self, rng):
        """Eckart-Young: SVD truncation error equals the singular tail."""
        w = jax.random.normal(rng, (32, 32))
        s = jnp.linalg.svd(w, compute_uv=False)
        for r in (4, 16, 28):
            f = svd.svd_decompose(w, r)
            err = float(jnp.linalg.norm(w - svd.reconstruct(f)))
            tail = float(jnp.sqrt(jnp.sum(s[r:] ** 2)))
            assert abs(err - tail) < 1e-3

    def test_batched(self, rng):
        w = jax.random.normal(rng, (4, 32, 24))
        f = svd.svd_decompose(w, 24)
        assert f.w0.shape == (4, 32, 24)
        np.testing.assert_allclose(
            np.asarray(jnp.matmul(f.w0, f.w1)), np.asarray(w), atol=1e-4)

    def test_randomized_close_to_exact(self, rng):
        # low-rank-structured matrix: randomized SVD should nail it
        a = jax.random.normal(rng, (256, 16))
        b = jax.random.normal(jax.random.fold_in(rng, 1), (16, 128))
        w = a @ b
        f = svd.randomized_svd(w, 16)
        assert svd.approximation_error(w, f) < 1e-3

    @pytest.mark.parametrize("shape,rank", [
        ((64, 48), 48), ((48, 200), 20), ((200, 48), 20), ((3, 96, 64), 32)])
    def test_gram_svd_matches_svd(self, rng, shape, rank):
        """The Gram-matrix path (decompose_auto's) gives svd_decompose's
        rank-r product and balanced factor norms, wide or tall, batched
        over leading dims."""
        w = jax.random.normal(rng, shape)
        want = svd.svd_decompose(w, rank)
        got = svd.gram_svd_decompose(w, rank)
        assert got.w0.shape == want.w0.shape and got.w1.shape == want.w1.shape
        np.testing.assert_allclose(np.asarray(svd.reconstruct(got)),
                                   np.asarray(svd.reconstruct(want)),
                                   atol=2e-4)
        np.testing.assert_allclose(float(jnp.linalg.norm(got.w0)),
                                   float(jnp.linalg.norm(got.w1)), rtol=1e-4)

    def test_gram_svd_rank_deficient(self, rng):
        """Directions with no signal get zero factors, not inf/nan."""
        a, b = jax.random.split(rng)
        w = jax.random.normal(a, (64, 4)) @ jax.random.normal(b, (4, 96))
        f = svd.gram_svd_decompose(w, 16)
        assert np.all(np.isfinite(np.asarray(f.w1)))
        np.testing.assert_allclose(np.asarray(svd.reconstruct(f)),
                                   np.asarray(w), atol=1e-3)

    def test_host_twin_matches(self, rng):
        w = np.asarray(jax.random.normal(rng, (32, 48)))
        w0, w1 = svd.host_svd_decompose(w, 16)
        f = svd.svd_decompose(jnp.asarray(w), 16)
        np.testing.assert_allclose(w0 @ w1, np.asarray(f.w0 @ f.w1),
                                   atol=1e-4)

    @given(c=st.integers(8, 96), s=st.integers(8, 96),
           alpha=st.floats(1.2, 8.0))
    @settings(max_examples=40, deadline=None)
    def test_ratio_rank_property(self, c, s, alpha):
        """ratio_rank always compresses by >= alpha (paper's Eq. 7 goal)."""
        r = svd.ratio_rank(c, s, alpha)
        assert 1 <= r <= min(c, s)
        if c * s >= alpha * (c + s):  # a rank >= 1 can hit alpha at all
            assert svd.compression_of_rank(c, s, r) >= alpha * 0.99

    def test_energy_rank_monotone(self, rng):
        w = jax.random.normal(rng, (64, 64))
        r90 = svd.energy_rank(w, 0.90)
        r99 = svd.energy_rank(w, 0.99)
        assert r90 <= r99 <= 64


class TestTucker:
    def test_full_rank_exact(self, rng):
        w = jax.random.normal(rng, (3, 3, 16, 32))
        f = tucker.tucker2_decompose(w, 16, 32)
        assert tucker.approximation_error(w, f) < 1e-5

    def test_shapes(self, rng):
        f = tucker.tucker2_decompose(
            jax.random.normal(rng, (3, 3, 32, 64)), 8, 16)
        assert f.u.shape == (32, 8)
        assert f.core.shape == (3, 3, 8, 16)
        assert f.v.shape == (16, 64)

    def test_truncation_monotone(self, rng):
        w = jax.random.normal(rng, (3, 3, 24, 24))
        errs = [tucker.approximation_error(
            w, tucker.tucker2_decompose(w, r, r)) for r in (4, 12, 24)]
        assert errs[0] >= errs[1] >= errs[2]

    @given(c=st.sampled_from([32, 64, 128]), s=st.sampled_from([32, 64, 256]),
           alpha=st.floats(1.5, 6.0))
    @settings(max_examples=30, deadline=None)
    def test_ratio_ranks_hit_compression(self, c, s, alpha):
        """Paper Eq. 7: returned ranks compress the conv by ~alpha."""
        k = 3
        r1, r2 = tucker.ratio_ranks(c, s, k, alpha)
        dense = tucker.dense_conv_params(c, s, k)
        got = dense / tucker.tucker2_params(c, s, k, r1, r2)
        assert got > alpha * 0.7      # integer rounding slack

    def test_params_formula(self):
        assert tucker.tucker2_params(64, 128, 3, 8, 16) \
            == 64 * 8 + 8 * 16 * 9 + 16 * 128
