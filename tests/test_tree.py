"""Tree generator invariants + fitting behaviour (paper §3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tree as tree_lib
from repro.core.tree_fit import FitConfig, fit_tree, tree_log_likelihood

jax.config.update("jax_enable_x64", False)


def _random_tree(seed, c, k, scale=0.7):
    return tree_lib.init_tree(jax.random.PRNGKey(seed), c, k, scale=scale)


class TestTreeBasics:
    def test_padded_size(self):
        assert tree_lib.padded_size(1) == 2
        assert tree_lib.padded_size(2) == 2
        assert tree_lib.padded_size(3) == 4
        assert tree_lib.padded_size(1024) == 1024
        assert tree_lib.padded_size(1025) == 2048

    def test_depth_property(self):
        t = _random_tree(0, 37, 8)
        assert t.depth == 6           # padded to 64 leaves
        assert t.w.shape == (63, 8)

    def test_log_prob_matches_log_prob_all(self):
        c, k, b = 37, 8, 16
        t = _random_tree(1, c, k)
        x = jax.random.normal(jax.random.PRNGKey(2), (b, k))
        y = jax.random.randint(jax.random.PRNGKey(3), (b,), 0, c)
        lp_path = tree_lib.log_prob(t, x, y)
        lp_all = tree_lib.log_prob_all(t, x)
        np.testing.assert_allclose(
            np.asarray(lp_path),
            np.asarray(jnp.take_along_axis(lp_all, y[:, None], -1)[:, 0]),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("c", [2, 3, 16, 37, 100])
    def test_normalization_over_real_labels(self, c):
        """sum_y p_n(y|x) == 1: padding leaves carry (numerically) no mass."""
        t = _random_tree(4, c, 6)
        x = jax.random.normal(jax.random.PRNGKey(5), (9, 6))
        mass = tree_lib.prob_mass_real(t, x)
        np.testing.assert_allclose(np.asarray(mass), 1.0, atol=1e-5)

    def test_sampling_matches_log_prob(self):
        """Empirical sampling frequencies ~ exp(log_prob_all)."""
        c, k = 13, 4
        t = _random_tree(6, c, k)
        x = jnp.tile(jax.random.normal(jax.random.PRNGKey(7), (1, k)),
                     (40_000, 1))
        ids, logp = tree_lib.sample(t, x, jax.random.PRNGKey(8))
        counts = np.bincount(np.asarray(ids), minlength=c) / ids.shape[0]
        probs = np.exp(np.asarray(tree_lib.log_prob_all(t, x[:1])))[0]
        np.testing.assert_allclose(counts, probs, atol=0.015)
        # The log-prob accumulated during the walk equals log_prob(y).
        lp2 = tree_lib.log_prob(t, x, ids)
        np.testing.assert_allclose(np.asarray(logp), np.asarray(lp2),
                                   rtol=1e-5, atol=1e-5)

    def test_sample_never_returns_padding(self):
        c, k = 5, 3   # padded to 8 leaves -> 3 padding labels
        t = _random_tree(9, c, k, scale=2.0)
        x = jax.random.normal(jax.random.PRNGKey(10), (20_000, k))
        ids, _ = tree_lib.sample(t, x, jax.random.PRNGKey(11))
        assert int(jnp.max(ids)) < c


@settings(max_examples=25, deadline=None)
@given(c=st.integers(2, 70), k=st.integers(1, 9), seed=st.integers(0, 2**20))
def test_property_normalized_and_consistent(c, k, seed):
    """Property: for any tree params, probs normalize over real labels and
    path log-probs agree with the dense evaluation."""
    t = _random_tree(seed, c, k, scale=1.5)
    key = jax.random.PRNGKey(seed + 1)
    x = jax.random.normal(key, (4, k))
    mass = np.asarray(tree_lib.prob_mass_real(t, x))
    np.testing.assert_allclose(mass, 1.0, atol=1e-4)
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (4,), 0, c)
    lp = np.asarray(tree_lib.log_prob(t, x, y))
    lp_all = np.asarray(tree_lib.log_prob_all(t, x))
    np.testing.assert_allclose(lp, np.take_along_axis(
        lp_all, np.asarray(y)[:, None], -1)[:, 0], rtol=1e-4, atol=1e-4)


class TestTreeFitting:
    def _clustered_data(self, seed=0, n=3000, c=16, k=6):
        """Labels live in feature clusters -> a fittable structure."""
        rng = np.random.default_rng(seed)
        centers = rng.standard_normal((c, k)) * 3.0
        y = rng.integers(0, c, n)
        x = centers[y] + rng.standard_normal((n, k))
        return x.astype(np.float32), y

    def test_fit_improves_over_random(self):
        x, y = self._clustered_data()
        c = 16
        fitted = fit_tree(x, y, c, config=FitConfig(reg=0.1, seed=0))
        random_t = _random_tree(0, c, x.shape[1], scale=0.1)
        ll_fit = tree_log_likelihood(fitted, x, y)
        ll_rand = tree_log_likelihood(random_t, x, y)
        uniform_ll = -np.log(c)
        assert ll_fit > ll_rand
        assert ll_fit > uniform_ll + 0.5, (
            f"fitted tree ({ll_fit:.3f}) should beat uniform "
            f"({uniform_ll:.3f}) clearly on clustered data")

    def test_fit_non_power_of_two_labels(self):
        x, y = self._clustered_data(c=13)
        t = fit_tree(x, y, 13, config=FitConfig(seed=1))
        xs = jnp.asarray(x[:64])
        np.testing.assert_allclose(
            np.asarray(tree_lib.prob_mass_real(t, xs)), 1.0, atol=1e-5)
        ids, _ = tree_lib.sample(t, xs, jax.random.PRNGKey(0))
        assert int(jnp.max(ids)) < 13

    def test_fit_with_sample_weights_matches_expansion(self):
        """Weighted fit == fit on the expanded data set (aggregation path
        used by the LM bigram generator)."""
        rng = np.random.default_rng(3)
        x_u = rng.standard_normal((40, 4)).astype(np.float32)
        y_u = rng.integers(0, 8, 40)
        w = rng.integers(1, 4, 40)
        x_e = np.repeat(x_u, w, axis=0)
        y_e = np.repeat(y_u, w, axis=0)
        cfg = FitConfig(seed=5)
        t_w = fit_tree(x_u, y_u, 8, sample_weight=w.astype(np.float64),
                       config=cfg)
        t_e = fit_tree(x_e, y_e, 8, config=cfg)
        np.testing.assert_allclose(np.asarray(t_w.w), np.asarray(t_e.w),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(t_w.label_to_leaf),
                                      np.asarray(t_e.label_to_leaf))

    def test_leaf_permutation_is_bijective(self):
        x, y = self._clustered_data(c=16)
        t = fit_tree(x, y, 16, config=FitConfig(seed=2))
        l2l = np.asarray(t.label_to_leaf)
        assert len(np.unique(l2l)) == 16
        inv = np.asarray(t.leaf_to_label)[l2l]
        np.testing.assert_array_equal(inv, np.arange(16))
