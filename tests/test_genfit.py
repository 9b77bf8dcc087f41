"""repro.genfit: level-parallel fit parity with the sequential oracle,
tree invariants, incremental/sharded refits, and refresh determinism."""
import dataclasses
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tree as tree_lib
from repro.core.tree_fit import FitConfig, fit_tree, tree_log_likelihood
from repro.genfit import (fit_tree_levelwise, fit_tree_sharded,
                          label_counts, refit_params, refresh_tree,
                          subtree_drift)
from repro.genfit.incremental import perm_from_tree, real_leaf_mask

jax.config.update("jax_enable_x64", False)


def _clustered(seed=0, n=3000, c=16, k=6, spread=3.0, n_held=1000,
               observed=None):
    """Labels live in feature clusters; optional cap on observed labels."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((c, k)) * spread
    y = rng.integers(0, observed or c, n)
    x = (centers[y] + rng.standard_normal((n, k))).astype(np.float32)
    yh = rng.integers(0, observed or c, n_held)
    xh = (centers[yh] + rng.standard_normal((n_held, k))).astype(
        np.float32)
    return x, y, xh, yh


def _check_invariants(tree, num_labels, x):
    """Leaf<->label bijection, padded mass ~ 0, path == dense log-probs."""
    l2l = np.asarray(tree.label_to_leaf)
    assert len(np.unique(l2l)) == num_labels
    inv = np.asarray(tree.leaf_to_label)[l2l]
    np.testing.assert_array_equal(inv, np.arange(num_labels))
    xs = jnp.asarray(x[:64])
    mass = np.asarray(tree_lib.prob_mass_real(tree, xs))
    np.testing.assert_allclose(mass, 1.0, atol=1e-4)
    y = jnp.asarray(np.arange(min(num_labels, 32)) % num_labels)
    lp = np.asarray(tree_lib.log_prob(tree, xs[:len(y)], y))
    lp_all = np.asarray(tree_lib.log_prob_all(tree, xs[:len(y)]))
    np.testing.assert_allclose(
        lp, np.take_along_axis(lp_all, np.asarray(y)[:, None], -1)[:, 0],
        rtol=1e-4, atol=1e-4)


class TestLevelwiseParity:
    @pytest.mark.parametrize("c", [13, 16, 64])
    def test_heldout_ll_matches_sequential(self, c):
        """The acceptance property: level-parallel == sequential-reference
        held-out log-likelihood within tolerance (both fits are local
        optima from different inits; 5% relative covers that spread, and
        both must clearly beat uniform)."""
        x, y, xh, yh = _clustered(seed=c, c=c, n=4000)
        cfg = FitConfig(seed=0)
        ll_seq = tree_log_likelihood(fit_tree(x, y, c, config=cfg), xh, yh)
        ll_lvl = tree_log_likelihood(
            fit_tree_levelwise(x, y, c, config=cfg), xh, yh)
        assert ll_lvl > -np.log(c) + 0.5, "must clearly beat uniform"
        assert abs(ll_lvl - ll_seq) <= 0.05 * abs(ll_seq) + 0.02, (
            f"levelwise {ll_lvl:.4f} vs sequential {ll_seq:.4f}")

    def test_weighted_matches_expanded(self):
        rng = np.random.default_rng(3)
        x_u = rng.standard_normal((40, 4)).astype(np.float32)
        y_u = rng.integers(0, 8, 40)
        w = rng.integers(1, 4, 40)
        cfg = FitConfig(seed=5)
        t_w = fit_tree_levelwise(x_u, y_u, 8,
                                 sample_weight=w.astype(np.float64),
                                 config=cfg)
        t_e = fit_tree_levelwise(np.repeat(x_u, w, axis=0),
                                 np.repeat(y_u, w, axis=0), 8, config=cfg)
        np.testing.assert_allclose(np.asarray(t_w.w), np.asarray(t_e.w),
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(t_w.label_to_leaf),
                                      np.asarray(t_e.label_to_leaf))

    def test_zero_weight_points_are_invisible(self):
        """The subtree fitters pad point counts with weight-0 rows; those
        must not change the fit at all."""
        x, y, _, _ = _clustered(seed=1, c=16, n=1500)
        cfg = FitConfig(seed=0)
        t0 = fit_tree_levelwise(x, y, 16, config=cfg)
        x2 = np.concatenate([x, np.zeros((64, x.shape[1]), np.float32)])
        y2 = np.concatenate([y, np.zeros(64, y.dtype)])
        w2 = np.concatenate([np.ones(len(y), np.float32),
                             np.zeros(64, np.float32)])
        t1 = fit_tree_levelwise(x2, y2, 16, sample_weight=w2, config=cfg)
        np.testing.assert_array_equal(np.asarray(t0.w), np.asarray(t1.w))
        np.testing.assert_array_equal(np.asarray(t0.label_to_leaf),
                                      np.asarray(t1.label_to_leaf))

    def test_deterministic(self):
        x, y, _, _ = _clustered(seed=2, c=32, n=2000)
        cfg = FitConfig(seed=7)
        t0 = fit_tree_levelwise(x, y, 32, config=cfg)
        t1 = fit_tree_levelwise(x, y, 32, config=cfg)
        np.testing.assert_array_equal(np.asarray(t0.w), np.asarray(t1.w))
        np.testing.assert_array_equal(np.asarray(t0.b), np.asarray(t1.b))
        np.testing.assert_array_equal(np.asarray(t0.label_to_leaf),
                                      np.asarray(t1.label_to_leaf))

    def test_unobserved_labels_and_padding(self):
        """Non-power-of-two C with never-observed labels: bijection holds,
        padding mass ~ 0, sampling never returns >= C."""
        x, y, _, _ = _clustered(seed=4, c=13, n=900, observed=11)
        t = fit_tree_levelwise(x, y, 13, config=FitConfig(seed=1))
        _check_invariants(t, 13, x)
        ids, _ = tree_lib.sample(t, jnp.asarray(x[:2000]),
                                 jax.random.PRNGKey(0))
        assert int(jnp.max(ids)) < 13


@settings(max_examples=10, deadline=None)
@given(c=st.integers(2, 40), k=st.integers(1, 8),
       seed=st.integers(0, 2**20))
def test_property_levelwise_invariants(c, k, seed):
    """Property: for any clustered problem, the level-parallel fit yields
    a bijective, normalized tree whose path log-probs match the dense
    evaluation."""
    rng = np.random.default_rng(seed)
    n = 300
    centers = rng.standard_normal((c, k)) * 2.0
    y = rng.integers(0, c, n)
    x = (centers[y] + rng.standard_normal((n, k))).astype(np.float32)
    t = fit_tree_levelwise(x, y, c, config=FitConfig(seed=seed % 17))
    _check_invariants(t, c, x)


class TestIncremental:
    def test_refit_preserves_structure_and_recovers_ll(self):
        x, y, _, _ = _clustered(seed=0, c=32, n=4000, k=8)
        cfg = FitConfig(seed=0)
        t0 = fit_tree_levelwise(x, y, 32, config=cfg)
        rng = np.random.default_rng(9)
        x2 = x + 0.3 * rng.standard_normal(x.shape).astype(np.float32)
        t1 = refit_params(t0, x2, y, 32, config=cfg)
        np.testing.assert_array_equal(np.asarray(t1.label_to_leaf),
                                      np.asarray(t0.label_to_leaf))
        _check_invariants(t1, 32, x2)
        ll_warm = tree_log_likelihood(t1, x2, y)
        ll_cold = tree_log_likelihood(
            fit_tree_levelwise(x2, y, 32, config=cfg), x2, y)
        ll_stale = tree_log_likelihood(t0, x2, y)
        assert ll_warm >= ll_stale - 1e-6
        assert ll_warm > ll_cold - 0.1 * abs(ll_cold), (
            f"warm {ll_warm:.4f} vs cold {ll_cold:.4f}")

    def test_refit_deterministic(self):
        x, y, _, _ = _clustered(seed=1, c=16, n=1200)
        cfg = FitConfig(seed=0)
        t0 = fit_tree_levelwise(x, y, 16, config=cfg)
        a = refit_params(t0, x, y, 16, config=cfg)
        b = refit_params(t0, x, y, 16, config=cfg)
        np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
        np.testing.assert_array_equal(np.asarray(a.b), np.asarray(b.b))

    def test_drift_detection_and_subtree_refresh(self):
        """Kill the data of half the labels: the subtrees owning them
        drift (TV -> large), a refresh refits them locally, and the
        result stays a valid tree."""
        x, y, _, _ = _clustered(seed=5, c=32, n=4000, k=8)
        cfg = FitConfig(seed=0)
        t0 = fit_tree_levelwise(x, y, 32, config=cfg)
        cnt0 = label_counts(y, 32)
        keep = y < 16                 # labels 16.. vanish from the stream
        x2, y2 = x[keep], y[keep]
        drifts = subtree_drift(cnt0, label_counts(y2, 32), t0,
                               split_depth=2)
        assert drifts.max() > 0.1, drifts
        t1, cnt1 = refresh_tree(t0, x2, y2, 32, config=cfg,
                                prev_counts=cnt0, drift_threshold=0.1,
                                split_depth=2)
        _check_invariants(t1, 32, x2)
        np.testing.assert_allclose(cnt1, label_counts(y2, 32))

    def test_perm_roundtrip(self):
        x, y, _, _ = _clustered(seed=6, c=13, n=600)
        t = fit_tree_levelwise(x, y, 13, config=FitConfig(seed=2))
        perm = perm_from_tree(t, 13)
        assert sorted(perm.tolist()) == list(range(16))
        real = real_leaf_mask(t, 13)
        assert int(real.sum()) == 13
        np.testing.assert_array_equal(
            perm[real], np.asarray(t.leaf_to_label)[real])


class TestSharded:
    def test_sharded_matches_serial_and_threaded(self):
        """Subtree fan-out is deterministic: serial and threaded executors
        produce bit-identical trees, and the result keeps the invariants
        and the quality of the unsharded fit."""
        x, y, xh, yh = _clustered(seed=0, c=64, n=6000, k=8)
        cfg = FitConfig(seed=0)
        t_serial = fit_tree_sharded(x, y, 64, config=cfg, split_depth=2)
        with ThreadPoolExecutor(2) as ex:
            t_thread = fit_tree_sharded(x, y, 64, config=cfg,
                                        split_depth=2, executor=ex)
        np.testing.assert_array_equal(np.asarray(t_serial.w),
                                      np.asarray(t_thread.w))
        np.testing.assert_array_equal(
            np.asarray(t_serial.label_to_leaf),
            np.asarray(t_thread.label_to_leaf))
        _check_invariants(t_serial, 64, x)
        ll_sharded = tree_log_likelihood(t_serial, xh, yh)
        ll_lvl = tree_log_likelihood(
            fit_tree_levelwise(x, y, 64, config=cfg), xh, yh)
        assert abs(ll_sharded - ll_lvl) <= 0.1 * abs(ll_lvl) + 0.02

    def test_split_depth_edges(self):
        x, y, _, _ = _clustered(seed=2, c=8, n=500, k=4)
        cfg = FitConfig(seed=0)
        # split at the full depth = plain levelwise fit
        t_full = fit_tree_sharded(x, y, 8, config=cfg, split_depth=10)
        t_lvl = fit_tree_levelwise(x, y, 8, config=cfg)
        np.testing.assert_array_equal(np.asarray(t_full.w),
                                      np.asarray(t_lvl.w))
        t0 = fit_tree_sharded(x, y, 8, config=cfg, split_depth=0)
        _check_invariants(t0, 8, x)

    def test_round_robin_shard(self):
        from repro.parallel import round_robin_shard
        all_items = sorted(round_robin_shard(10, 0, 3)
                           + round_robin_shard(10, 1, 3)
                           + round_robin_shard(10, 2, 3))
        assert all_items == list(range(10))
