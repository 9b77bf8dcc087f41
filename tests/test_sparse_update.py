"""Sparse-row head training (DESIGN.md §8): the O(B·K·n_neg) gradient path
vs the dense autodiff oracle.

Pins the tentpole guarantees:
  * closed-form scatter coefficients == autodiff of the shared objective,
  * SparseRows == dense head gradient under forced duplicate collisions
    (same negative drawn twice / negative == positive),
  * identical params after N optimizer steps — exact for Adagrad/SGD on
    touched rows, lazy-decay semantics for AdamW,
  * metrics parity (pos_score/neg_score, mask=None and all-masked),
  * full train_step sparse == dense (trunk grads driven by the analytic
    head cotangent),
  * global-norm clipping sees the sparse leaves' true norm.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import heads as heads_lib
from repro.core import tree as tree_lib
from repro.core.heads import Generator, HeadConfig
from repro.kernels.sampled_loss import SAMPLED_KINDS, loss_and_coeffs
from repro.optim import (OptimizerConfig, apply_updates, global_norm,
                         init_opt_state)
from repro.optim import sparse as sparse_lib

C, K, KG = 16, 12, 4        # tiny C: collisions guaranteed at n_neg > 1


def _gen(kind, c=C, seed=0):
    if kind == "freq_ns":
        return heads_lib.make_freq_generator(
            jnp.arange(1, c + 1, dtype=jnp.float32))
    return Generator(tree=tree_lib.init_tree(jax.random.PRNGKey(seed), c,
                                             KG, scale=0.5))


def _problem(batch=48, seed=0, c=C):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(ks[0], (batch, K))
    xg = jax.random.normal(ks[1], (batch, KG))
    y = jax.random.randint(ks[2], (batch,), 0, c)
    params = heads_lib.init_head_params(ks[3], c, K, scale=0.3)
    return params, h, xg, y


def _dense_grads(cfg, params, gen, h, xg, y, rng, mask=None):
    (loss, metrics), g = jax.value_and_grad(
        heads_lib.head_loss, argnums=1, has_aux=True)(
            cfg, params, gen, h, xg, y, rng, mask=mask)
    return loss, metrics, g


class TestCoefficients:
    """Closed-form coeff == jax.vjp of the shared objective's own loss."""

    @pytest.mark.parametrize("kind", SAMPLED_KINDS)
    @pytest.mark.parametrize("reg,softcap", [(0.0, 0.0), (1e-2, 25.0)])
    def test_coeff_is_score_gradient(self, kind, reg, softcap):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        t, m = 17, 4
        scores = 3.0 * jax.random.normal(ks[0], (t, m))
        lp = -jnp.abs(jax.random.normal(ks[1], (t, m)))
        ids = jax.random.randint(ks[2], (t, m), 0, 5)   # frequent hits
        hit = (ids == ids[:, :1]).at[:, 0].set(False)
        kw = dict(kind=kind, num_labels=C, reg=reg, softcap=softcap)
        loss_vec, vjp = jax.vjp(
            lambda s: loss_and_coeffs(s, lp, hit, **kw)[0], scores)
        (want,) = vjp(jnp.ones_like(loss_vec))
        _, coeff, _ = loss_and_coeffs(scores, lp, hit, **kw)
        np.testing.assert_allclose(np.asarray(coeff), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


class TestSparseVsDenseGrads:
    @pytest.mark.parametrize("kind", SAMPLED_KINDS)
    @pytest.mark.parametrize("n_neg", [1, 4])
    def test_grads_match(self, kind, n_neg):
        cfg = HeadConfig(num_labels=C, kind=kind, n_neg=n_neg, reg=1e-3)
        gen = _gen(kind)
        params, h, xg, y = _problem()
        mask = (jnp.arange(48) % 3 > 0).astype(jnp.float32)
        rng = jax.random.PRNGKey(7)
        loss_d, met_d, gd = _dense_grads(cfg, params, gen, h, xg, y, rng,
                                         mask)
        loss_s, met_s, srows, dh = heads_lib.sparse_head_loss(
            cfg, params, gen, h, xg, y, rng, mask=mask)
        np.testing.assert_allclose(float(loss_s), float(loss_d), rtol=1e-6)
        dw, db = sparse_lib.to_dense(srows, params.w.shape)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(gd.w),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(db), np.asarray(gd.b),
                                   rtol=2e-5, atol=1e-6)
        gh = jax.grad(lambda hh: heads_lib.head_loss(
            cfg, params, gen, hh, xg, y, rng, mask=mask)[0])(h)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(gh),
                                   rtol=2e-5, atol=1e-6)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000), c=st.sampled_from([4, 8, 16]),
           kind=st.sampled_from(["adversarial_ns", "uniform_ns",
                                 "sampled_softmax", "nce"]))
    def test_property_forced_collisions(self, seed, c, kind):
        """Duplicate-id correctness: tiny C + n_neg=4 forces repeated
        negatives and negative==positive collisions; sparse coefficients
        must SUM per unique row to match the dense scatter-add."""
        cfg = HeadConfig(num_labels=c, kind=kind, n_neg=4, reg=1e-3)
        gen = _gen(kind, c=c, seed=seed)
        params, h, xg, y = _problem(batch=32, seed=seed, c=c)
        rng = jax.random.PRNGKey(seed + 1)
        # sanity: the draw really does collide
        ids, _, _ = heads_lib._sample_candidates(cfg, gen, xg,
                                                 y.astype(jnp.int32), rng)
        flat = np.asarray(ids.reshape(-1))
        assert len(np.unique(flat)) < flat.size, "no collision drawn"
        _, _, gd = _dense_grads(cfg, params, gen, h, xg, y, rng)
        _, _, srows, _ = heads_lib.sparse_head_loss(cfg, params, gen, h,
                                                    xg, y, rng)
        uniq = np.asarray(srows.ids)
        live = uniq[uniq < c]
        assert len(np.unique(live)) == len(live), "ids not deduped"
        dw, db = sparse_lib.to_dense(srows, params.w.shape)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(gd.w),
                                   rtol=5e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(db), np.asarray(gd.b),
                                   rtol=5e-5, atol=1e-6)


class TestOptimizerEquivalence:
    def _run(self, kind, n_neg, opt_name, steps=5, clip=1.0, wd=0.0):
        cfg = HeadConfig(num_labels=C, kind=kind, n_neg=n_neg, reg=1e-3)
        gen = _gen(kind)
        params, h, xg, y = _problem()
        ocfg = OptimizerConfig(name=opt_name, learning_rate=0.1,
                               clip_norm=clip, weight_decay=wd)
        pd = ps = params
        sd = ss = init_opt_state(ocfg, params)
        for s in range(steps):
            r = jax.random.fold_in(jax.random.PRNGKey(11), s)
            _, _, gd = _dense_grads(cfg, pd, gen, h, xg, y, r)
            pd, sd, _ = apply_updates(ocfg, pd, gd, sd)
            _, _, srows, _ = heads_lib.sparse_head_loss(cfg, ps, gen, h,
                                                        xg, y, r)
            ps, ss, _ = apply_updates(ocfg, ps, srows, ss)
        return pd, ps

    @pytest.mark.parametrize("kind", SAMPLED_KINDS)
    @pytest.mark.parametrize("n_neg", [1, 4])
    def test_adagrad_exact(self, kind, n_neg):
        pd, ps = self._run(kind, n_neg, "adagrad")
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ps.b), np.asarray(pd.b),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("kind", ["adversarial_ns", "ove"])
    def test_sgd_exact(self, kind):
        pd, ps = self._run(kind, 2, "sgd")
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-5, atol=1e-6)

    def test_adamw_exact_when_all_rows_touched(self):
        """With every row touched every step, lazy AdamW == dense AdamW
        (decay/bias correction applied on schedule). C=4, B=48, n_neg=4."""
        cfg = HeadConfig(num_labels=4, kind="uniform_ns", n_neg=4)
        gen = Generator()
        params, h, xg, y = _problem(batch=48, c=4)
        ocfg = OptimizerConfig(name="adamw", learning_rate=0.01,
                               weight_decay=0.1)
        pd = ps = params
        sd = ss = init_opt_state(ocfg, params)
        for s in range(4):
            r = jax.random.fold_in(jax.random.PRNGKey(5), s)
            ids, _, _ = heads_lib._sample_candidates(
                cfg, gen, xg, y.astype(jnp.int32), r)
            assert len(np.unique(np.asarray(ids))) == 4  # all rows touched
            _, _, gd = _dense_grads(cfg, pd, gen, h, xg, y, r)
            pd, sd, _ = apply_updates(ocfg, pd, gd, sd)
            _, _, srows, _ = heads_lib.sparse_head_loss(cfg, ps, gen, h,
                                                        xg, y, r)
            ps, ss, _ = apply_updates(ocfg, ps, srows, ss)
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-5, atol=1e-6)

    def test_adamw_lazy_rows_untouched(self):
        """Lazy AdamW defers untouched rows: rows outside the touched set
        keep exactly their old bits under the sparse path (dense AdamW
        decays them immediately). Since the exact catch-up (DESIGN.md
        §11) this is deferral, not a deviation — the skipped decay and
        momentum tail are replayed in closed form on the row's next
        touch (tests/test_state_memory.py::TestLazyAdamW)."""
        cfg = HeadConfig(num_labels=64, kind="uniform_ns", n_neg=1)
        gen = Generator()
        params, h, xg, y = _problem(batch=4, c=64)
        ocfg = OptimizerConfig(name="adamw", learning_rate=0.01,
                               weight_decay=0.5)
        r = jax.random.PRNGKey(5)
        _, _, srows, _ = heads_lib.sparse_head_loss(cfg, params, gen, h,
                                                    xg, y, r)
        ps, _, _ = apply_updates(ocfg, params,  srows,
                                 init_opt_state(ocfg, params))
        _, _, gd = _dense_grads(cfg, params, gen, h, xg, y, r)
        pd, _, _ = apply_updates(ocfg, params, gd,
                                 init_opt_state(ocfg, params))
        touched = np.unique(np.asarray(srows.ids))
        touched = touched[touched < 64]
        untouched = np.setdiff1d(np.arange(64), touched)
        w0 = np.asarray(params.w)
        np.testing.assert_array_equal(np.asarray(ps.w)[untouched],
                                      w0[untouched])       # lazy: frozen
        assert np.abs(np.asarray(pd.w)[untouched]
                      - w0[untouched]).max() > 0            # dense: decayed
        np.testing.assert_allclose(np.asarray(ps.w)[touched],
                                   np.asarray(pd.w)[touched],
                                   rtol=1e-5, atol=1e-6)


class TestClipNorm:
    def test_global_norm_counts_sparse_leaves(self):
        cfg = HeadConfig(num_labels=C, kind="adversarial_ns", n_neg=3)
        gen = _gen("adversarial_ns")
        params, h, xg, y = _problem()
        rng = jax.random.PRNGKey(2)
        _, _, gd = _dense_grads(cfg, params, gen, h, xg, y, rng)
        _, _, srows, _ = heads_lib.sparse_head_loss(cfg, params, gen, h,
                                                    xg, y, rng)
        trunk = jnp.ones((3, 5))
        dense_tree = {"trunk": trunk, "head": {"w": gd.w, "b": gd.b}}
        sparse_tree = {"trunk": trunk, "head": srows}
        np.testing.assert_allclose(float(global_norm(sparse_tree)),
                                   float(global_norm(dense_tree)),
                                   rtol=1e-5)


class TestMetricsParity:
    @pytest.mark.parametrize("kind", SAMPLED_KINDS)
    @pytest.mark.parametrize("mask_case", ["none", "partial", "all_masked"])
    def test_metrics_match_dense(self, kind, mask_case):
        cfg = HeadConfig(num_labels=C, kind=kind, n_neg=2)
        gen = _gen(kind)
        params, h, xg, y = _problem(batch=12)
        mask = {"none": None,
                "partial": (jnp.arange(12) < 7).astype(jnp.float32),
                "all_masked": jnp.zeros((12,), jnp.float32)}[mask_case]
        rng = jax.random.PRNGKey(9)
        _, met_d, _ = _dense_grads(cfg, params, gen, h, xg, y, rng, mask)
        _, met_s, _, _ = heads_lib.sparse_head_loss(cfg, params, gen, h,
                                                    xg, y, rng, mask=mask)
        assert set(met_d) == set(met_s), (kind, met_d, met_s)
        assert "pos_score" in met_d
        if kind in ("uniform_ns", "freq_ns", "adversarial_ns", "nce"):
            assert "neg_score" in met_d
        for k2 in met_d:
            np.testing.assert_allclose(float(met_s[k2]), float(met_d[k2]),
                                       rtol=1e-5, atol=1e-7,
                                       err_msg=f"{kind}/{mask_case}/{k2}")


class TestTrainStep:
    @pytest.mark.parametrize("kind", ["adversarial_ns", "sampled_softmax"])
    def test_full_train_step_matches_dense(self, kind):
        from repro.data import lm_batch_fn
        from repro.models import lm_head
        from repro.models.config import ModelConfig
        from repro.train.step import init_train_state, make_train_step

        cfg = ModelConfig(name="t", num_layers=2, d_model=32, d_ff=64,
                          vocab_size=128, num_heads=2, num_kv_heads=2,
                          vocab_pad_multiple=64, gen_feature_dim=8,
                          dtype="float32", remat=False)
        hcfg = lm_head.head_config(cfg, kind, n_neg=2, reg=1e-4)
        opt = OptimizerConfig(name="adagrad", learning_rate=0.05,
                              clip_norm=1.0)
        make = lm_batch_fn(cfg.vocab_size, 4, 16, seed=0)
        st_d = init_train_state(jax.random.PRNGKey(0), cfg, opt, kind)
        st_s = init_train_state(jax.random.PRNGKey(0), cfg, opt, kind)
        step_d = jax.jit(make_train_step(cfg, hcfg, opt,
                                         head_update="dense"))
        step_s = jax.jit(make_train_step(cfg, hcfg, opt,
                                         head_update="sparse"))
        for s in range(3):
            r = jax.random.fold_in(jax.random.PRNGKey(1), s)
            b = {k: jnp.asarray(v) for k, v in make(s).items()}
            st_d, md = step_d(st_d, b, r)
            st_s, ms = step_s(st_s, b, r)
            assert sorted(md) == sorted(ms)
        # fp32 tolerance: dense autodiff scatter-adds occurrence-order,
        # the sparse path segment-sums per unique row; Adagrad's rsqrt
        # amplifies the last-bit difference over steps.
        for (pa, da), (pb, db_) in zip(
                jax.tree_util.tree_flatten_with_path(st_d.params)[0],
                jax.tree_util.tree_flatten_with_path(st_s.params)[0]):
            assert pa == pb
            np.testing.assert_allclose(np.asarray(db_), np.asarray(da),
                                       rtol=5e-3, atol=5e-5,
                                       err_msg=str(pa))

    def test_auto_resolution(self):
        from repro.train.step import resolve_head_update
        assert resolve_head_update("auto", "softmax") == "dense"
        assert resolve_head_update("auto", "adversarial_ns") == "sparse"
        with pytest.raises(AssertionError):
            resolve_head_update("sparse", "softmax")


class TestXcTrain:
    def test_train_linear_head_sparse_matches_dense(self):
        from repro.core.xc_train import train_linear_head
        rng = np.random.default_rng(0)
        c, n = 24, 400
        centers = rng.standard_normal((c, K)) * 2.0
        y = rng.integers(0, c, n)
        x = jnp.asarray(centers[y] + 0.4 * rng.standard_normal((n, K)),
                        jnp.float32)
        y = jnp.asarray(y)
        xg = x[:, :KG]
        gen = Generator(tree=tree_lib.init_tree(jax.random.PRNGKey(0), c,
                                                KG, scale=0.5))
        cfg = HeadConfig(num_labels=c, kind="adversarial_ns", n_neg=2,
                         reg=1e-4)
        pd = train_linear_head(cfg, gen, x, xg, y, 0.1, 40,
                               head_update="dense")
        ps = train_linear_head(cfg, gen, x, xg, y, 0.1, 40,
                               head_update="sparse")
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-4, atol=1e-5)
