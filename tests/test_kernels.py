"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tree as tree_lib
from repro.kernels import ref as ref_lib
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gather_scores import gather_scores
from repro.kernels.sampled_loss import SAMPLED_KINDS, sampled_head_loss
from repro.kernels.segment_scores import segment_stats
from repro.kernels.tree_logprob import tree_logprob_all

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    def _inputs(self, b, h, sq, skv, hd, dtype, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, h, sq, hd), dtype)
        k = jax.random.normal(ks[1], (b, h, skv, hd), dtype)
        v = jax.random.normal(ks[2], (b, h, skv, hd), dtype)
        return q, k, v

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("sq,skv,hd", [
        (64, 64, 32), (128, 128, 64), (64, 256, 32), (32, 32, 16),
    ])
    def test_causal_sweep(self, sq, skv, hd, dtype):
        q, k, v = self._inputs(2, 3, sq, skv, hd, dtype)
        out = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32,
                              interpret=True)
        ref = ref_lib.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOL[dtype])

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, window):
        q, k, v = self._inputs(1, 2, 128, 128, 32, jnp.float32, seed=1)
        out = flash_attention(q, k, v, causal=True, window=window,
                              blk_q=32, blk_k=32, interpret=True)
        ref = ref_lib.flash_attention_ref(q, k, v, causal=True,
                                          window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        q, k, v = self._inputs(1, 2, 64, 64, 32, jnp.float32, seed=2)
        out = flash_attention(q, k, v, causal=True, softcap=50.0,
                              blk_q=32, blk_k=32, interpret=True)
        ref = ref_lib.flash_attention_ref(q, k, v, causal=True,
                                          softcap=50.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_shape(self):
        """Sq=1 against a long KV (end-aligned positions)."""
        q, k, v = self._inputs(2, 2, 1, 256, 32, jnp.float32, seed=3)
        out = flash_attention(q, k, v, causal=True, blk_q=1, blk_k=64,
                              interpret=True)
        ref = ref_lib.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_model_attention_semantics(self):
        """Kernel mask semantics == the model's einsum attention."""
        from repro.models.layers import _softcap
        q, k, v = self._inputs(1, 2, 64, 64, 16, jnp.float32, seed=4)
        out = flash_attention(q, k, v, causal=True, window=24,
                              blk_q=16, blk_k=16, interpret=True)
        # direct reference with the model's mask construction
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(16.0)
        pos = jnp.arange(64)
        delta = pos[:, None] - pos[None, :]
        valid = (delta >= 0) & (delta < 24)
        probs = jax.nn.softmax(jnp.where(valid, logits, -1e30), -1)
        ref = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestTreeLogprob:
    @pytest.mark.parametrize("c,k,blk_c", [(64, 8, 16), (256, 16, 64),
                                           (1024, 4, 256), (128, 8, 128)])
    def test_sweep_vs_ref(self, c, k, blk_c):
        t = tree_lib.init_tree(jax.random.PRNGKey(0), c, k, scale=0.8)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, k))
        out = tree_logprob_all(t.w, t.b, x, blk_b=16, blk_c=blk_c,
                               interpret=True)
        ref = ref_lib.tree_logprob_all_ref(t.w, t.b, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_core_tree_path(self):
        """Kernel output (leaf order) == core log_prob_all (label order)."""
        c, k = 37, 6
        t = tree_lib.init_tree(jax.random.PRNGKey(2), c, k, scale=0.5)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, k))
        out = tree_logprob_all(t.w, t.b, x, blk_b=16, blk_c=16,
                               interpret=True)
        core = tree_lib.log_prob_all(t, x)           # (B, C) label order
        out_labels = jnp.take(out, t.label_to_leaf, axis=-1)
        np.testing.assert_allclose(np.asarray(out_labels), np.asarray(core),
                                   rtol=1e-5, atol=1e-5)

    def test_bfloat16_inputs(self):
        c, k = 128, 8
        t = tree_lib.init_tree(jax.random.PRNGKey(4), c, k, scale=0.5)
        x = jax.random.normal(jax.random.PRNGKey(5), (16, k), jnp.bfloat16)
        out = tree_logprob_all(t.w, t.b, x, blk_b=16, blk_c=32,
                               interpret=True)
        ref = ref_lib.tree_logprob_all_ref(t.w, t.b,
                                           x.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-2, atol=5e-2)


class TestGatherScores:
    @pytest.mark.parametrize("c,kdim,t,n", [(64, 16, 32, 2), (512, 32, 64, 4),
                                            (128, 8, 256, 1)])
    def test_sweep_vs_ref(self, c, kdim, t, n):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        w = jax.random.normal(ks[0], (c, kdim))
        b = jax.random.normal(ks[1], (c,))
        h = jax.random.normal(ks[2], (t, kdim))
        ids = jax.random.randint(ks[3], (t, n), 0, c)
        out = gather_scores(w, b, h, ids, blk_t=16, interpret=True)
        ref = ref_lib.gather_scores_ref(w, b, h, ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bfloat16_table(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        w = jax.random.normal(ks[0], (128, 16), jnp.bfloat16)
        b = jnp.zeros((128,), jnp.bfloat16)
        h = jax.random.normal(ks[2], (32, 16), jnp.bfloat16)
        ids = jax.random.randint(ks[3], (32, 2), 0, 128)
        out = gather_scores(w, b, h, ids, blk_t=16, interpret=True)
        ref = ref_lib.gather_scores_ref(w, b, h, ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-2, atol=3e-2)


class TestSampledLoss:
    """The fused sampled-head loss kernel (fwd + bwd in one row pass) vs
    the unfused gather→einsum→loss→coefficient oracle."""

    def _inputs(self, c, kdim, t, m, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        w = jax.random.normal(ks[0], (c, kdim))
        b = jax.random.normal(ks[1], (c,))
        h = jax.random.normal(ks[2], (t, kdim))
        ids = jax.random.randint(ks[3], (t, m), 0, c)
        lp = -jnp.abs(jax.random.normal(ks[4], (t, m)))
        return w, b, h, ids, lp

    @pytest.mark.parametrize("kind", SAMPLED_KINDS)
    def test_all_kinds_vs_ref(self, kind):
        c, kdim, t, m = 64, 16, 32, 3
        w, b, h, ids, lp = self._inputs(c, kdim, t, m)
        kw = dict(kind=kind, num_labels=c, reg=1e-3, softcap=25.0)
        out = sampled_head_loss(w, b, h, ids, lp, blk_t=8, interpret=True,
                                **kw)
        ref = ref_lib.sampled_head_loss_ref(w, b, h, ids, lp, **kw)
        for o, r, name in zip(out, ref, ["loss", "coeff", "xi", "dh"]):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"{kind}/{name}")

    @pytest.mark.parametrize("t,blk_t", [(30, 8), (7, 16), (64, 64)])
    def test_ragged_t_padding(self, t, blk_t):
        """T not divisible by blk_t: padded rows must not leak into the
        sliced outputs."""
        c, kdim, m = 32, 8, 2
        w, b, h, ids, lp = self._inputs(c, kdim, t, m, seed=1)
        kw = dict(kind="adversarial_ns", num_labels=c)
        out = sampled_head_loss(w, b, h, ids, lp, blk_t=blk_t,
                                interpret=True, **kw)
        ref = ref_lib.sampled_head_loss_ref(w, b, h, ids, lp, **kw)
        for o, r in zip(out, ref):
            assert o.shape == r.shape
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)

    def test_accidental_hit_masking(self):
        """sampled_softmax: a negative equal to the positive is masked out
        of the candidate set — zero coefficient in kernel and ref."""
        c, kdim, t, m = 16, 8, 8, 3
        w, b, h, ids, lp = self._inputs(c, kdim, t, m, seed=2)
        ids = ids.at[:, 1].set(ids[:, 0])           # force collisions
        kw = dict(kind="sampled_softmax", num_labels=c)
        out = sampled_head_loss(w, b, h, ids, lp, blk_t=8, interpret=True,
                                **kw)
        ref = ref_lib.sampled_head_loss_ref(w, b, h, ids, lp, **kw)
        np.testing.assert_allclose(np.asarray(out[1][:, 1]), 0.0)
        for o, r in zip(out, ref):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)

    def test_sparse_head_loss_kernel_routing(self):
        """heads.sparse_head_loss(use_kernel=True) == the jnp path, and
        ops.use_pallas(False) A/B routes to the reference."""
        from repro.core import heads as heads_lib
        from repro.core.heads import Generator, HeadConfig
        from repro.kernels import ops

        c, kdim, kg, bsz = 32, 16, 4, 24
        tr = tree_lib.init_tree(jax.random.PRNGKey(0), c, kg, scale=0.5)
        cfg = HeadConfig(num_labels=c, kind="adversarial_ns", n_neg=3,
                         reg=1e-3)
        params = heads_lib.init_head_params(jax.random.PRNGKey(1), c,
                                            kdim, scale=0.3)
        h = jax.random.normal(jax.random.PRNGKey(2), (bsz, kdim))
        xg = jax.random.normal(jax.random.PRNGKey(3), (bsz, kg))
        y = jax.random.randint(jax.random.PRNGKey(4), (bsz,), 0, c)
        rng = jax.random.PRNGKey(6)
        args = (cfg, params, Generator(tree=tr), h, xg, y, rng)
        jnp_path = heads_lib.sparse_head_loss(*args, softcap=30.0)
        ker_path = heads_lib.sparse_head_loss(*args, softcap=30.0,
                                              use_kernel=True)
        ops.use_pallas(False)
        try:
            ref_path = heads_lib.sparse_head_loss(*args, softcap=30.0,
                                                  use_kernel=True)
        finally:
            ops.use_pallas(True)
        for a, b2 in ((jnp_path, ker_path), (ref_path, ker_path)):
            np.testing.assert_allclose(float(a[0]), float(b2[0]),
                                       rtol=1e-5)
            np.testing.assert_allclose(np.asarray(a[2].dw),
                                       np.asarray(b2[2].dw),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(a[3]), np.asarray(b2[3]),
                                       rtol=1e-4, atol=1e-5)


class TestSegmentStats:
    """The genfit segment-reduction kernel vs jax.ops.segment_sum."""

    @pytest.mark.parametrize("n,d,s", [(300, 17, 8), (1024, 4, 64),
                                       (37, 1, 5), (513, 32, 128)])
    def test_sweep_vs_ref(self, n, d, s):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        vals = jax.random.normal(ks[0], (n, d))
        seg = jax.random.randint(ks[1], (n,), 0, s)
        out = segment_stats(vals, seg, s, blk_n=128, interpret=True)
        ref = ref_lib.segment_stats_ref(vals, seg, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_out_of_range_ids_dropped(self):
        """Padding rows carry id == S and must contribute nothing."""
        vals = jnp.ones((16, 3))
        seg = jnp.concatenate([jnp.zeros((8,), jnp.int32),
                               jnp.full((8,), 4, jnp.int32)])
        out = segment_stats(vals, seg, 4, blk_n=8, interpret=True)
        expect = np.zeros((4, 3))
        expect[0] = 8.0
        np.testing.assert_allclose(np.asarray(out), expect)

    def test_levelwise_fit_with_kernel_matches_default(self):
        """FitConfig(use_kernel=True) routes the fit's reductions through
        the kernel; the fitted tree must match the jnp path bit-for-bit
        in interpret mode."""
        from repro.core.tree_fit import FitConfig
        from repro.genfit import fit_tree_levelwise
        rng = np.random.default_rng(0)
        c, k, n = 8, 4, 400
        centers = rng.standard_normal((c, k)) * 3.0
        y = rng.integers(0, c, n)
        x = (centers[y] + rng.standard_normal((n, k))).astype(np.float32)
        t_jnp = fit_tree_levelwise(x, y, c, config=FitConfig(seed=0))
        t_ker = fit_tree_levelwise(x, y, c,
                                   config=FitConfig(seed=0,
                                                    use_kernel=True))
        np.testing.assert_allclose(np.asarray(t_jnp.w),
                                   np.asarray(t_ker.w),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(t_jnp.label_to_leaf),
                                      np.asarray(t_ker.label_to_leaf))


def _ssd_block_inputs(q, n, h, p=16, bsz=1, nc=2, dt_shift=0.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (bsz, nc, q, h)) - 1.0)
    dt = dt + dt_shift
    a_neg = -jnp.exp(0.3 * jax.random.normal(ks[1], (h,)))
    seg = jnp.cumsum(dt * a_neg, axis=2)
    xbar = jax.random.normal(ks[2], (bsz, nc, q, h, p)) * dt[..., None]
    bm = jax.random.normal(ks[3], (bsz, nc, q, n)) / np.sqrt(n)
    cm = jax.random.normal(ks[4], (bsz, nc, q, n)) / np.sqrt(n)
    dy = jax.random.normal(ks[5], (bsz, nc, q, h, p))
    return (cm, bm, seg, xbar), dy


@pytest.mark.parametrize("q,n,h,dt_shift,mxu", [
    (128, 16, 2, 0.0, None), (128, 16, 3, 0.0, None),
    (128, 128, 2, 0.0, None), (128, 128, 3, 0.0, None),
    (256, 16, 2, 0.0, None), (256, 16, 3, 0.0, None),
    (256, 128, 2, 0.0, None), (256, 128, 3, 0.0, None),
    # dt + 1: the summed decay passes 88 within one chunk, where exp
    # overflows; the masked triangle must leak no NaN into the gradients.
    (256, 16, 2, 1.0, None),
    # The TPU's matmul operands (one bf16 pass), run by the interpreter.
    (256, 128, 3, 0.0, jnp.bfloat16),
    # A chunk shorter than the 128 lanes stays on the einsums.
    (8, 16, 2, 0.0, None),
])
def test_ssd_intra_vs_einsums(q, n, h, dt_shift, mxu):
    """The SSD intra-chunk kernel's output and its gradients with respect
    to cm, bm, seg and xbar against the einsums it replaces."""
    from repro.kernels import ops
    from repro.kernels.ssd_intra import ssd_intra

    args, dy = _ssd_block_inputs(q, n, h, dt_shift=dt_shift)
    fn = (ops.ssd_intra if mxu is None
          else lambda *a: ssd_intra(*a, True, mxu))
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    assert ("pallas_call" in jaxpr) == (q % 128 == 0)
    out, vjp = jax.vjp(fn, *args)
    ref, ref_vjp = jax.vjp(ref_lib.ssd_intra_ref, *args)
    # bf16 operands: a few of bf16's 2^-8 steps of the largest value.
    tol = 1e-5 if mxu is None else 4 * 2.0 ** -8
    for got, want in zip((out,) + vjp(dy), (ref,) + ref_vjp(dy)):
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=tol,
            atol=tol * float(jnp.max(jnp.abs(want))))
