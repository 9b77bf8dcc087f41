"""SSD chunked-vs-sequential equivalence; MoE dispatch vs dense oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro import configs as cfg_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib


def _ssd_inputs(seed, b, s, h, p, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    a_neg = -jnp.exp(0.3 * jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n)) / np.sqrt(n)
    cm = jax.random.normal(ks[4], (b, s, n)) / np.sqrt(n)
    d = jnp.ones((h,)) * 0.5
    return x, dt, a_neg, bm, cm, d


class TestSSD:
    def test_chunked_matches_sequential(self):
        x, dt, a, bm, cm, d = _ssd_inputs(0, 2, 32, 3, 8, 4)
        y_ref, s_ref = ssm_lib.ssd_sequential(x, dt, a, bm, cm, d)
        for chunk in (4, 8, 16, 32):
            y, s = ssm_lib.ssd_chunked(x, dt, a, bm, cm, d, chunk)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                       rtol=2e-4, atol=2e-4)

    def test_initial_state_continuation(self):
        """Chunked over [0:16] then [16:32] with carried state == one pass."""
        x, dt, a, bm, cm, d = _ssd_inputs(1, 1, 32, 2, 4, 4)
        y_ref, s_ref = ssm_lib.ssd_sequential(x, dt, a, bm, cm, d)
        y1, s1 = ssm_lib.ssd_chunked(x[:, :16], dt[:, :16], a, bm[:, :16],
                                     cm[:, :16], d, 8)
        y2, s2 = ssm_lib.ssd_chunked(x[:, 16:], dt[:, 16:], a, bm[:, 16:],
                                     cm[:, 16:], d, 8, initial_state=s1)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                                   np.asarray(y_ref), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_chunked_grads_finite_where_decay_overflows(self):
        """A long chunk whose summed decay exceeds fp32's exp range: the
        masked upper triangle must not leak NaN into the gradients, which
        must match the sequential oracle's."""
        x, dt, a, bm, cm, d = _ssd_inputs(2, 1, 256, 2, 4, 4)
        dt = dt + 1.0                       # sum(dt·|A|) over 256 steps > 88

        def loss(fn, x, dt, bm):
            y, s = fn(x, dt, a, bm, cm, d)
            return jnp.sum(y ** 2) + jnp.sum(s ** 2)

        chunked = jax.grad(lambda *v: loss(
            lambda *u: ssm_lib.ssd_chunked(*u, 256), *v), argnums=(0, 1, 2))
        seq = jax.grad(lambda *v: loss(ssm_lib.ssd_sequential, *v),
                       argnums=(0, 1, 2))
        for g, g_ref in zip(chunked(x, dt, bm), seq(x, dt, bm)):
            assert bool(jnp.all(jnp.isfinite(g)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                       rtol=1e-3, atol=1e-3)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000), chunk=st.sampled_from([2, 4, 8]))
    def test_property_chunked_equals_sequential(self, seed, chunk):
        x, dt, a, bm, cm, d = _ssd_inputs(seed, 1, 16, 2, 4, 3)
        y_ref, _ = ssm_lib.ssd_sequential(x, dt, a, bm, cm, d)
        y, _ = ssm_lib.ssd_chunked(x, dt, a, bm, cm, d, chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=5e-4, atol=5e-4)

    def test_decode_step_matches_sequential(self):
        """ssm_block decode steps reproduce the train-mode forward."""
        cfg = cfg_lib.reduced_config("mamba2-370m")
        cfg = dataclasses.replace(cfg, dtype="float32")
        params = ssm_lib.init_ssm_block(jax.random.PRNGKey(0), cfg)
        b, s = 2, 8
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (b, s,
                                                            cfg.d_model))
        y_train, _ = ssm_lib.ssm_block(params, cfg, x)
        cache = ssm_lib.init_ssm_cache(cfg, b)
        ys = []
        for t in range(s):
            y_t, cache = ssm_lib.ssm_block(params, cfg, x[:, t:t + 1],
                                           cache=cache, decode=True)
            ys.append(y_t)
        y_dec = jnp.concatenate(ys, 1)
        np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_train),
                                   rtol=1e-3, atol=1e-3)


class TestMoE:
    def _cfg(self, **over):
        base = cfg_lib.reduced_config("deepseek-moe-16b")
        return dataclasses.replace(base, dtype="float32", **over)

    def test_dispatch_matches_dense_oracle(self):
        """With generous capacity nothing drops; gather path == oracle."""
        cfg = self._cfg(capacity_factor=8.0)
        params = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
        y, metrics = moe_lib.moe_ffn(params, cfg, x)
        y_ref = moe_lib.moe_ffn_dense_oracle(params, cfg, x)
        assert float(metrics["moe_dropped_frac"]) == 0.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_mixtral_router_convention(self):
        cfg = dataclasses.replace(
            cfg_lib.reduced_config("mixtral-8x22b"), dtype="float32",
            capacity_factor=8.0)
        params = moe_lib.init_moe(jax.random.PRNGKey(2), cfg)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model))
        y, _ = moe_lib.moe_ffn(params, cfg, x)
        y_ref = moe_lib.moe_ffn_dense_oracle(params, cfg, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_capacity_drops_are_counted(self):
        cfg = self._cfg(capacity_factor=0.25)
        params = moe_lib.init_moe(jax.random.PRNGKey(4), cfg)
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))
        y, metrics = moe_lib.moe_ffn(params, cfg, x)
        assert float(metrics["moe_dropped_frac"]) > 0.0
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_grads_flow(self):
        cfg = self._cfg(capacity_factor=4.0)
        params = moe_lib.init_moe(jax.random.PRNGKey(6), cfg)
        x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, cfg.d_model))

        def f(p):
            y, _ = moe_lib.moe_ffn(p, cfg, x)
            return jnp.sum(y ** 2)

        g = jax.grad(f)(params)
        assert float(jnp.abs(g["router"]).sum()) > 0
        assert float(jnp.abs(g["w_gate"]).sum()) > 0
