"""Theorem 2: adversarial noise (p_n = p_D) maximizes the gradient SNR."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import snr as snr_lib


def _random_dist(seed, n, c, temp=1.0):
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((n, c)) * temp
    p = np.exp(logits)
    return jnp.asarray(p / p.sum(-1, keepdims=True), jnp.float32)


def test_empirical_matches_closed_form():
    p_d = _random_dist(0, 4, 12, temp=1.2)
    p_n = _random_dist(1, 4, 12, temp=0.8)
    eta_cf = float(snr_lib.snr_closed_form(p_d, p_n))
    eta_mc = float(snr_lib.snr_empirical(p_d, p_n, jax.random.PRNGKey(2),
                                         n_samples=400_000))
    np.testing.assert_allclose(eta_mc, eta_cf, rtol=0.05)


def test_adversarial_noise_maximizes_snr():
    """eta(p_n = p_D) > eta(uniform), eta(marginal), eta(mixtures)."""
    n, c = 8, 32
    p_d = _random_dist(3, n, c, temp=2.0)
    eta_adv = float(snr_lib.snr_closed_form(p_d, p_d))
    uniform = jnp.full((n, c), 1.0 / c)
    marginal = jnp.tile(jnp.mean(p_d, 0, keepdims=True), (n, 1))
    assert eta_adv > float(snr_lib.snr_closed_form(p_d, uniform))
    assert eta_adv > float(snr_lib.snr_closed_form(p_d, marginal))
    for lam in (0.25, 0.5, 0.75):
        mix = lam * p_d + (1 - lam) * uniform
        assert eta_adv >= float(snr_lib.snr_closed_form(p_d, mix))


def test_snr_upper_bound_is_half_per_xy():
    """At p_n = p_D: sum_y alpha = 1/2 exactly (Jensen bound attained),
    so 1/eta = N * X * (C - 1)."""
    n, c = 5, 16
    p_d = _random_dist(4, n, c)
    eta = float(snr_lib.snr_closed_form(p_d, p_d))
    np.testing.assert_allclose(eta, 1.0 / (n * n * (c - 1.0)), rtol=1e-5)


def test_streamed_agrees_with_dense_reference():
    """The streamed accumulator and the small-C dense-scatter reference
    are the same estimator up to float32 re-association: both must land
    within Monte-Carlo tolerance of the closed form and of each other.
    (Bit-level agreement is impossible by construction — the dense path
    sums per-(x, y) cell then divides by alpha once, the streamed path
    adds g^2/alpha per draw — which is exactly why the docstring promises
    tolerance, not bits.)"""
    p_d = _random_dist(5, 4, 12, temp=1.2)
    p_n = _random_dist(6, 4, 12, temp=0.8)
    rng = jax.random.PRNGKey(7)
    eta_cf = float(snr_lib.snr_closed_form(p_d, p_n))
    eta_stream = float(snr_lib.snr_empirical(p_d, p_n, rng,
                                             n_samples=400_000))
    eta_dense = float(snr_lib.snr_empirical_dense(p_d, p_n, rng,
                                                  n_samples=400_000))
    np.testing.assert_allclose(eta_stream, eta_cf, rtol=0.05)
    np.testing.assert_allclose(eta_dense, eta_cf, rtol=0.05)
    np.testing.assert_allclose(eta_stream, eta_dense, rtol=0.05)


def test_streamed_is_bitwise_deterministic():
    """Identical (rng, n_samples, chunk) -> identical bits, including a
    ragged final chunk; changing the chunking changes the re-association
    order (different bits allowed) but not the value beyond tolerance."""
    p_d = _random_dist(8, 3, 10)
    p_n = _random_dist(9, 3, 10)
    rng = jax.random.PRNGKey(11)
    a = snr_lib.snr_empirical(p_d, p_n, rng, n_samples=50_001, chunk=256)
    b = snr_lib.snr_empirical(p_d, p_n, rng, n_samples=50_001, chunk=256)
    assert (jnp.asarray(a).view(jnp.uint32)
            == jnp.asarray(b).view(jnp.uint32)).item()
    c = snr_lib.snr_empirical(p_d, p_n, rng, n_samples=50_001, chunk=128)
    np.testing.assert_allclose(float(a), float(c), rtol=0.05)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**20), n=st.integers(2, 6), c=st.integers(3, 40),
       temp=st.floats(0.2, 3.0))
def test_property_pd_is_global_max(seed, n, c, temp):
    p_d = _random_dist(seed, n, c, temp)
    p_other = _random_dist(seed + 1, n, c, temp)
    eta_adv = float(snr_lib.snr_closed_form(p_d, p_d))
    eta_other = float(snr_lib.snr_closed_form(p_d, p_other))
    assert eta_adv >= eta_other - 1e-9
