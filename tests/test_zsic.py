"""ZSIC (Alg. 1) unit + property tests, incl. Lemma 3.2."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import zsic_numpy, zsic_jax, zsic_lmmse_jax, zsic_lmmse_numpy, \
    zsic_blocked, random_covariance, chol_lower


def _setup(n, a, seed=0, condition=20.0):
    rng = np.random.default_rng(seed)
    sigma, _ = random_covariance(n, condition=condition, seed=seed + 1)
    l = chol_lower(sigma)
    w = rng.standard_normal((a, n))
    return w, sigma, l


def test_lemma_3_2_error_support():
    """e_SIC = Y − Z·A·L ∈ CUBE·A·diag(L): |e·(A diag L)⁻¹| ≤ ½ + eps."""
    w, sigma, l = _setup(48, 64)
    alphas = np.exp(np.random.default_rng(2).normal(size=48) * 0.3) * 0.1
    y = w @ l
    z, resid = zsic_numpy(y, l, alphas)
    # residual returned by the algorithm equals Y − Z A L
    recon = (z * alphas[None, :]) @ l
    np.testing.assert_allclose(resid, y - recon, atol=1e-9)
    bound = alphas * np.abs(np.diag(l))
    assert np.all(np.abs(resid) <= 0.5 * bound[None, :] * (1 + 1e-9))


def test_jax_matches_numpy():
    w, sigma, l = _setup(32, 16, seed=3)
    alphas = np.full(32, 0.07)
    z_np, r_np = zsic_numpy(w @ l, l, alphas)
    res = zsic_jax(jnp.asarray(w @ l, jnp.float32), jnp.asarray(l, jnp.float32),
                   jnp.asarray(alphas, jnp.float32))
    # f32 vs f64 rounding can differ on knife-edge ties; demand ≥99.9% match
    agree = (np.asarray(res.codes) == z_np).mean()
    assert agree > 0.999


def test_blocked_matches_unblocked():
    """Blocked (TPU) restructuring is bit-exact vs the column recursion
    (in f64; f32 only reorders accumulation at knife-edge ties)."""
    import jax
    jax.config.update("jax_enable_x64", True)
    try:
        w, sigma, l = _setup(40, 24, seed=4)
        alphas = np.full(40, 0.05)
        lj = jnp.asarray(l, jnp.float64)
        yj = jnp.asarray(w @ l, jnp.float64)
        aj = jnp.asarray(alphas, jnp.float64)
        ref = zsic_jax(yj, lj, aj)
        for block in (8, 16, 40, 64):
            blk = zsic_blocked(yj, lj, aj, block=block)
            np.testing.assert_array_equal(np.asarray(blk.codes),
                                          np.asarray(ref.codes))
            np.testing.assert_allclose(np.asarray(blk.residual),
                                       np.asarray(ref.residual), atol=1e-9)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_lmmse_shrinkage_bounds_and_effect():
    w, sigma, l = _setup(64, 512, seed=5)
    c = 0.8  # low rate → LMMSE matters (paper Fig. 4)
    z, g, resid = zsic_lmmse_numpy(w @ l, l, c)
    assert np.isfinite(g).all()
    # shrinkage typically < 1 in low-rate regime for most columns
    assert np.median(g) < 1.0
    # distortion with LMMSE ≤ without, measured through Σ
    ldiag = np.diag(l)
    alphas = c / ldiag
    z0, r0 = zsic_numpy(w @ l, l, alphas)
    d_lmmse = np.mean(resid ** 2)
    d_plain = np.mean(r0 ** 2)
    assert d_lmmse <= d_plain * 1.001


def test_lmmse_jax_matches_numpy():
    w, sigma, l = _setup(24, 64, seed=6)
    c = 0.3
    z_np, g_np, _ = zsic_lmmse_numpy(w @ l, l, c)
    alphas = c / np.abs(np.diag(l))  # WaterSIC spacing: step_i = c
    res = zsic_lmmse_jax(jnp.asarray(w @ l), jnp.asarray(l),
                         jnp.asarray(alphas, jnp.float32))
    agree = (np.asarray(res.codes) == z_np).mean()
    assert agree > 0.995
    np.testing.assert_allclose(np.asarray(res.gammas), g_np, rtol=5e-3,
                               atol=5e-3)


def test_zero_column_guard():
    """All-zero codes in a column must not produce NaN gammas."""
    n, a = 8, 4
    sigma, _ = random_covariance(n, condition=2.0, seed=7)
    l = chol_lower(sigma)
    y = np.zeros((a, n))
    z, g, resid = zsic_lmmse_numpy(y, l, 1.0)
    assert np.all(z == 0) and np.isfinite(g).all()
    res = zsic_lmmse_jax(jnp.asarray(y, jnp.float32), jnp.asarray(l, jnp.float32),
                         jnp.asarray(1.0, jnp.float32))
    assert np.isfinite(np.asarray(res.gammas)).all()


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 24), a=st.integers(1, 16),
       seed=st.integers(0, 1000), logc=st.floats(-3.0, 0.5))
def test_property_lemma_3_2(n, a, seed, logc):
    """Property: error support bound holds for random shapes/scales."""
    rng = np.random.default_rng(seed)
    sigma, _ = random_covariance(n, condition=10.0, seed=seed)
    l = chol_lower(sigma)
    alphas = np.exp(rng.normal(size=n) * 0.5) * (10.0 ** logc)
    w = rng.standard_normal((a, n)) * 3.0
    y = w @ l
    z, resid = zsic_numpy(y, l, alphas)
    bound = 0.5 * alphas * np.abs(np.diag(l))
    assert np.all(np.abs(resid) <= bound[None, :] * (1 + 1e-9) + 1e-12)
