"""Regression tests for bugs found during development."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CalibStats, quantize_at_rate, random_covariance


def test_rate_search_subsamples_residual_rows():
    """quantize_at_rate row-subsamples W; Σ_{Δ,X̂} (a, n) must be
    subsampled with the same rows (crash found via benchmarks/rd_curves)."""
    rng = np.random.default_rng(0)
    a, n = 96, 64   # a > min_rows so the subsample path triggers
    sigma, _ = random_covariance(n, condition=10.0, seed=1)
    w = rng.standard_normal((a, n)).astype(np.float32)
    sdx = (0.01 * rng.standard_normal((a, n))).astype(np.float32)
    stats = CalibStats(sigma_x=jnp.asarray(sigma, jnp.float32),
                       sigma_delta_xhat=jnp.asarray(sdx))
    q = quantize_at_rate(jnp.asarray(w), stats, 2.5, min_rows=32,
                         subsample_rows=0.3, seed=2)
    assert abs(q.entropy_bits - 2.5) < 0.1
    assert np.isfinite(np.asarray(q.dequant())).all()


def test_decode_cache_dtype_consistency():
    """bf16 cache + f32 params must not raise (dtype cast at cache write)."""
    from repro.configs import get_config
    from repro.models import decode_step, init_cache, init_params, split_tree
    cfg = get_config("minitron-8b").reduced()
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    cache = init_cache(cfg, 2, 8, jnp.bfloat16)
    logits, cache2 = decode_step(cfg, params, cache, jnp.zeros((2, 1),
                                                               jnp.int32))
    assert cache2.kv.k.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(logits).all())


def test_padded_vocab_logits_true_size():
    """Odd vocab (whisper 51865) pads the table but logits slice back."""
    from repro.configs import get_config
    from repro.models import forward_train, init_params, split_tree
    cfg = get_config("whisper-base").reduced()
    assert cfg.padded_vocab % 256 == 0
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    b = {"frames": jnp.ones((1, cfg.enc_seq, cfg.d_model)) * 0.1,
         "tokens": jnp.zeros((1, 4), jnp.int32),
         "targets": jnp.zeros((1, 4), jnp.int32)}
    logits = forward_train(cfg, params, b)
    assert logits.shape[-1] == cfg.vocab
