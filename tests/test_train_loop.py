"""Train loop: loss decreases, microbatching is exact, WSD schedule,
compressed-DP step runs with error feedback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.data import DataConfig, global_batch_for_step
from repro.launch.mesh import make_mesh
from repro.models import init_params, split_tree
from repro.train import (AdamWConfig, TrainState, adamw_init,
                         cosine_schedule, make_compressed_step,
                         make_train_step, microbatch_grads, wsd_schedule)

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv=2, d_ff=64, vocab=64, head_dim=16)


def _setup(seed=0):
    params, _ = split_tree(init_params(CFG, jax.random.PRNGKey(seed)))
    dcfg = DataConfig(vocab=CFG.vocab, seq_len=24, global_batch=8)
    return params, dcfg


def test_loss_decreases():
    params, dcfg = _setup()
    opt = AdamWConfig(lr=5e-3, total_steps=150, warmup_steps=10)
    state = TrainState(params=params, opt=adamw_init(params), err=None)
    step = jax.jit(make_train_step(CFG, opt, compute_dtype=jnp.float32))
    losses = []
    for s in range(150):
        batch = jax.tree.map(jnp.asarray, global_batch_for_step(dcfg, s))
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.05


def test_microbatch_grads_match_full_batch():
    params, dcfg = _setup(1)
    batch = jax.tree.map(jnp.asarray, global_batch_for_step(dcfg, 0))
    # f32 compute so accumulation differences stay tiny
    l1, g1 = microbatch_grads(CFG, params, batch, 1, compute_dtype=jnp.float32)
    l4, g4 = microbatch_grads(CFG, params, batch, 4, compute_dtype=jnp.float32)
    assert abs(float(l1) - float(l4)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_wsd_schedule_shape():
    cfg = AdamWConfig(lr=1.0, schedule="wsd", warmup_steps=10,
                      total_steps=100, stable_frac=0.8, min_lr_frac=0.1)
    lrs = [float(wsd_schedule(cfg, jnp.asarray(s))) for s in
           (0, 5, 10, 50, 79, 90, 100)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == lrs[3] == pytest.approx(1.0)   # stable plateau
    assert lrs[4] == pytest.approx(1.0, abs=0.05)
    assert lrs[5] < 1.0
    assert lrs[6] == pytest.approx(0.1, abs=1e-6)   # decayed to min


def test_cosine_schedule_monotone_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=5, total_steps=50)
    lrs = [float(cosine_schedule(cfg, jnp.asarray(s))) for s in range(50)]
    assert lrs[5] == pytest.approx(1.0)
    assert all(a >= b - 1e-9 for a, b in zip(lrs[5:], lrs[6:]))


def test_compressed_dp_step_trains():
    """shard_map int8 error-feedback step runs and reduces loss (1-device
    mesh degenerates gracefully; collective logic is exercised)."""
    params, dcfg = _setup(2)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = AdamWConfig(lr=5e-3, total_steps=120, warmup_steps=10)
    from repro.train.grad_compress import init_error_buf
    state = TrainState(params=params, opt=adamw_init(params),
                       err=init_error_buf(params))
    step = make_compressed_step(CFG, opt, mesh)
    losses = []
    for s in range(120):
        batch = jax.tree.map(jnp.asarray, global_batch_for_step(dcfg, s))
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    # window means: single-step losses are batch-to-batch noise at this size
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02
    # error feedback buffers are being used (non-zero)
    assert any(float(jnp.abs(e).max()) > 0 for e in
               jax.tree.leaves(state.err))
