"""Sharded checkpoint save/restore: atomicity, retention, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.checkpoint import (cleanup_old, latest_step, list_steps,
                                   restore_checkpoint, save_checkpoint)
from repro.launch.mesh import make_mesh


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 16)),
                       "b": jnp.zeros(16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_roundtrip(tmp_path):
    st = _state()
    save_checkpoint(str(tmp_path), 10, st)
    assert latest_step(str(tmp_path)) == 10
    restored, manifest = restore_checkpoint(str(tmp_path), st)
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(st["params"]["w"]))
    assert manifest["step"] == 10


def test_retention_and_latest(tmp_path):
    st = _state()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, st, keep=2)
    assert list_steps(str(tmp_path)) == [4, 5]


def test_atomic_no_partial_read(tmp_path):
    """A stale tmp dir (simulated crash) must not be visible as a ckpt."""
    st = _state()
    save_checkpoint(str(tmp_path), 1, st)
    os.makedirs(tmp_path / "step_00000002.tmp.deadbeef")
    assert latest_step(str(tmp_path)) == 1


def test_restore_missing_leaf_raises(tmp_path):
    st = _state()
    save_checkpoint(str(tmp_path), 1, st)
    bigger = {**st, "extra": jnp.zeros(3)}
    with pytest.raises(KeyError):
        restore_checkpoint(str(tmp_path), bigger)


def test_elastic_restore_new_sharding(tmp_path):
    """Restore onto explicit shardings (the elastic re-shard path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    st = _state()
    save_checkpoint(str(tmp_path), 3, st)
    mesh = make_mesh((1,), ("data",))
    shardings = {"params": {"w": NamedSharding(mesh, P("data", None)),
                            "b": NamedSharding(mesh, P())},
                 "step": NamedSharding(mesh, P())}
    restored, _ = restore_checkpoint(str(tmp_path), st, shardings=shardings)
    assert restored["params"]["w"].sharding.spec == P("data", None)


def test_stale_staging_gc(tmp_path):
    """Crashed writers leak ``.tmp.`` staging dirs; saves and retention
    sweep dirs older than the stale TTL but leave young ones (a live
    concurrent writer) and every committed step alone."""
    st = _state()
    save_checkpoint(str(tmp_path), 1, st)
    stale = tmp_path / "step_00000002.tmp.deadbeef"
    young = tmp_path / "step_00000003.tmp.cafef00d"
    stale.mkdir()
    young.mkdir()
    old = os.path.getmtime(stale) - 2 * 3600.0
    os.utime(stale, (old, old))
    save_checkpoint(str(tmp_path), 4, st)      # save-time sweep
    assert not stale.exists()
    assert young.exists()
    assert list_steps(str(tmp_path)) == [1, 4]
    os.utime(young, (old, old))
    cleanup_old(str(tmp_path), keep=2)         # retention-time sweep
    assert not young.exists()
    assert list_steps(str(tmp_path)) == [1, 4]


def test_cleanup_never_deletes_step_a_reader_holds(tmp_path):
    """Retention must not race a concurrent resume: the step recorded by
    the last manifest read (and everything newer) survives cleanup."""
    st = _state()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, st)
    restore_checkpoint(str(tmp_path), st, step=3)   # reader pins step 3
    removed = cleanup_old(str(tmp_path), keep=1)
    assert removed == [1, 2]
    assert list_steps(str(tmp_path)) == [3, 4, 5]


def test_restore_does_not_pin_to_template_device(tmp_path):
    """A plain jnp/np template's accidental single-device commitment must
    not pin the restored arrays — restores come back uncommitted so the
    first computation (e.g. a shard_map over the serving mesh) lays them
    out, and numpy templates need no special casing."""
    st = _state()
    save_checkpoint(str(tmp_path), 1, st)
    pinned = jax.tree.map(lambda a: jax.device_put(a, jax.devices()[0]), st)
    restored, _ = restore_checkpoint(str(tmp_path), pinned)
    assert not restored["params"]["w"]._committed
    np_template = jax.tree.map(np.asarray, st)
    via_np, _ = restore_checkpoint(str(tmp_path), np_template)
    np.testing.assert_array_equal(np.asarray(via_np["params"]["w"]),
                                  np.asarray(st["params"]["w"]))
    assert isinstance(via_np["params"]["w"], jax.Array)
