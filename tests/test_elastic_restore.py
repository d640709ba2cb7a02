"""Elastic scaling: a checkpoint written under one device layout restores
onto a DIFFERENT mesh (8 devices, 2×4) with explicit shardings — the
restart-on-resized-cluster path (subprocess: forced host device count)."""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.checkpoint import restore_checkpoint, save_checkpoint
    from repro.configs import get_config
    from repro.models import init_params, split_tree
    from repro.models.transformer import param_specs_tree
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_mesh

    cfg = get_config("minicpm-2b").reduced()
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    d = tempfile.mkdtemp()
    save_checkpoint(d, 7, params)          # written replicated (1-dev view)

    # restore onto the 2x4 mesh with the model's real FSDP x TP shardings
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        px = init_params(cfg, jax.random.PRNGKey(0))
        _, specs = param_specs_tree(px)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: not isinstance(x, dict))
        restored, manifest = restore_checkpoint(d, params,
                                                shardings=shardings)
    assert manifest["step"] == 7
    # values identical, now distributed
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    some = [x for x in jax.tree.leaves(restored) if x.ndim >= 2][0]
    assert len(some.sharding.device_set) > 1   # actually sharded
    print("OK")
""")


def test_elastic_restore_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=400, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
