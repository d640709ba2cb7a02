"""``moe`` under a 2×4 (data, model) mesh ≡ ``moe`` with no mesh, and its
gradient under the mesh is finite.

Runs in a subprocess with 8 forced host devices (jax device count locks at
first init, so the main pytest process can't host this mesh).
"""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.models.layers import moe, moe_init, split_tree

    mesh = make_mesh((2, 4), ("data", "model"))
    E, k, d, ff = 8, 2, 32, 64
    p, _ = split_tree(moe_init(jax.random.PRNGKey(0), d, ff, E))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))

    def run(xx):
        return moe(p, xx, n_experts=E, top_k=k, capacity_factor=8.0)

    base = run(x)
    with use_mesh(mesh):
        sharded = run(x)
        g = jax.grad(lambda xx: run(xx).sum())(x)
    err = float(jnp.abs(base - sharded).max())
    scale = float(jnp.abs(base).max())
    assert err / scale < 1e-4, (err, scale)
    assert bool(jnp.isfinite(g).all())
    print("OK")
""")


def test_moe_under_mesh_matches_no_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=300, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
