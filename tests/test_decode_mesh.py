"""Decode under a 2×4 (data, model) mesh ≡ decode with no mesh when the kv
heads do not divide the model axis (n_kv=2 on a 4-way axis): the cache is
replicated over "model" and four steps give the same logits (subprocess,
8 forced host devices)."""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import contextlib
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.dist.sharding import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.models import decode_step, init_cache, init_params, split_tree

    cfg = get_config("qwen2.5-32b").reduced()
    # kv=2 does not divide model=4
    cfg = dataclasses.replace(cfg, n_kv=2, n_heads=4)
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    mesh = make_mesh((2, 4), ("data", "model"))
    toks = [jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 1)), jnp.int32) for _ in range(4)]

    def run(ctx):
        cache = init_cache(cfg, 2, 8, jnp.float32)
        outs = []
        with ctx:
            step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
            for t in toks:
                logits, cache = step(params, cache, t)
                outs.append(np.asarray(logits))
        return np.stack(outs), cache

    base, _ = run(contextlib.nullcontext())
    sharded, cache = run(use_mesh(mesh))
    k = cache.kv.k
    # every shard holds all kv-head rows and every position
    assert all(s.data.shape[2:] == k.shape[2:] for s in k.addressable_shards)
    err = np.abs(base - sharded).max() / (np.abs(base).max() + 1e-9)
    assert err < 1e-4, err
    print("OK")
""")


def test_decode_under_mesh_matches_no_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=400, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
