"""The serving driver's own entry point, and the compile-cache helper every
entry point calls first."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache, serve
from repro.launch.compile_cache import enable_compile_cache


@pytest.fixture
def no_cache_change(monkeypatch, tmp_path):
    """Run an entry point without switching this process's persistent
    compilation cache on: with the variable set the helper changes
    nothing (JAX read the variable at import, when it was unset)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    yield
    assert jax.config.jax_compilation_cache_dir == before


def test_serve_main_continuous_int4_under_mesh(no_cache_change):
    """``serve.main`` builds its engine inside ``use_mesh(make_host_mesh())``,
    so every ``logical_shard`` applies a sharding constraint — the path
    engine-level tests (built without a mesh) never reach."""
    eng, done = serve.main(["--arch", "minicpm-2b", "--reduced",
                            "--continuous", "--wbits", "4"])
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert all(len(r.out_tokens) == 8 for r in done)
    assert eng.weight_formats.get("packed-int4", 0) > 0


def test_serve_main_weights_and_cache_are_bf16(no_cache_change):
    """The 16-bit rung serves bf16 weights and a bf16 KV cache."""
    eng, done = serve.main(["--arch", "minicpm-2b", "--reduced",
                            "--continuous", "--requests", "2",
                            "--max-new", "3"])
    mats = [eng.params["layers"]["attn"]["wq"]["w"],
            eng.params["layers"]["mlp"]["w_out"]["w"],
            eng.params["embed"]["w"]]
    assert all(m.dtype == jnp.bfloat16 for m in mats)
    assert eng.params["layers"]["ln_attn"]["scale"].dtype == jnp.float32
    assert eng.cache.kv.k.dtype == jnp.bfloat16
    assert all(len(r.out_tokens) == 3 for r in done)
    logits = eng.prefill_logits(np.stack([r.prompt for r in done]))
    assert logits.shape == (2, eng.cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


def test_compile_cache_respects_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.default_cache_dir().parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").is_file()
