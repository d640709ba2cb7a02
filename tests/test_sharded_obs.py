"""Mesh serving observability parity (DESIGN.md §14, satellite of §13).

The sharded decode path must honor the same one-boolean contract as the
single-device engines: with obs disabled the mesh engine's token
streams are byte-identical to an obs-enabled run (the instrumentation
records, never steers), and with obs enabled the mesh-specific
``serve.mesh.compile`` spans and ``repro_serve_mesh_*`` counters land —
one compile per (tag, shape) cache miss, one dispatch count matching
the engine's own device-call bookkeeping.

Runs in a subprocess with 8 forced host devices (the jax device count
locks at first init), mirroring tests/test_mesh_serving.py.
"""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro import obs
    from repro.configs.base import ArchConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params, split_tree
    from repro.quant import quantize_params_tree
    from repro.serve import (ContinuousEngine, Request,
                             build_sharded_decode_fns, shard_params_tree)

    CFG = ArchConfig(name="m", family="dense", n_layers=2, d_model=32,
                     n_heads=2, n_kv=2, d_ff=64, vocab=64, head_dim=16)
    MESH = make_host_mesh(model_parallel=8)
    params, _ = split_tree(init_params(CFG, jax.random.PRNGKey(0)))
    sp = shard_params_tree(
        quantize_params_tree(params, nbits=4, packed=True, min_dim=16),
        8, min_dim=16)
    rng = np.random.default_rng(3)
    PROMPTS = [rng.integers(0, CFG.vocab, p).astype(np.int32)
               for p in (5, 7, 4)]

    def serve():
        # fresh decode fns per run: the compile cache is per-call-site,
        # so each run pays (and, when enabled, records) its own misses
        fns = build_sharded_decode_fns(CFG, sp, MESH)
        eng = ContinuousEngine(CFG, sp, n_slots=2, max_len=14,
                               prefill_chunk=4, decode_fn=fns[0],
                               decode_chunk_fn=fns[1])
        for i, p in enumerate(PROMPTS):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=4))
        done = eng.run_until_done()
        return {r.rid: tuple(r.out_tokens) for r in done}, eng

    assert not obs.enabled()
    out_off, eng_off = serve()
    obs.enable()
    out_on, eng_on = serve()
    assert out_on == out_off, (out_on, out_off)
    assert len(eng_on.step_stats) == len(eng_off.step_stats)
    print("mesh streams identical obs on/off", flush=True)

    snap = obs.counters_snapshot("repro_serve_mesh_")
    compiles = {k: v for k, v in snap.items()
                if k.startswith("repro_serve_mesh_compile_total")}
    dispatches = {k: v for k, v in snap.items()
                  if k.startswith("repro_serve_mesh_dispatch_total")}
    assert compiles, snap
    assert 'repro_serve_mesh_compile_total{tag="step"}' in compiles
    assert sum(dispatches.values()) >= sum(compiles.values())
    # single-device metric parity: the mesh run feeds the same lifecycle
    # surface the engines already export
    life = obs.counters_snapshot("repro_serve_finished_total")
    assert life['repro_serve_finished_total{engine="continuous"}'] == 3
    spans = [e for e in obs.tracer().to_chrome()["traceEvents"]
             if e["name"] == "serve.mesh.compile"]
    assert len(spans) == sum(int(v) for v in compiles.values())
    for e in spans:
        assert e["ph"] == "X" and e["args"]["shards"] == 8
        assert e["args"]["tag"] in ("step", "chunk")
    print("mesh compile spans + counters present", flush=True)
    print("OK")
""")


def test_sharded_obs_parity_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("REPRO_OBS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=580, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
