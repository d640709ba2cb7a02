"""Tensor-parallel k-sharded serving (DESIGN.md §13).

Eager tests cover the host-side machinery (per-shard planar re-pack
losslessness, escape partitioning, the ordered-chain-sum oracle, and the
sharded storage inventory the bytes gate audits).  The mesh itself runs
in a subprocess with 8 forced host devices (jax device count locks at
first init): a differential fuzz over served formats × staggered
arrivals × device-loss chaos asserting the mesh engine's token streams
are BIT-identical to the single-device oracle over the same sharded
tree, plus the compiled-HLO audit that no weight payload (integer
all-gather) ever crosses devices.
"""
import os
import subprocess
import sys
import textwrap
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import (pack_codes_jnp, shard_pad_cols,
                                shard_planar_codes_jnp, unpack_int2_planar_jnp,
                                unpack_int3_planar_jnp, unpack_int4_planar_jnp)
from repro.models.layers import dense
from repro.quant import (leaf_inventory, quantize_params_tree, qweight_bytes)
from repro.serve import shard_params_tree

_UNPACK = {2: unpack_int2_planar_jnp, 3: unpack_int3_planar_jnp,
           4: unpack_int4_planar_jnp}
_QMAX = {2: 1, 3: 3, 4: 7}


@pytest.mark.parametrize("nbits", [2, 3, 4])
@pytest.mark.parametrize("k,shards", [(32, 8), (30, 8), (17, 4), (64, 2)])
def test_shard_planar_codes_roundtrip(nbits, k, shards):
    """Per-shard re-pack is lossless: unpacking every shard's payload and
    keeping its first k_loc columns reassembles the input codes."""
    rng = np.random.default_rng(nbits * 100 + k)
    z = rng.integers(-_QMAX[nbits], _QMAX[nbits] + 1,
                     (6, k)).astype(np.int8)
    payload = shard_planar_codes_jnp(jnp.asarray(z), shards, nbits=nbits)
    k_loc = -(-k // shards)
    back = np.asarray(_UNPACK[nbits](payload))[..., :k_loc]   # (S, a, k_loc)
    flat = np.concatenate([back[s] for s in range(shards)], axis=-1)[:, :k]
    np.testing.assert_array_equal(flat, z)
    # stored payload bytes match the shard_pad_cols accounting exactly:
    # every shard pays the planar pad for its own k_loc block
    total_cols = k + shard_pad_cols(k, nbits, shards)
    assert payload.size == total_cols * 6 * nbits // 8


def _packed_leaf_with_escapes(rng, n, k, nbits, n_esc):
    """A packed qweight leaf whose codes overflow the clip range at
    ``n_esc`` sites — real escape-COO entries, not zero-capacity pads."""
    qmax = _QMAX[nbits]
    z = rng.integers(-qmax, qmax + 1, (n, k)).astype(np.int32)
    flat = rng.choice(n * k, size=n_esc, replace=False)
    z[np.unravel_index(flat, z.shape)] = qmax + rng.integers(
        1, 4, n_esc)                                  # beyond the clip range
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z), nbits=nbits,
                                         escape_capacity=n_esc + 3)
    return {"codes": payload,
            "s": jnp.asarray(rng.uniform(0.5, 1.5, k), jnp.float32),
            "t": jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32),
            "esc_row": er, "esc_col": ec, "esc_dval": ev}, z


@pytest.mark.parametrize("nbits", [2, 3, 4])
@pytest.mark.parametrize("shards", [3, 8])
def test_sharded_dense_matches_unsharded_with_escapes(nbits, shards):
    """dense() over a k-sharded packed leaf (single-device oracle loop)
    agrees with the unsharded packed path — including escape-COO
    corrections partitioned by owner shard with LOCAL column indices."""
    rng = np.random.default_rng(17 * nbits + shards)
    n, k = 24, 22                                     # ragged: k % shards != 0
    leaf, z = _packed_leaf_with_escapes(rng, n, k, nbits, n_esc=5)
    tree = shard_params_tree({"w": leaf}, shards, min_dim=1)
    assert "kshard" in tree["w"] and tree["w"]["kshard"].shape == ()
    assert int(tree["w"]["s"].shape[-2]) == shards
    x = jnp.asarray(rng.standard_normal((4, k)), jnp.float32)
    want = np.asarray(dense({"w": leaf}, x))
    got = np.asarray(dense(tree, x))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the true (unclipped) code matrix is what both must represent
    ref = (np.asarray(x) * np.asarray(leaf["s"])) @ z.T \
        * np.asarray(leaf["t"])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("wbits", [8, 4, 3, 2])
def test_sharded_inventory_bytes_reconcile(wbits):
    """Mesh-aware leaf_inventory: sharded records carry the shard count,
    their byte fields obey the per-shard pad formulas, and the inventory
    sums exactly to qweight_bytes — the engine-side half of the
    check_bytes/check_mesh reconciliation."""
    import math
    rng = jax.random.PRNGKey(0)
    params = {"layers": {"mlp": {"w": jax.random.normal(rng, (2, 72, 48))}}}
    q = quantize_params_tree(params, min_dim=16, nbits=wbits,
                             packed=(wbits == 4))
    sp = shard_params_tree(q, 8, min_dim=16)
    recs = [r for r in leaf_inventory(sp) if r["format"] != "raw"]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["shards"] == 8
    st, o, i, sh = rec["stack"], rec["out"], rec["in"], rec["shards"]
    assert i % sh == 0 and i == sh * math.ceil(72 / sh)
    formula = {
        "int8": lambda o, i: o * i,
        "packed-int4": lambda o, i: o * math.ceil(i / 2),
        "packed-int3": lambda o, i: o * 3 * math.ceil(i / 8),
        "packed-int2": lambda o, i: o * math.ceil(i / 4)}[rec["format"]]
    assert rec["payload_bytes"] == st * sh * formula(o, i // sh)
    assert rec["scale_bytes"] == st * (i + o) * 4
    assert rec["esc_bytes"] == st * rec["esc_capacity"] * 12
    qb, _ = qweight_bytes(sp)
    other = sum(r["bytes"] for r in leaf_inventory(sp)
                if r["format"] == "raw")
    assert rec["bytes"] + other == qb


def test_shard_skips_small_and_marker_excluded():
    """Leaves narrower than the shard count stay unsharded; the kshard
    marker never shows up in byte accounting."""
    rng = jax.random.PRNGKey(1)
    params = {"small": {"w": jax.random.normal(rng, (4, 48))},
              "big": {"w": jax.random.normal(rng, (64, 48))}}
    q = quantize_params_tree(params, min_dim=4, nbits=3)
    sp = shard_params_tree(q, 8, min_dim=4)
    assert "kshard" in sp["big"]["w"]
    assert "kshard" not in sp["small"]["w"]
    qb_marked, _ = qweight_bytes(sp)
    stripped = {"small": sp["small"],
                "big": {"w": {k: v for k, v in sp["big"]["w"].items()
                              if k != "kshard"}}}
    qb_stripped, _ = qweight_bytes(stripped)
    assert qb_marked == qb_stripped


# ---------------------------------------------------------------------------
# The mesh itself: subprocess with 8 forced host devices
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os, zlib
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro import chaos
    from repro.configs.base import ArchConfig
    from repro.dist.fault import RestartPolicy
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params, split_tree
    from repro.models.transformer import init_cache
    from repro.quant import quantize_params_tree
    from repro.serve import (ContinuousEngine, Request, ResilienceConfig,
                             build_sharded_decode_fns, integer_allgathers,
                             lower_decode_hlo, shard_params_tree)

    CFG = ArchConfig(name="m", family="dense", n_layers=2, d_model=32,
                     n_heads=2, n_kv=2, d_ff=64, vocab=64, head_dim=16)
    MESH = make_host_mesh(model_parallel=8)
    assert int(MESH.shape["model"]) == 8
    params, _ = split_tree(init_params(CFG, jax.random.PRNGKey(0)))

    def mixed_bits(path):
        # deterministic per-leaf format mix (a plan-chosen tree stand-in)
        return [2, 3, 4, 8][zlib.crc32("/".join(path).encode()) % 4]

    TREES = {
        "fp": params,
        "int8": quantize_params_tree(params, min_dim=16),
        "int4": quantize_params_tree(params, nbits=4, packed=True,
                                     min_dim=16),
        "int3": quantize_params_tree(params, nbits=3, min_dim=16),
        "int2": quantize_params_tree(params, nbits=2, min_dim=16),
        "mixed": quantize_params_tree(params, min_dim=16,
                                      nbits_by_path=mixed_bits),
    }

    rng = np.random.default_rng(3)
    # staggered arrivals: (admit-at-step, rid, prompt, budget) — requests
    # land mid-flight so slot churn and co-prefill paths are exercised
    WORK = [(0, 0, rng.integers(0, CFG.vocab, 5).astype(np.int32), 4),
            (0, 1, rng.integers(0, CFG.vocab, 7).astype(np.int32), 3),
            (1, 2, rng.integers(0, CFG.vocab, 4).astype(np.int32), 5),
            (3, 3, rng.integers(0, CFG.vocab, 6).astype(np.int32), 4)]

    def drain(eng):
        done, pending, steps = [], list(WORK), 0
        while pending or eng.queue or eng.active_slots:
            while pending and pending[0][0] <= steps:
                _, rid, prompt, budget = pending.pop(0)
                assert eng.submit(Request(rid=rid, prompt=prompt.copy(),
                                          max_new_tokens=budget))
            done.extend(eng.step())
            steps += 1
            assert steps < 300, "engine failed to drain"
        return {r.rid: tuple(r.out_tokens) for r in done}

    def serve(tree, fns, res=None, plan=None):
        kw = {} if fns is None else {"decode_fn": fns[0],
                                     "decode_chunk_fn": fns[1]}
        eng = ContinuousEngine(CFG, tree, n_slots=2, max_len=16,
                               prefill_chunk=4, resilience=res, **kw)
        if plan is None:
            return drain(eng)
        with chaos.active(plan):
            return drain(eng)

    for name, tree in TREES.items():
        sp = shard_params_tree(tree, 8, min_dim=16)
        fns = build_sharded_decode_fns(CFG, sp, MESH)
        oracle = serve(sp, None)
        meshed = serve(sp, fns)
        assert set(oracle) == {0, 1, 2, 3}
        assert all(oracle.values())
        assert oracle == meshed, (name, oracle, meshed)
        print(name, "bit-identical", flush=True)

    # device-loss chaos mid-stream: the injected fault kills decode
    # dispatches on a seeded schedule; the retry policy replays them and
    # the recovered mesh streams must STILL match the fault-free run
    sp = shard_params_tree(TREES["int3"], 8, min_dim=16)
    fns = build_sharded_decode_fns(CFG, sp, MESH)
    res = ResilienceConfig(retry=RestartPolicy(max_restarts=8,
                                               backoff_base_s=0.0,
                                               reset_after=4))
    clean = serve(sp, fns)
    plan = chaos.seeded_plan("device-loss", seed=0)
    faulted = serve(sp, fns, res=res, plan=plan)
    assert faulted == clean, (faulted, clean)
    print("device-loss recovered bit-identical", flush=True)

    # compiled decode path: fp partial/KV all-gathers only — any integer
    # all-gather means weight payload bytes crossed devices
    cache = init_cache(CFG, 2, 16, jnp.float32, per_slot=True)
    tok = jnp.zeros((2, 1), jnp.int32)
    hlo = lower_decode_hlo(CFG, sp, MESH, cache, tok)
    assert not integer_allgathers(hlo)
    assert any("all-gather" in ln for ln in hlo.splitlines())
    print("hlo audit clean", flush=True)
    print("OK")
""")


def test_mesh_serving_differential_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=580, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
