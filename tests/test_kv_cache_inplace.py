"""The stacked KV cache is updated in place, by layer index.

``decode_step`` carries the layer-stacked cache through its layer scan and
``attention_decode`` writes each token at (layer, row, slot) of the stack;
the engine's jitted serving programs donate the cache, so the update
aliases the caller's buffer.  These tests hold the carried formulation to
the one it replaced (each layer's K/V sliced out of the stack, updated,
and written back), the token write to the writes it replaced, the
programs to their donation, and a retried decode to its fault-free
stream.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import chaos
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.dist.fault import RestartPolicy
from repro.models import decode_step, init_cache, init_params, split_tree
from repro.models import layers, transformer
from repro.serve import (ContinuousEngine, EngineConfig, Request,
                         ResilienceConfig)
from repro.serve.engine import _serving_programs

CFG = ArchConfig(name="kvip", family="dense", n_layers=3, d_model=32,
                 n_heads=4, n_kv=2, d_ff=64, vocab=64, head_dim=16)
CFG_RING = dataclasses.replace(CFG, name="kvip-ring", local_window=6)
CFG_HYB = ArchConfig(name="kvip-hyb", family="hybrid", n_layers=6,
                     d_model=32, n_heads=2, n_kv=1, d_ff=64, vocab=64,
                     head_dim=16, block_pattern=("rec", "attn", "attn"),
                     local_window=6, lru_width=32, conv_width=4,
                     activation="gelu", gated_mlp=True, embed_scale=True,
                     subquadratic=True)
CFG_ENCDEC = get_config("whisper-base").reduced()

#: cache kind → (config, rows, cache positions, per-slot, start positions,
#: steps).  Row 1 of ``per_slot`` starts past its buffer, as an idle
#: serving slot does: its writes are dropped.  The ring kinds wrap.
KINDS = {
    "per_slot": (CFG, 2, 8, True, (0, 9), 5),
    "lockstep": (CFG, 2, 8, False, 2, 5),
    "ring": (CFG_RING, 2, 32, True, (0, 4), 9),
    "hybrid": (CFG_HYB, 2, 32, True, (0, 3), 8),
    "encdec": (CFG_ENCDEC, 2, 8, True, (0, 2), 4),
}


@functools.lru_cache(maxsize=None)
def _params(cfg):
    return split_tree(init_params(cfg, jax.random.PRNGKey(0)))[0]


def _layer_sliced(attention_decode):
    """``attention_decode`` as the xs/ys layer scan ran it: layer
    ``layer``'s K/V taken out of the stack as a cache of its own, updated,
    and written back whole."""
    def call(p, x_t, kv, layer, pos, **kw):
        one = jax.tree.map(
            lambda t: jax.lax.dynamic_slice_in_dim(t, layer, 1), kv)
        out, one = attention_decode(p, x_t, one, 0, pos, **kw)
        kv = jax.tree.map(
            lambda t, u: jax.lax.dynamic_update_slice_in_dim(t, u, layer, 0),
            kv, one)
        return out, kv
    return call


def _decode_run(cfg, rows, max_len, per_slot, start, steps):
    cache = init_cache(cfg, rows, max_len, jnp.float32, per_slot=per_slot)
    cache = cache._replace(pos=jnp.asarray(start, jnp.int32))
    step = jax.jit(functools.partial(decode_step, cfg))
    toks = np.random.default_rng(7).integers(0, cfg.vocab, (steps, rows, 1))
    logits = []
    for tok in toks:
        out, cache = step(_params(cfg), cache, jnp.asarray(tok, jnp.int32))
        logits.append(np.asarray(out))
    return logits, cache


@pytest.mark.parametrize("kind", list(KINDS))
def test_carried_cache_matches_layer_sliced_scan(kind, monkeypatch):
    cfg, rows, max_len, per_slot, start, steps = KINDS[kind]
    run = functools.partial(_decode_run, cfg, rows, max_len, per_slot,
                            start, steps)
    logits, cache = run()
    monkeypatch.setattr(transformer, "attention_decode",
                        _layer_sliced(layers.attention_decode))
    ref_logits, ref_cache = run()
    for a, b in zip(logits, ref_logits):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(ref_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#: (buffer length, slot or per-row slots): rows at either end, past the
#: end, before the start, and a buffer shorter than the write window
PUT_CASES = [(16, (0, 15, 7)), (16, (16, 3, -1)), (5, (4, 0, 2)),
             (16, 0), (16, 15), (16, 6), (3, 2)]


@pytest.mark.parametrize("buf,slot", PUT_CASES)
def test_put_token_writes_as_scatter_and_update_slice(buf, slot):
    """Per-row slots write as the scatter they replaced (an out-of-range
    row dropped); a scalar slot as ``dynamic_update_slice`` at it."""
    rng = np.random.default_rng(buf)
    # 4 heads of 4, two to a row of 8
    big = jnp.asarray(rng.normal(size=(3, 3, 2, buf, 8)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(3, 1, 4, 4)), jnp.float32)
    rows = new.reshape(3, 2, 1, 8)
    layer = jnp.int32(1)
    got = layers._put_token(big, new, layer, jnp.asarray(slot, jnp.int32))
    if np.ndim(slot):
        want = np.array(big)
        for r, s in enumerate(slot):
            if 0 <= s < buf:
                want[1, r, :, s] = rows[r, :, 0]
    else:
        want = jax.lax.dynamic_update_slice(big, rows[None],
                                            (1, 0, 0, slot, 0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_serving_programs_donate_the_cache(program):
    step, chunk = _serving_programs(CFG)
    per_slot = program == "decode_step"
    fn, toks = {"decode_step": (step, jnp.zeros((2, 1), jnp.int32)),
                "prefill_chunk": (chunk, jnp.zeros((2, 4), jnp.int32))}[
                    program]
    cache = init_cache(CFG, 2, 16, jnp.bfloat16, per_slot=per_slot)
    logits, out = fn(_params(CFG), cache, toks)
    jax.block_until_ready(logits)
    assert all(x.is_deleted() for x in jax.tree.leaves(cache))
    assert not any(x.is_deleted() for x in jax.tree.leaves(out))


def _serve(plan=None):
    res = ResilienceConfig(
        retry=RestartPolicy(max_restarts=8, backoff_base_s=1e-4,
                            backoff_max_s=1e-3, reset_after=2),
        retry_sleep=lambda s: None)
    eng = ContinuousEngine(CFG, _params(CFG), config=EngineConfig(
        n_slots=2, max_len=32, prefill_chunk=3, resilience=res))
    rng = np.random.default_rng(3)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=rng.integers(0, CFG.vocab, 5)
                           .astype(np.int32), max_new_tokens=4))
    if plan is None:
        return {r.rid: tuple(r.out_tokens) for r in eng.run_until_done()}, 0
    with chaos.active(plan) as rt:
        done = eng.run_until_done()
    return {r.rid: tuple(r.out_tokens) for r in done}, rt.injected()


def test_decode_retry_after_fault_with_donated_cache():
    """The fault fires before the donating call, so the retry reads the
    cache the failed attempt left alive and the streams do not move."""
    baseline, _ = _serve()
    plan = chaos.ChaosPlan(seed=0, specs=(
        chaos.FaultSpec(kind="device-loss", site="serve.decode",
                        at=(1, 2, 5)),))
    faulted, injected = _serve(plan)
    assert injected == 3
    assert faulted == baseline
