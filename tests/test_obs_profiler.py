"""The engine's spans on the profiler's clock, and the names a device trace
needs to charge an op to its program and its layer (DESIGN.md §11).

* Under ``jax.profiler.trace`` with ``repro.obs`` disabled, the continuous
  engine's ``serve.*`` spans land on the profiler's host plane as one
  nested tree per step, and its device ops carry the engine's module
  names.
* The decode step and the prefill chunk carry the layer scopes in their
  ops' ``op_name`` metadata; each Pallas kernel carries its own name.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ArchConfig
from repro.kernels.dequant import ops
from repro.models import (decode_chunk, decode_step, init_cache,
                          init_params, split_tree)
from repro.quant import quantize_params_tree
from repro.serve import ContinuousEngine, EngineConfig, Request

CFG = ArchConfig(name="s", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv=2, d_ff=64, vocab=64, head_dim=16)
PROMPTS = [5, 8, 4, 9]          # a burst of two with a ragged tail, then
MAX_NEW = 3                     # admissions into freed slots
#: the engine's programs, as ``hlo_module`` names them
ENGINE_MODULES = {"jit_serve_decode_step", "jit_serve_prefill_chunk",
                  "jit_serve_init_cache", "jit_serve_admit_row",
                  "jit_cache_write_slot", "jit_cache_reset_slot"}


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.reset()
    yield
    obs.reset()


def _params():
    params, _ = split_tree(init_params(CFG, jax.random.PRNGKey(0)))
    return params


def _engine(params):
    eng = ContinuousEngine(CFG, params, config=EngineConfig(
        n_slots=2, max_len=16, prefill_chunk=4, reset_on_evict=True))
    rng = np.random.default_rng(3)
    for i, n in enumerate(PROMPTS):
        eng.submit(Request(rid=i, prompt=rng.integers(0, CFG.vocab, n)
                           .astype(np.int32), max_new_tokens=MAX_NEW))
    return eng


def _profile(tmp_path):
    """Host spans (name, start, end) and device-op modules of the trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
                stats = dict(ev.stats)
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return spans, modules


def _parent(spans, child):
    """The innermost span that holds ``child`` (its parent in the tree)."""
    _, s, e = child
    holders = [sp for sp in spans if sp is not child
               and sp[1] <= s and e <= sp[2]]
    return min(holders, key=lambda sp: sp[2] - sp[1])[0] if holders \
        else None


def test_engine_spans_nest_on_the_profiler_clock(tmp_path):
    eng = _engine(_params())
    assert not obs.enabled()
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_done()
    spans, modules = _profile(tmp_path)
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[0], []).append(sp)
    assert len(by_name["serve.step"]) == len(eng.step_stats)
    # one sync per decode step, inside its serve.decode inside serve.step
    assert len(by_name["serve.decode.sync"]) == eng.decode_calls
    assert len(by_name["serve.decode"]) == eng.decode_calls
    for name in ("serve.decode.dispatch", "serve.decode.wait",
                 "serve.decode.sync", "serve.decode.commit"):
        assert len(by_name[name]) == eng.decode_calls
        assert {_parent(spans, sp) for sp in by_name[name]} \
            == {"serve.decode"}
    assert {_parent(spans, sp) for sp in by_name["serve.decode"]} \
        == {"serve.step"}
    # admissions: a burst span per admitting step, its children inside it
    admitting = sum(1 for st in eng.step_stats if st.admitted)
    assert len(by_name["serve.admit"]) == admitting
    assert len(by_name["serve.admit.prefill"]) == admitting
    assert {_parent(spans, sp) for sp in by_name["serve.admit"]} \
        == {"serve.step"}
    for name in ("serve.admit.prefill", "serve.admit.tail",
                 "serve.admit.graft", "serve.admit.first_token"):
        assert {_parent(spans, sp) for sp in by_name[name]} \
            == {"serve.admit"}, name
    assert len(by_name["serve.admit.first_token"]) == len(PROMPTS)
    assert by_name["serve.admit.tail"]           # the ragged tail ran
    # the device ops of the serving loop come from the programs the
    # engine named
    assert ENGINE_MODULES <= modules


def test_engine_programs_are_named():
    eng = _engine(_params())
    tok = jnp.zeros((2, 1), jnp.int32)
    sub = eng._init_sub(2)
    logits = jnp.zeros((2, CFG.vocab))
    lowered = [
        eng._decode.lower(eng.params, eng.cache, tok),
        eng._decode_chunk.lower(eng.params, eng.cache, tok),
        eng._init_sub.lower(2),
        eng._admit_row.lower(sub, logits, np.int32(1)),
        eng._write_slot.lower(eng.cache, eng._init_sub(1), np.int32(0)),
        eng._reset_slot.lower(eng.cache, np.int32(0))]
    names = {re.search(r"module @(\w+)", lo.as_text()).group(1)
             for lo in lowered}
    assert names == ENGINE_MODULES


@pytest.mark.parametrize("program", ["decode_step", "decode_chunk"])
def test_step_programs_carry_layer_scopes(program):
    params = quantize_params_tree(_params(), nbits=4, packed=True,
                                  min_dim=16)
    cache = init_cache(CFG, 2, 16, jnp.bfloat16, per_slot=True)
    fn, toks = {"decode_step": (decode_step, jnp.zeros((2, 1), jnp.int32)),
                "decode_chunk": (decode_chunk,
                                 jnp.zeros((2, 4), jnp.int32))}[program]
    text = jax.jit(lambda p, c, t: fn(CFG, p, c, t)).lower(
        params, cache, toks).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]+)"', text))
    for scope in ("attn_proj", "attention", "kv_cache", "mlp", "lm_head",
                  "packed_matmul"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    # the cache write is the kv_cache scope's, the scores the attention's
    assert any("/kv_cache/dynamic_update_slice" in n for n in op_names)
    assert any(re.search(r"/attention/.*dot_general", n) for n in op_names)


@pytest.mark.parametrize("nbits", [4, 3, 2])
def test_packed_kernel_carries_its_name(nbits):
    k, n = 256, 128
    payload = {4: (n, k // 2), 3: (n, 3, k // 8), 2: (n, 1, k // 4)}[nbits]
    text = ops._dequant_matmul_packed.lower(
        jnp.ones((8, k), jnp.bfloat16), jnp.zeros(payload, jnp.uint8),
        jnp.ones((k,), jnp.float32), jnp.ones((n,), jnp.float32),
        nbits=nbits, interpret=True).as_text(debug_info=True)
    assert f"dequant_matmul_packed_int{nbits}/pallas_call" in text
    assert "packed_matmul/" in text
