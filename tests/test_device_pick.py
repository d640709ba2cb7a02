"""The continuous engine's greedy pick on the device (``serve_greedy``).

* ``serve_greedy`` gives numpy's argmax, first index among equal maxima,
  on bf16 logits with ties and a vocab that is not a multiple of 128.
* The continuous engine's streams equal the static engine's, which keeps
  its host argmax, for mixed prompt lengths and budgets with chunked
  prefill.
* Only the (B,) int32 ids reach the host: the host-bytes counter rises by
  ``n_slots * 4`` per decode round and by 4 per admitted request.
* ``EngineConfig.decode_fn`` still returns logits, and a decode round calls
  it exactly once; the pick is composed after it.
"""
import functools
import re

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import decode_step, init_params, split_tree
from repro.quant import quantize_params_tree
from repro.serve import ContinuousEngine, EngineConfig, Request, ServeEngine
from repro.serve.engine import serve_greedy

CFG = ArchConfig(name="dp", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv=2, d_ff=64, vocab=250, head_dim=16)
CHUNK = 3


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@functools.lru_cache(maxsize=None)
def _tree(fmt):
    base, _ = split_tree(init_params(CFG, jax.random.PRNGKey(0)))
    if fmt == "bf16":
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), base)
    assert fmt == "int4_packed"
    return quantize_params_tree(base, nbits=4, packed=True, min_dim=16)


def _config(tree, **kw):
    bf16 = any(getattr(x, "dtype", None) == jnp.bfloat16
               for x in jax.tree.leaves(tree))
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("max_len", 24)
    return EngineConfig(cache_dtype=jnp.bfloat16 if bf16 else jnp.float32,
                        **kw)


def _tied_logits(rows, vocab, seed):
    """bf16 logits in which most rows tie their maximum at several indices,
    the first and last index among them, and one row is constant."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, vocab)).astype(ml_dtypes.bfloat16)
    for r in range(rows):
        if r % 4 == 3:
            x[r] = 0                                   # every index ties
            continue
        top = x[r].max() + ml_dtypes.bfloat16(1)
        at = rng.choice(vocab, size=3, replace=False)
        if r % 4 == 1:
            at[0] = 0
        if r % 4 == 2:
            at[0] = vocab - 1
        x[r, at] = top
    return x


@pytest.mark.parametrize("rows", [1, 8, 16])
@pytest.mark.parametrize("vocab", [250, 1000, 4099])
def test_serve_greedy_is_numpy_argmax_on_bf16_ties(rows, vocab):
    x = _tied_logits(rows, vocab, seed=rows * vocab)
    ids = serve_greedy(jnp.asarray(x))
    assert ids.dtype == jnp.int32 and ids.shape == (rows,)
    np.testing.assert_array_equal(np.asarray(ids), np.argmax(x, axis=-1))


def _workload(seed):
    """(prompt, budget) pairs: mixed prompt lengths, so that bursts leave
    ragged tails, and mixed budgets."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, CFG.vocab, int(rng.integers(2, 9)),
                          dtype=np.int32), int(rng.integers(1, 7)))
            for _ in range(int(rng.integers(5, 8)))]


def _serve(cls, tree, workload, **kw):
    eng = cls(CFG, tree, config=_config(tree, **kw))
    for rid, (prompt, budget) in enumerate(workload):
        eng.submit(Request(rid=rid, prompt=prompt.copy(),
                           max_new_tokens=budget))
    done = eng.run_until_done()
    assert len(done) == len(workload)
    return {r.rid: tuple(r.out_tokens) for r in done}, eng


@pytest.mark.parametrize("seed", [3, 4])
@pytest.mark.parametrize("fmt", ["bf16", "int4_packed"])
def test_continuous_device_pick_matches_static_host_argmax(fmt, seed):
    tree = _tree(fmt)
    workload = _workload(seed)
    ref, _ = _serve(ServeEngine, tree, workload, n_slots=4)
    out, eng = _serve(ContinuousEngine, tree, workload, n_slots=3)
    assert out == ref
    assert eng.decode_calls > 0 and eng.prefill_calls > 0


def _host_bytes(site):
    return obs.counters_snapshot("repro_serve_host_bytes_total").get(
        f'repro_serve_host_bytes_total{{engine="continuous",site="{site}"}}',
        0.0)


def test_host_bytes_are_the_ids_alone():
    obs.enable()
    n_slots = 3
    tree = _tree("bf16")
    eng = ContinuousEngine(CFG, tree, config=_config(tree, n_slots=n_slots))
    workload = _workload(5)
    for rid, (prompt, budget) in enumerate(workload):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=budget))
    while eng.queue or eng.active_slots:
        calls, sent = eng.decode_calls, _host_bytes("decode")
        eng.step()
        rounds = eng.decode_calls - calls
        assert rounds in (0, 1)
        assert _host_bytes("decode") - sent == rounds * n_slots * 4
    assert _host_bytes("first_token") == len(workload) * 4


def test_decode_fn_returns_logits_and_runs_once_per_round():
    """A Python-level ``decode_fn`` (as ``bench.harness.engine_jits`` lends
    and the sharded engine injects) sees one call per decode round and hands
    back logits; whole-chunk prompts keep it out of prefill."""
    tree = _tree("bf16")
    step = jax.jit(functools.partial(decode_step, CFG))
    calls = []

    def decode_fn(params, cache, tok):
        logits, cache = step(params, cache, tok)
        calls.append(logits.shape)
        return logits, cache

    eng = ContinuousEngine(CFG, tree, config=_config(
        tree, n_slots=2, decode_fn=decode_fn))
    rng = np.random.default_rng(6)
    budgets = [4, 2, 5]
    for rid, budget in enumerate(budgets):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, CFG.vocab, 2 * CHUNK, dtype=np.int32), max_new_tokens=budget))
    eng.run_until_done()
    assert eng.decode_calls > 0
    assert len(calls) == eng.decode_calls
    assert set(calls) == {(2, CFG.vocab)}
    assert sorted(len(r.out_tokens) for r in eng.finished) == sorted(budgets)


def test_serve_greedy_ops_carry_the_lm_head_scope():
    text = serve_greedy.lower(jnp.zeros((4, CFG.vocab), jnp.bfloat16)) \
        .compile().as_text()
    assert "jit_serve_greedy" in text
    # the program's own ops (the reducer's comparators carry bare names)
    paths = {n for n in re.findall(r'op_name="([^"]+)"', text)
             if n.startswith("jit(")}
    assert paths and all("lm_head" in n.split("/") for n in paths), paths
