"""Observability woven through the engines must be invisible when off
and reconciled when on (DESIGN.md §11).

The contracts under test:

* **off (the default)**: instrumented engines emit byte-identical token
  streams and structurally identical RoundStats vs … themselves — the
  hooks are behind one boolean and record nothing;
* **under a profiler session**: the spans become TraceAnnotations and
  the token streams stay byte-identical;
* **on**: the lifecycle counters/histograms agree with the engines' own
  bookkeeping, and the burst and per-slot spans land in the trace.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import init_params, split_tree
from repro.obs.trace import NULL_SPAN
from repro.serve import ContinuousEngine, Request, ServeEngine

CFG = ArchConfig(name="s", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv=2, d_ff=64, vocab=64, head_dim=16)


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _params(seed=0):
    params, _ = split_tree(init_params(CFG, jax.random.PRNGKey(seed)))
    return params


def _prompts(n=3, plen=5, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, plen).astype(np.int32)
            for _ in range(n)]


def _run(cls, params, prompts, max_new=3, n_slots=2):
    eng = cls(CFG, params, n_slots=n_slots,
              max_len=max(len(p) for p in prompts) + max_new + 2,
              prefill_chunk=4)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=max_new))
    done = eng.run_until_done()
    return eng, {r.rid: tuple(r.out_tokens) for r in done}


def _round_structure(eng):
    return [(st.batch, st.prompt_len, st.prefill_calls, st.decode_calls,
             st.new_tokens) for st in eng.round_stats]


def test_static_engine_identical_with_obs_on_and_off():
    params = _params()
    prompts = _prompts()
    assert not obs.enabled()                  # REPRO_OBS defaults off
    eng_off, out_off = _run(ServeEngine, params, prompts)
    obs.enable()
    eng_on, out_on = _run(ServeEngine, params, prompts)
    assert out_on == out_off                  # byte-identical streams
    assert _round_structure(eng_on) == _round_structure(eng_off)
    # and the enabled run actually recorded the lifecycle
    snap = obs.counters_snapshot("repro_serve_")
    assert snap['repro_serve_finished_total{engine="static"}'] == len(prompts)


def test_continuous_engine_identical_with_obs_on_and_off():
    params = _params()
    prompts = _prompts(n=4, seed=5)
    eng_off, out_off = _run(ContinuousEngine, params, prompts)
    obs.enable()
    eng_on, out_on = _run(ContinuousEngine, params, prompts)
    assert out_on == out_off
    assert eng_on.prefill_calls == eng_off.prefill_calls
    assert len(eng_on.step_stats) == len(eng_off.step_stats)


@pytest.mark.parametrize("cls", [ServeEngine, ContinuousEngine])
def test_engine_identical_under_a_profiler_session(cls, tmp_path):
    """With obs off but a profiler collecting, every span enters a
    TraceAnnotation; the streams and the dispatch counts stay the same."""
    params = _params()
    prompts = _prompts(n=4, seed=5)
    eng_off, out_off = _run(cls, params, prompts)
    with jax.profiler.trace(str(tmp_path)):
        assert obs.span("serve.x") is not NULL_SPAN
        eng_on, out_on = _run(cls, params, prompts)
    assert obs.span("serve.x") is NULL_SPAN
    assert out_on == out_off
    if cls is ContinuousEngine:
        assert eng_on.prefill_calls == eng_off.prefill_calls
        assert len(eng_on.step_stats) == len(eng_off.step_stats)
    else:
        assert _round_structure(eng_on) == _round_structure(eng_off)
    assert not obs.tracer().to_chrome()["traceEvents"]


def test_continuous_counters_spans_and_slot_lanes():
    obs.enable()
    params = _params()
    prompts = _prompts(n=5, seed=7)
    eng, out = _run(ContinuousEngine, params, prompts, n_slots=2)
    assert len(out) == 5
    snap = obs.counters_snapshot("repro_serve_")
    assert snap['repro_serve_admitted_total{engine="continuous"}'] == 5
    assert snap['repro_serve_finished_total{engine="continuous"}'] == 5
    assert snap["repro_serve_evicted_total"] == 5
    ttft = obs.registry().histogram("repro_serve_ttft_seconds",
                                    engine="continuous")
    assert ttft.count == 5 and ttft.min > 0
    events = obs.tracer().to_chrome()["traceEvents"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    # every admission got a per-slot lane (tid == slot) and both slots of
    # this 2-slot engine saw admit + decode work; the bursts together
    # admitted all five
    firsts = by_name["serve.admit.first_token"]
    assert len(firsts) == 5
    assert all(e["tid"] == e["args"]["slot"] for e in firsts)
    assert {e["args"]["slot"] for e in firsts} == {0, 1}
    admits = by_name["serve.admit"]
    assert sum(e["args"]["g"] for e in admits) == 5
    assert {s for e in admits for s in e["args"]["slots"]} == {0, 1}
    decode_slots = {s for e in by_name["serve.decode"]
                    for s in e["args"]["slots"]}
    assert decode_slots == {0, 1}
    assert "serve.prefill" in by_name and "serve.step" in by_name
    # the burst prefill is recorded under its own name and its alias
    assert len(by_name["serve.admit.prefill"]) == len(admits)
    assert len(by_name["serve.request.arrival"]) == 5
    assert len(by_name["serve.request.first_token"]) == 5


def test_tokens_counter_matches_emitted_tokens():
    obs.enable()
    params = _params()
    _, out = _run(ContinuousEngine, params, _prompts(n=4, seed=9),
                  max_new=4)
    total = sum(len(t) for t in out.values())
    snap = obs.counters_snapshot("repro_serve_tokens_total")
    assert snap['repro_serve_tokens_total{engine="continuous"}'] == total
