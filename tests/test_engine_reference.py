"""The serving engine against the benchmark's plain reference, on the CPU.

A tiny dense decoder of each served shape is drawn as packed int4 weights
by ``bench/weights.program_params`` (what the benchmark serves) and served
through ``ContinuousEngine`` with a bf16 cache and chunked prefill, in
admission bursts of several sizes, ragged prompt tails and cached decode.
Every logit vector the engine turns into a token (the first token after
prefill, then each decode step) is held against the logits of the float32
teacher-forced forward of ``bench/reference.py`` at the same position,
which rebuilds the weights from the seed and imports nothing of the
program.  The same tolerances refuse the float8 control
(``reference.fp8``): the reference with every matmul operand rounded
through float8 e4m3.

Errors are in spreads (standard deviations) of the reference logits at
the position, so one tolerance holds at any width.  Readings over three
seeds at both shapes: the engine, the reference with its matmul operands
rounded through bf16, and the float8 control.  The tolerances and their
reasons:

* ``MAX_ERR`` 0.25: the widest error of any logit.  The engine keeps its
  activations, residual stream, cache and attention weights in bf16
  (relative rounding 2^-9), so it reads above the bf16-operand reference
  (0.064–0.085 against 0.049–0.068); float8 e4m3 rounds to 2^-4 and reads
  1.25–2.14.
* ``MEAN_ERR`` 0.03: the error averaged over every served position and
  token (engine 0.0088–0.0105, bf16 operands 0.005–0.007, float8
  0.157–0.186).
* ``MAX_GAP`` 0.2: how far the token the engine serves lies below the
  reference's best (engine 0–0.025, float8 0.40–0.92); the benchmark's
  ``max_gap_sigma`` is the same number over a served sample on the chip.

Each tolerance lies about three times above the engine's widest reading
and at least twice below the control's narrowest, and the control fails
every one.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

SEED = 2**33 + 15

MAX_ERR = 0.25
MEAN_ERR = 0.03
MAX_GAP = 0.2


def _tiny(program_arch, heads, kv, head_dim, d_ff, act, gated):
    return {"name": f"tiny-{program_arch}", "program_arch": program_arch,
            "num_hidden_layers": 4, "hidden_size": 128,
            "num_attention_heads": heads, "num_key_value_heads": kv,
            "head_dim": head_dim, "intermediate_size": d_ff,
            "hidden_act": act, "gated_mlp": gated, "vocab_size": 2000,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
            "serving": {"slots": 4, "max_len": 160, "prefill_chunk": 16},
            "check": {"max_gap_sigma": 0.6}}


#: minitron-8b's block: GQA group 6 with a query width (192) that is not
#: d_model, ungated relu^2; minicpm-2b's: MHA, gated SiLU
SHAPES = {
    "minitron-8b": _tiny("minitron-8b", 12, 2, 16, 512, "relu2", False),
    "minicpm-2b": _tiny("minicpm-2b", 8, 8, 16, 320, "silu", True),
}

#: (prompt length, tokens to serve) in waves: the first arrives at an idle
#: engine (a burst of 3 with ragged tails of 16 and 21), the second while
#: it decodes, the third waits for slots; two of them free at the same step
#: (a burst of 2)
WAVES = [[(16, 6), (32, 14), (37, 10)], [(20, 9)],
         [(16, 8), (48, 5), (17, 9), (33, 7)]]


def _serve(spec, params):
    """Serve ``WAVES``; returns the requests, the burst sizes and, per
    request, {position: the logits the engine took its token from}."""
    import harness
    from repro.serve import Request
    eng = harness.build_engine(spec, params)
    seen = {}
    bursts = []
    state = {"pairs": {}, "recent": None}
    admit, decode, chunk, row, write = (eng._admit_many, eng._decode,
                                        eng._decode_chunk, eng._admit_row,
                                        eng._write_slot)

    def admit_many(pairs, finished):
        bursts.append(len(pairs))
        state["pairs"] = dict(pairs)
        return admit(pairs, finished)

    def decode_step(params, cache, tok):
        # the lockstep decode over every slot (no prompt of WAVES leaves
        # a one-token tail, the other caller)
        rows = [(i, r, len(r.prompt) + len(r.out_tokens) - 1)
                for i, r in enumerate(eng.slots)
                if r is not None and r.out_tokens]
        logits, cache = decode(params, cache, tok)
        for i, r, pos in rows:
            seen[r.rid][pos] = np.asarray(logits[i], np.float32)
        return logits, cache

    def decode_chunk(params, cache, toks):
        logits, cache = chunk(params, cache, toks)
        state["recent"] = logits
        return logits, cache

    def admit_row(sub, logits, i):
        sub_i, log_i = row(sub, logits, i)
        state["recent"] = log_i
        return sub_i, log_i

    def write_slot(cache, sub_i, slot):
        # called once a row's first token is taken from its last logits
        r = state["pairs"][int(slot)]
        seen[r.rid][len(r.prompt) - 1] = np.asarray(state["recent"][0],
                                                    np.float32)
        return write(cache, sub_i, slot)

    eng._admit_many, eng._decode, eng._decode_chunk = (admit_many,
                                                       decode_step,
                                                       decode_chunk)
    eng._admit_row, eng._write_slot = admit_row, write_slot
    rng = np.random.default_rng(SEED % 2**32)
    reqs = []
    for wave in WAVES:
        for plen, new in wave:
            r = Request(rid=len(reqs),
                        prompt=rng.integers(0, spec.vocab, plen,
                                            dtype=np.int32),
                        max_new_tokens=new)
            seen[r.rid] = {}
            reqs.append(r)
            eng.submit(r)
        eng.step()
    eng.run_until_done()
    return reqs, bursts, seen


def _reference_logits(spec, key, reqs, rounding):
    """(B, max_len, vocab) logits of ``rounding``'s teacher-forced forward
    over each request's prompt and served tokens."""
    import reference
    from weights import draw_embed
    tokens = np.zeros((len(reqs), spec.max_len), np.int32)
    for b, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                   np.int32)])
        tokens[b, :len(seq)] = seq
    rnd = reference.ROUNDING[rounding]
    x = reference.hidden(spec, key, tokens, rounding)
    emb = draw_embed(key, spec)[: spec.vocab].astype(jnp.float32)
    h = reference._rms(x, spec.norm_eps)
    return np.asarray(jnp.einsum("btd,vd->btv", rnd(h), rnd(emb),
                                 precision=jax.lax.Precision.HIGHEST))


def _errors(ref, got):
    """Per position: the widest logit error and the mean one, and the gap
    of ``got``'s top token below the reference's best, all in spreads of
    the reference logits there."""
    spread = ref.std(-1)
    err = np.abs(got - ref) / spread[:, None]
    top = got.argmax(-1)
    gap = (ref.max(-1) - ref[np.arange(len(ref)), top]) / spread
    return err.max(-1), err.mean(-1), gap


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_served_logits_match_reference(shape):
    import harness
    from spec import spec_from_dict
    from weights import program_params, seed_key
    spec = spec_from_dict(SHAPES[shape])
    cfg = harness.program_config(spec)
    if shape == "minitron-8b":
        assert cfg.n_heads * cfg.head_dim != cfg.d_model
        assert cfg.n_heads // cfg.n_kv == 6 and not cfg.gated_mlp
    assert spec.vocab % 256
    reqs, bursts, seen = _serve(spec, program_params(spec, SEED))
    assert {1, 2, 3} <= set(bursts), bursts
    key = seed_key(SEED)
    ref = _reference_logits(spec, key, reqs, "f32")
    ctl = _reference_logits(spec, key, reqs, "fp8")
    got, want, low = [], [], []
    for b, r in enumerate(reqs):
        assert r.done and len(r.out_tokens) == r.max_new_tokens
        at = sorted(seen[r.rid])
        # a logit vector for every served token, the token its argmax
        assert at == list(range(len(r.prompt) - 1,
                                len(r.prompt) - 1 + r.max_new_tokens))
        served = np.stack([seen[r.rid][p] for p in at])
        assert list(served.argmax(-1)) == list(r.out_tokens)
        got.append(served)
        want.append(ref[b, at])
        low.append(ctl[b, at])
    got, want, low = (np.concatenate(a) for a in (got, want, low))
    e_max, e_mean, gap = _errors(want, got)
    c_max, c_mean, c_gap = _errors(want, low)
    assert e_max.max() <= MAX_ERR, e_max.max()
    assert e_mean.mean() <= MEAN_ERR, e_mean.mean()
    assert gap.max() <= MAX_GAP, gap.max()
    # the float8 control, held to the same tolerances, fails each of them
    assert c_max.max() > MAX_ERR, c_max.max()
    assert c_mean.mean() > MEAN_ERR, c_mean.mean()
    assert c_gap.max() > MAX_GAP, c_gap.max()
