"""CPU tests of the benchmark's own code (not part of the repo's tier-1
suite, whose ``testpaths`` is ``tests``).  Run them from the checkout:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: a small dense decoder of the same kind as the configurations, for CPU
#: runs: 4 layers, GQA, gated SiLU, vocab 2000
TINY = {"name": "tiny", "program_arch": "minicpm-2b", "num_hidden_layers": 4,
        "hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
        "hidden_act": "silu", "gated_mlp": True, "vocab_size": 2000,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "serving": {"slots": 4, "max_len": 160, "prefill_chunk": 16},
        "check": {"max_gap_sigma": 1.0}}

TINY_CHAT = {"loop": "open", "rate_per_s": 6.0,
             "prompt": {"dist": "lognormal", "median": 32, "sigma": 0.6,
                        "min": 16, "max": 96, "multiple": 16},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                        "min": 4, "max": 32},
             "check_requests": 4}

#: long answers, as in the chip's decode mix, so that the check compares
#: some hundreds of tokens
TINY_DECODE = {"loop": "closed", "block": 4,
               "prompt": {"dist": "choice", "values": [16, 32]},
               "output": {"dist": "uniform", "min": 64, "max": 112},
               "check_requests": 4}


def config_limits():
    """Each configuration's correctness limit, by name."""
    from spec import load_spec
    return {p.stem: load_spec(p.stem).max_gap_sigma
            for p in sorted((BENCH / "configs").glob("*.json"))}


@pytest.fixture(scope="session")
def tiny_spec():
    """TINY held to the tightest limit of the configurations."""
    import dataclasses
    from spec import spec_from_dict
    return dataclasses.replace(spec_from_dict(TINY),
                               max_gap_sigma=min(config_limits().values()))


def serve_tiny(spec, mix, seed, seconds=3.0):
    """Serve ``mix`` on the CPU through the harness; returns the window and
    the finished requests."""
    import harness
    from weights import program_params
    clock = harness.CompileClock()
    eng = harness.build_engine(spec, program_params(spec, seed))
    rec = harness.Recorder(eng)
    harness.warm(eng, spec)
    w = harness.drive(eng, rec, spec, mix, seed, seconds, clock)
    return w, [r for r in w.arrived if r.done]


# Faults of the timed path, each a function of the engine that breaks its
# decode step underneath the harness.

def alter_tokens(eng):
    """A token altered where it is produced: every decode step's top token
    is pushed to its neighbour."""
    import jax
    import jax.numpy as jnp
    decode = eng._decode

    def altered(params, cache, tok):
        logits, cache = decode(params, cache, tok)
        top = jnp.argmax(logits, -1)
        bump = jax.nn.one_hot((top + 1) % logits.shape[-1], logits.shape[-1],
                              dtype=logits.dtype) * 1e4
        return logits + bump, cache
    eng._decode = altered


def stale_cache(eng):
    """A step that returns its state unchanged: the decode step hands back
    the cache it was given."""
    decode = eng._decode

    def stale(params, cache, tok):
        logits, _ = decode(params, cache, tok)
        return logits, cache
    eng._decode = stale


def half_batch(eng):
    """Half of the batch left out: the decode step answers the second half
    of the slots with the first half's logits."""
    import jax.numpy as jnp
    decode = eng._decode

    def halved(params, cache, tok):
        logits, cache = decode(params, cache, tok)
        n = logits.shape[0]
        return jnp.concatenate([logits[:n - n // 2], logits[:n // 2]]), cache
    eng._decode = halved


def control_answers(spec, seed, reqs):
    """The control in the program's place: each request's answer rewritten
    as the reference in float8 decodes it greedily from the prompt, as
    many tokens as the program served."""
    import reference
    from weights import seed_key
    key = seed_key(seed)
    tokens = np.zeros((len(reqs), spec.max_len), np.int32)
    for b, r in enumerate(reqs):
        tokens[b, :len(r.prompt)] = r.prompt
    answers = [[] for _ in reqs]
    none = np.full(tokens.shape, -1, np.int32)
    for k in range(max(len(r.out_tokens) for r in reqs)):
        x = reference.hidden(spec, key, tokens, "fp8")
        *_, top = reference.head(spec, key, x, none, "fp8")
        for b, r in enumerate(reqs):
            at = len(r.prompt) + k
            if k < len(r.out_tokens):
                answers[b].append(int(top[b, at - 1]))
                if at < spec.max_len:
                    tokens[b, at] = answers[b][-1]
    for r, a in zip(reqs, answers):
        r.out_tokens = a
