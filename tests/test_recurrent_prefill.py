"""``prefill`` of the recurrent families (ssm, hybrid) ≡ the full-sequence
training forward, an independent path: the last logits agree, and a decode
step continuing from the prefilled cache agrees, also after the hybrid's
local-attention ring has wrapped during the prompt."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step, forward_train, init_params, split_tree
from repro.models.transformer import prefill

#: the tolerance of test_models_smoke's decode-vs-forward consistency test
TOL = dict(rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ["rwkv6-7b", "recurrentgemma-2b"])
def test_prefill_matches_forward(arch):
    cfg = get_config(arch).reduced()
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    logits, cache = prefill(cfg, params, {"tokens": toks}, max_len=16,
                            cache_dtype=jnp.float32)
    full = forward_train(cfg, params, {"tokens": toks})
    assert int(cache.pos) == 12
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, -1]), **TOL)


def test_prefill_ring_wrap_then_decode_matches_forward():
    """A 13-token prompt through an 8-position local window wraps the ring
    during prefill; the next decode step reads it as the forward does."""
    cfg = dataclasses.replace(get_config("recurrentgemma-2b").reduced(),
                              local_window=8)
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 14), 0, cfg.vocab)
    _, cache = prefill(cfg, params, {"tokens": toks[:, :13]}, max_len=32,
                       cache_dtype=jnp.float32)
    logits, _ = decode_step(cfg, params, cache, toks[:, 13:])
    full = forward_train(cfg, params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, -1]), **TOL)
