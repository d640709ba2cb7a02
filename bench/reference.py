"""Plain reference of the dense decoder the configurations describe.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
teacher-forced over whole sequences: embedding, then per layer RMSNorm,
causal attention with rotary positions (half-split rotation), residual,
RMSNorm, MLP (gated SiLU or ungated relu^2), residual; final RMSNorm and
the tied LM head.  No cache, no kernels, no batching tricks.  Each layer's
weights are rebuilt from the seed by ``weights.draw_matrix`` inside the
layer's own jitted call, so the reference takes nothing the program made.

``round_fn`` is where the control lowers the precision: it is applied to
every operand of every matmul (activations times column scales, queries,
keys, values, attention weights, the final hidden state and the embedding).
The reference passes the identity; the control passes a round trip
through float8_e4m3fn, the precision below the bfloat16 that the
configurations state for activations and the cache.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from spec import ModelSpec
from weights import draw_embed, draw_matrix

HI = jax.lax.Precision.HIGHEST


def identity(a):
    return a


def fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


ROUNDING = {"f32": identity, "fp8": fp8}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, :, None].astype(jnp.float32) * freqs       # (B, T, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _linear(x, codes, s, t, rnd):
    """x @ W with W[i, o] = s[i] * codes[o, i] * t[o]."""
    xs = rnd(x * s)
    return jnp.einsum("...i,oi->...o", xs, codes.astype(jnp.float32),
                      precision=HI) * t


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(spec: ModelSpec, rounding: str, key, layer, x):
    rnd = ROUNDING[rounding]
    names = [m.name for m in spec.matrices()]
    w = {n: draw_matrix(key, spec, i, layer) for i, n in enumerate(names)}
    b, t_len, _ = x.shape
    hd, nq, nkv = spec.head_dim, spec.n_heads, spec.n_kv
    pos = jnp.broadcast_to(jnp.arange(t_len), (b, t_len))
    h = _rms(x, spec.norm_eps)
    q = _linear(h, *w["wq"], rnd).reshape(b, t_len, nq, hd)
    k = _linear(h, *w["wk"], rnd).reshape(b, t_len, nkv, hd)
    v = _linear(h, *w["wv"], rnd).reshape(b, t_len, nkv, hd)
    q, k = _rope(q, pos, spec.rope_theta), _rope(k, pos, spec.rope_theta)
    g = nq // nkv
    qg = rnd(q).reshape(b, t_len, nkv, g, hd)
    scores = jnp.einsum("bsngh,btnh->bngst", qg, rnd(k),
                        precision=HI) / math.sqrt(hd)
    causal = jnp.arange(t_len)[None, :] <= jnp.arange(t_len)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bngst,btnh->bsngh", rnd(probs), rnd(v), precision=HI)
    x = x + _linear(att.reshape(b, t_len, nq * hd), *w["wo"], rnd)
    h = _rms(x, spec.norm_eps)
    if spec.gated_mlp:
        u = jax.nn.silu(_linear(h, *w["w_gate"], rnd)) \
            * _linear(h, *w["w_up"], rnd)
    else:
        u = jnp.square(jax.nn.relu(_linear(h, *w["w_in"], rnd)))
    return x + _linear(u, *w["w_out"], rnd)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(spec: ModelSpec, key, tokens):
    return jnp.take(draw_embed(key, spec).astype(jnp.float32), tokens, axis=0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(spec: ModelSpec, rounding: str, key, x, targets):
    """Per position: the best logit, the target's logit, the logits' spread
    and the position's own top token, for one sequence (T, d)."""
    rnd = ROUNDING[rounding]
    emb = draw_embed(key, spec)[: spec.vocab].astype(jnp.float32)
    logits = jnp.einsum("td,vd->tv", rnd(_rms(x, spec.norm_eps)), rnd(emb),
                        precision=HI)
    tgt = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                              axis=1)[:, 0]
    return (logits.max(-1), tgt, logits.std(-1),
            jnp.argmax(logits, -1).astype(jnp.int32))


def hidden(spec: ModelSpec, key, tokens, rounding="f32"):
    """Teacher-forced final hidden states (B, T, d) over ``tokens`` (B, T),
    one layer at a time."""
    x = _embed(spec, key, jnp.asarray(tokens))
    for layer in range(spec.layers):
        x = _layer(spec, rounding, key, jnp.int32(layer), x)
    return x


def head(spec: ModelSpec, key, x, targets, rounding="f32"):
    """Per position of ``x`` (B, T, d): (best logit, logit of ``targets``
    (B, T; -1 where none), spread of the logits, own top token), each a
    (B, T) numpy array.  One sequence at a time, so that the (T, vocab)
    logits of only one are alive."""
    import numpy as np
    outs = [_head(spec, rounding, key, x[i], jnp.asarray(targets[i]))
            for i in range(x.shape[0])]
    return tuple(np.stack([np.asarray(o[j]) for o in outs]) for j in range(4))
