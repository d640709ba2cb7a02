"""Blockwise (flash) attention Pallas TPU kernel — forward pass.

§Perf identified the dense-train/prefill memory dominator: XLA materializes
(S × S × heads) f32 score/probability tensors per layer (qwen2.5 train_4k:
~1.6 GB/layer-visit of score traffic).  Online-softmax blockwise attention
keeps the running (m, l, acc) statistics in VMEM and never writes the S×S
matrix to HBM — the classic flash-attention restructuring, here in its
TPU-native form:

  * grid (batch·heads, Q-blocks, K-blocks), K innermost (sequential) so the
    (bq × d) accumulator lives in VMEM scratch across K steps,
  * MXU-aligned tiles (bq = bk = 128, d = head_dim),
  * causal + local-window masking via block-index iota (fully-masked K
    blocks are skipped with pl.when — restores the 2× causal FLOP saving),
  * numerics: running max/sum in f32 regardless of input dtype.

Forward-only: serving (prefill) uses it directly; the training backward is
wired as recompute-from-reference via jax.custom_vjp in ops.py (kernelized
backward is future work, documented in DESIGN.md).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq: int, bk: int, n_k: int, scale: float, causal: bool,
            window: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qb * bq
    k_start = kb * bk

    # block-level reachability: any (i, j) with j <= i and i - j < window?
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    run = live if isinstance(live, bool) else None

    def body():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window > 0:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), jnp.bool_)
            if causal:
                mask = jnp.logical_and(mask, kj <= qi)
            if window > 0:
                mask = jnp.logical_and(mask, qi - kj < window)
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]                        # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # skip K blocks strictly above the diagonal (2× causal saving)
        pl.when(k_start <= q_start + bq - 1)(body)
    else:
        body()

    @pl.when(kb == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q, k, v: (BH, S, d) → (BH, S, d).  S must divide block sizes
    (ops.py pads); d is the full head_dim (MXU-aligned by construction)."""
    bh, s, d = q.shape
    assert k.shape == v.shape == (bh, s, d)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_q = s // block_q
    n_k = s // block_k
    scale = 1.0 / math.sqrt(d)
    grid = (bh, n_q, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, bq=block_q, bk=block_k, n_k=n_k,
                          scale=scale, causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
