"""RG-LRU recurrent block (RecurrentGemma, arXiv:2402.19427).

Block structure (per the paper):
    x, y = split(W_in · u)                      (d_model → 2·lru_width)
    x    = temporal_conv1d(x, width=4)
    x    = RG-LRU(x)
    out  = W_out · (x ⊙ gelu(y))                (lru_width → d_model)

RG-LRU recurrence (gated, data-dependent decay):
    r_t = σ(W_a x_t + b_a)         recurrence gate
    i_t = σ(W_x x_t + b_x)         input gate
    log a_t = −c · softplus(Λ) ⊙ r_t          (c = 8)
    h_t = a_t ⊙ h_{t−1} + √(1 − a_t²) ⊙ (i_t ⊙ x_t)

Training uses ``jax.lax.associative_scan`` (log-depth linear recurrence —
the TPU-native formulation); decode carries (h, conv state) explicitly.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import KeyGen, Px, dense, dense_init

__all__ = ["rglru_init", "rglru_train", "rglru_decode", "RGLRUState",
           "RG_LRU_C"]

RG_LRU_C = 8.0


class RGLRUState(NamedTuple):
    h: jnp.ndarray          # (B, lru_width) recurrent state
    conv: jnp.ndarray       # (B, conv_width-1, lru_width) conv lookback


def rglru_init(key, d_model, lru_width, *, conv_width=4, dtype=jnp.float32,
               stack: Optional[int] = None):
    kg = KeyGen(key)
    def stk(shape, axes):
        full = shape if stack is None else (stack,) + shape
        fax = axes if stack is None else ("layers",) + tuple(axes)
        return full, fax
    lam_shape, lam_axes = stk((lru_width,), ("state",))
    conv_shape, conv_axes = stk((conv_width, lru_width), (None, "state"))
    # Λ init so a ∈ [0.9, 0.999] (paper's init range)
    lam0 = jnp.log(jnp.expm1(-jnp.log(
        jnp.linspace(0.9, 0.999, lru_width, dtype=jnp.float32)) / RG_LRU_C))
    lam = lam0 if stack is None else jnp.broadcast_to(lam0, lam_shape)
    return {
        "w_in": dense_init(kg(), d_model, 2 * lru_width,
                           axes=("d_model_w", "state"), dtype=dtype,
                           stack=stack),
        "w_out": dense_init(kg(), lru_width, d_model,
                            axes=("state", "d_model_w"), dtype=dtype,
                            stack=stack),
        "conv_w": Px(jax.random.normal(kg(), conv_shape, jnp.float32)
                     .astype(dtype) * 0.02, conv_axes),
        "w_a": dense_init(kg(), lru_width, lru_width,
                          axes=("d_model_w", "state"), bias=True, dtype=dtype,
                          stack=stack),
        "w_x": dense_init(kg(), lru_width, lru_width,
                          axes=("d_model_w", "state"), bias=True, dtype=dtype,
                          stack=stack),
        "lam": Px(lam.astype(dtype), lam_axes),
    }


def _gates(p, x):
    r = jax.nn.sigmoid(dense(p["w_a"], x))
    i = jax.nn.sigmoid(dense(p["w_x"], x))
    log_a = -RG_LRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) \
        * r.astype(jnp.float32)
    a = jnp.exp(log_a)
    gated_x = (i * x).astype(jnp.float32) * jnp.sqrt(
        jnp.maximum(1.0 - jnp.square(a), 1e-12))
    return a, gated_x


def _conv1d(x, w):
    """Causal depthwise temporal conv.  x (B,S,D); w (conv_width, D)."""
    cw = w.shape[0]
    out = x * w[-1][None, None, :].astype(x.dtype)
    for k in range(1, cw):
        shifted = jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :-k or None, :]
        shifted = shifted[:, : x.shape[1], :]
        out = out + shifted * w[-1 - k][None, None, :].astype(x.dtype)
    return out


def rglru_train(p, u):
    """Full-sequence recurrent block.  u: (B, S, d_model)."""
    xy = dense(p["w_in"], u)
    x, y = jnp.split(xy, 2, axis=-1)
    xc = _conv1d(x, p["conv_w"])
    a, gx = _gates(p, xc)

    # linear recurrence h_t = a_t h_{t-1} + gx_t via associative scan
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    a_s, h = jax.lax.associative_scan(combine, (a, gx), axis=1)
    h = h.astype(u.dtype)
    return dense(p["w_out"], h * jax.nn.gelu(y))


def rglru_decode(p, u_t, state: RGLRUState) -> Tuple[jnp.ndarray, RGLRUState]:
    """Single-token step.  u_t: (B, 1, d_model)."""
    xy = dense(p["w_in"], u_t)
    x, y = jnp.split(xy, 2, axis=-1)
    x = x[:, 0].astype(state.conv.dtype)  # (B, lru)
    # conv with lookback state (most recent last)
    cw = p["conv_w"].shape[0]
    hist = jnp.concatenate([state.conv, x[:, None, :]], axis=1)  # (B,cw,lru)
    xc = jnp.einsum("bkd,kd->bd", hist.astype(u_t.dtype),
                    p["conv_w"].astype(u_t.dtype))
    a, gx = _gates(p, xc[:, None, :])
    h = (a[:, 0] * state.h.astype(jnp.float32) + gx[:, 0]).astype(u_t.dtype)
    out = dense(p["w_out"], (h * jax.nn.gelu(y[:, 0]))[:, None, :])
    new_state = RGLRUState(h=h.astype(state.h.dtype), conv=hist[:, 1:, :])
    return out, new_state


def rglru_init_state(batch, lru_width, conv_width=4, dtype=jnp.float32):
    return RGLRUState(h=jnp.zeros((batch, lru_width), dtype),
                      conv=jnp.zeros((batch, conv_width - 1, lru_width),
                                     dtype))
