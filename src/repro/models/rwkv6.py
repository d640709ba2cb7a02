"""RWKV6 "Finch" blocks (arXiv:2404.05892) — attention-free with
data-dependent decay.

Time mixing (per layer):
    Δ_t = x_{t−1} − x_t   (token shift)
    ξ_t = x_t + Δ_t ⊙ μ_ξ          for ξ ∈ {r, k, v, w, g}
    r, k, v = W_r ξ_r, W_k ξ_k, W_v ξ_v     (reshaped to H heads × 64)
    g = silu(W_g ξ_g)
    w_t = exp(−exp(w0 + tanh(ξ_w A) B))      data-dependent decay (LoRA)
    per head:  out_t = rᵀ_t (S_{t−1} + (u ⊙ k_t) v_tᵀ)
               S_t   = diag(w_t) S_{t−1} + k_t v_tᵀ
    y = W_o (norm_head(out) ⊙ g)

Channel mixing:
    k = relu(W_k ξ_k)²;  y = σ(W_r ξ_r) ⊙ (k W_v)

Training runs the WKV recurrence with ``lax.scan`` over time (state is
(B, H, dk, dv) — tiny vs activations); a chunked parallel form is a §Perf
item.  Decode carries (shift states, S) explicitly — O(1) in sequence
length, which is what makes long_500k tractable for this family.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import KeyGen, Px, dense, dense_init, rmsnorm, rmsnorm_init

__all__ = ["rwkv6_init", "rwkv_time_mix_train", "rwkv_time_mix_decode",
           "rwkv_channel_mix_train", "rwkv_channel_mix_decode", "RWKVState"]


class RWKVState(NamedTuple):
    tm_shift: jnp.ndarray   # (B, d) last input of time-mix
    cm_shift: jnp.ndarray   # (B, d) last input of channel-mix
    wkv: jnp.ndarray        # (B, H, dk, dv) recurrent state


def rwkv6_init(key, d_model, d_ff, *, head_dim=64, decay_lora=64,
               dtype=jnp.float32, stack: Optional[int] = None):
    kg = KeyGen(key)
    n_heads = d_model // head_dim

    def vec(shape, axes, init=0.0):
        full = shape if stack is None else (stack,) + shape
        fax = tuple(axes) if stack is None else ("layers",) + tuple(axes)
        v = jnp.full(full, init, jnp.float32) if init else \
            jax.random.normal(kg(), full, jnp.float32) * 0.02
        return Px(v.astype(dtype), fax)

    tm = {
        "mu": vec((5, d_model), (None, None)),     # r,k,v,w,g mix coefs
        "w_r": dense_init(kg(), d_model, d_model, axes=("d_model_w", "heads"),
                          dtype=dtype, stack=stack),
        "w_k": dense_init(kg(), d_model, d_model, axes=("d_model_w", "heads"),
                          dtype=dtype, stack=stack),
        "w_v": dense_init(kg(), d_model, d_model, axes=("d_model_w", "heads"),
                          dtype=dtype, stack=stack),
        "w_g": dense_init(kg(), d_model, d_model, axes=("d_model_w", "heads"),
                          dtype=dtype, stack=stack),
        "w_o": dense_init(kg(), d_model, d_model, axes=("heads", "d_model_w"),
                          dtype=dtype, stack=stack),
        "decay_a": dense_init(kg(), d_model, decay_lora,
                              axes=("d_model_w", None), dtype=dtype,
                              stack=stack),
        "decay_b": dense_init(kg(), decay_lora, d_model,
                              axes=(None, "heads"), dtype=dtype, stack=stack),
        "w0": vec((d_model,), (None,), init=-2.0),   # base decay ≈ e^{-e^{-2}}
        "u": vec((n_heads, head_dim), ("state", None)),
        "ln_out": rmsnorm_init(head_dim) if stack is None else
        {"scale": Px(jnp.ones((stack, head_dim), jnp.float32), ("layers", None))},
    }
    cm = {
        "mu": vec((2, d_model), (None, None)),
        "w_k": dense_init(kg(), d_model, d_ff, axes=("d_model_w", "ff"),
                          dtype=dtype, stack=stack),
        "w_v": dense_init(kg(), d_ff, d_model, axes=("ff", "d_model_w"),
                          dtype=dtype, stack=stack),
        "w_r": dense_init(kg(), d_model, d_model,
                          axes=("d_model_w", None), dtype=dtype,
                          stack=stack),
    }
    return {"tm": tm, "cm": cm}


def _token_shift(x):
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]


def _mix(x, x_prev, mu):
    return x + (x_prev - x) * mu.astype(x.dtype)


def _decay(p, xw):
    lora = jnp.tanh(dense(p["decay_a"], xw))
    wlog = p["w0"].astype(jnp.float32) + dense(p["decay_b"], lora).astype(jnp.float32)
    return jnp.exp(-jnp.exp(wlog))  # (…, d) ∈ (0, 1)


def rwkv_time_mix_train(p, x, *, head_dim=64):
    """x: (B, S, d) → (B, S, d); scan over time for the WKV recurrence."""
    b, s, d = x.shape
    h = d // head_dim
    xp = _token_shift(x)
    mu = p["mu"]
    xr, xk, xv, xw, xg = (_mix(x, xp, mu[i]) for i in range(5))
    r = dense(p["w_r"], xr).reshape(b, s, h, head_dim)
    k = dense(p["w_k"], xk).reshape(b, s, h, head_dim)
    v = dense(p["w_v"], xv).reshape(b, s, h, head_dim)
    g = jax.nn.silu(dense(p["w_g"], xg))
    w = _decay(p, xw).reshape(b, s, h, head_dim)          # f32
    u = p["u"].astype(jnp.float32)

    def step(state, inp):
        r_t, k_t, v_t, w_t = inp                           # (B,H,dk/dv)
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)         # f32
        out = jnp.einsum("bhk,bhkv->bhv", r_t,
                         state + u[None, :, :, None] * kv)
        state = w_t[..., None] * state + kv
        return state, out

    seq = (jnp.moveaxis(r, 1, 0).astype(jnp.float32),
           jnp.moveaxis(k, 1, 0).astype(jnp.float32),
           jnp.moveaxis(v, 1, 0).astype(jnp.float32),
           jnp.moveaxis(w, 1, 0))
    state0 = jnp.zeros((b, h, head_dim, head_dim), jnp.float32)
    _, outs = jax.lax.scan(step, state0, seq)
    out = jnp.moveaxis(outs, 0, 1)                         # (B,S,H,dv)
    out = rmsnorm(p["ln_out"], out.astype(x.dtype))
    out = (out.reshape(b, s, d) * g)
    return dense(p["w_o"], out)


def rwkv_time_mix_decode(p, x_t, tm_shift, wkv, *, head_dim=64):
    """x_t: (B, 1, d). Returns (out, new_shift, new_wkv)."""
    b, _, d = x_t.shape
    h = d // head_dim
    x = x_t[:, 0]
    mu = p["mu"]
    xr, xk, xv, xw, xg = (x + (tm_shift - x) * mu[i].astype(x.dtype)
                          for i in range(5))
    r = dense(p["w_r"], xr).reshape(b, h, head_dim).astype(jnp.float32)
    k = dense(p["w_k"], xk).reshape(b, h, head_dim).astype(jnp.float32)
    v = dense(p["w_v"], xv).reshape(b, h, head_dim).astype(jnp.float32)
    g = jax.nn.silu(dense(p["w_g"], xg))
    w = _decay(p, xw).reshape(b, h, head_dim)
    u = p["u"].astype(jnp.float32)
    kv = jnp.einsum("bhk,bhv->bhkv", k, v)
    out = jnp.einsum("bhk,bhkv->bhv", r, wkv + u[None, :, :, None] * kv)
    new_wkv = w[..., None] * wkv + kv
    out = rmsnorm(p["ln_out"], out.astype(x.dtype).reshape(b, 1, h, head_dim))
    out = out.reshape(b, 1, d) * g[:, None, :]
    return (dense(p["w_o"], out), x.astype(tm_shift.dtype),
            new_wkv.astype(wkv.dtype))


def rwkv_channel_mix_train(p, x):
    xp = _token_shift(x)
    mu = p["mu"]
    xk = _mix(x, xp, mu[0])
    xr = _mix(x, xp, mu[1])
    k = jnp.square(jax.nn.relu(dense(p["w_k"], xk)))
    return jax.nn.sigmoid(dense(p["w_r"], xr)) * dense(p["w_v"], k)


def rwkv_channel_mix_decode(p, x_t, cm_shift):
    x = x_t[:, 0]
    mu = p["mu"]
    xk = x + (cm_shift - x) * mu[0].astype(x.dtype)
    xr = x + (cm_shift - x) * mu[1].astype(x.dtype)
    k = jnp.square(jax.nn.relu(dense(p["w_k"], xk[:, None, :])))
    out = jax.nn.sigmoid(dense(p["w_r"], xr[:, None, :])) * dense(p["w_v"], k)
    return out, x.astype(cm_shift.dtype)
