"""Calibration statistics for sequential model PTQ (paper §4, App. C).

Instrumented forward for the dense decoder family taps, per layer:

    x_attn   — input to wq/wk/wv (post ln_attn)
    ctx      — input to wo (pre-projection attention context)
    r_attn   — residual stream entering the attn block (the "R" of wo)
    x_mlp    — input to w_gate/w_up (post ln_mlp)
    hidden   — input to w_out (post-activation MLP hidden)
    r_mlp    — residual stream entering the MLP block (the "R" of w_out)
    attn_p   — per-key mean attention probability p_j  (eq. (19))

Running the same taps on the fp model (X, R) and the quantized-so-far model
(X̂, R̂) yields all covariances of eqs. (16)–(18):

    Σ_X = E[XXᵀ], Σ_X̂, Σ_{X,X̂} = E[XX̂ᵀ], Σ_{Δ,X̂} = W-free E[(R−R̂)X̂ᵀ]

Attention weighting (eq. (19)) multiplies token contributions by p_j when
accumulating QKV covariances; adaptive mixing (eq. (20)) blends the four
variants and is optimized per layer in pipeline.py by golden-section search.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import CalibStats
from repro.models.layers import (_attn_scores, _split_heads, dense, mlp,
                                 rope)
from repro.models.transformer import _attn_kwargs, _norm
import math

__all__ = ["forward_with_taps", "LayerTaps", "StatsAccumulator",
           "accumulate_stats", "stats_for_matrix"]


@dataclasses.dataclass
class LayerTaps:
    x_attn: np.ndarray      # (T, d)  flattened over batch·seq
    ctx: np.ndarray         # (T, n_q·hd)
    r_attn: np.ndarray      # (T, d)
    x_mlp: np.ndarray       # (T, d)
    hidden: np.ndarray      # (T, d_ff)
    r_mlp: np.ndarray       # (T, d)
    attn_p: np.ndarray      # (S,) mean attention mass per key position


def forward_with_taps(cfg: ArchConfig, params, tokens) -> Tuple[jnp.ndarray,
                                                                List[Dict]]:
    """Unscanned forward capturing per-layer tap tensors ("dense" + "moe"
    families).

    Returns (logits, taps list of dicts of jnp arrays).  MoE layers
    additionally expose per-expert routed-token buffers (`expert_in`,
    `expert_hidden` of shape (E, C, ·) with `expert_keep` masks) so the
    pipeline can calibrate each expert's FFN matrices on exactly the tokens
    routed to it.
    """
    assert cfg.family in ("dense", "moe"), cfg.family
    from repro.models.layers import embed, unembed
    ak = _attn_kwargs(cfg)
    x = embed(params["embed"], tokens)
    taps = []
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
           "relu2": lambda u: jnp.square(jax.nn.relu(u))}[cfg.activation]
    for l in range(L):
        lp = jax.tree.map(lambda t: t[l], params["layers"])
        t = {}
        t["r_attn"] = x
        a_in = _norm(cfg, lp["ln_attn"], x)
        t["x_attn"] = a_in
        ctx, probs = _attention_with_probs(lp["attn"], a_in, **ak)
        t["ctx"] = ctx
        t["attn_p"] = probs
        a_out = dense(lp["attn"]["wo"], ctx)
        x = x + a_out
        t["r_mlp"] = x
        m_in = _norm(cfg, lp["ln_mlp"], x)
        t["x_mlp"] = m_in
        if cfg.n_experts:
            m_out, ex = _moe_with_taps(lp["moe"], m_in, cfg, act)
            t.update(ex)
            x = x + m_out
        else:
            if "w_gate" in lp["mlp"]:
                h = act(dense(lp["mlp"]["w_gate"], m_in)) \
                    * dense(lp["mlp"]["w_up"], m_in)
            else:
                h = act(dense(lp["mlp"]["w_in"], m_in))
            t["hidden"] = h
            x = x + dense(lp["mlp"]["w_out"], h)
        taps.append(t)
    x = _norm(cfg, params["ln_f"], x)
    logits = unembed(params["embed"], x, cfg.vocab)
    return logits, taps


def _moe_with_taps(p, x, cfg: ArchConfig, act):
    """MoE forward capturing per-expert routed buffers (taps mirror
    models.layers.moe's sort-based dispatch, drop-free capacity)."""
    from repro.models.layers import _moe_pack
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(t, d)
    logits = (xt @ p["router"]["w"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates, k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)
    capacity = max(-(-t * k // e), k)  # drop-free for calibration fidelity
    buf, (token_of, dest, keep, weights) = _moe_pack(
        xt, top_e, top_g.astype(x.dtype), e, capacity, k)
    if "w_gate" in p:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))) \
            * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    else:
        h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_in"].astype(x.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_out"].astype(x.dtype))
    gathered = out_buf.reshape(e * capacity, d)[dest] \
        * keep[:, None].astype(x.dtype)
    contrib = gathered * weights[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)
    # per-slot occupancy mask: slot (e, c) used iff some kept pair landed
    occ = jnp.zeros((e * capacity,), x.dtype).at[dest].add(
        keep.astype(x.dtype))
    occ = jnp.clip(occ, 0.0, 1.0).reshape(e, capacity)
    return out.reshape(b, s, d), {
        "expert_in": buf,          # (E, C, d) routed inputs (zeros at holes)
        "expert_hidden": h,        # (E, C, ff)
        "expert_occ": occ,         # (E, C) 0/1 occupancy
    }


def _attention_with_probs(p, x, *, n_q, n_kv, head_dim, rope_theta):
    """Self-attention returning (pre-wo context, per-key mean attn mass)."""
    b, s, d = x.shape
    q = _split_heads(dense(p["wq"], x), n_q, head_dim)
    k = _split_heads(dense(p["wk"], x), n_kv, head_dim)
    v = _split_heads(dense(p["wv"], x), n_kv, head_dim)
    positions = jnp.arange(s)[None, :]
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)
    scores = _attn_scores(q, k, 1.0 / math.sqrt(head_dim))
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    scores = jnp.where((j <= i)[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs.astype(x.dtype), v)
    ctx = out.reshape(b, s, n_q * head_dim)
    # eq. (19): p_j = mean over heads/batch of attention into key j,
    # normalized by the (T - j) queries that can see it
    mass = probs.sum(axis=(0, 1, 2, 3))                     # (S,) over keys
    denom = (s - jnp.arange(s)).astype(jnp.float32) * b * n_q
    p_j = mass / denom
    return ctx, p_j


# ---------------------------------------------------------------------------
# Covariance accumulation
# ---------------------------------------------------------------------------


class StatsAccumulator:
    """Accumulates Σ_X / Σ_X̂ / Σ_{X,X̂} / Σ_{Δ,X̂} (+ attention-weighted
    variants) across calibration batches for every (layer, tap)."""

    def __init__(self):
        self.sums: Dict[str, np.ndarray] = {}
        self.counts: Dict[str, float] = {}

    def add(self, key: str, a: np.ndarray, b: Optional[np.ndarray] = None,
            weights: Optional[np.ndarray] = None):
        a = np.asarray(a, np.float64)
        if weights is not None:
            aw = a * weights[:, None]
        else:
            aw = a
        other = a if b is None else np.asarray(b, np.float64)
        m = aw.T @ other
        n = (weights.sum() if weights is not None else a.shape[0])
        if key not in self.sums:
            self.sums[key] = m
            self.counts[key] = n
        else:
            self.sums[key] += m
            self.counts[key] += n

    def get(self, key: str) -> np.ndarray:
        return self.sums[key] / max(self.counts[key], 1e-9)

    def has(self, key: str) -> bool:
        return key in self.sums


def _flat(x) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x.reshape(-1, x.shape[-1])


def accumulate_stats(acc: StatsAccumulator, layer: int,
                     taps_fp: Dict, taps_q: Dict) -> None:
    """Update all covariance sums for one calibration batch at one layer."""
    s = np.asarray(taps_fp["x_attn"]).shape[1]
    pw = np.asarray(taps_fp["attn_p"], np.float64)          # (S,)
    pw_tokens = np.tile(pw, np.asarray(taps_fp["x_attn"]).shape[0])
    for name in ("x_attn", "ctx", "x_mlp", "hidden"):
        if name not in taps_fp:
            continue  # MoE layers expose per-expert buffers instead
        x = _flat(taps_fp[name])
        xh = _flat(taps_q[name])
        acc.add(f"L{layer}/{name}/xx", x)
        acc.add(f"L{layer}/{name}/hh", xh)
        acc.add(f"L{layer}/{name}/xh", x, xh)
        if name == "x_attn":  # attention-weighted variants (QKV only)
            acc.add(f"L{layer}/{name}/xx_w", x, weights=pw_tokens)
            acc.add(f"L{layer}/{name}/hh_w", xh, weights=pw_tokens)
            acc.add(f"L{layer}/{name}/xh_w", x, xh, weights=pw_tokens)
    # residual-stream deltas for the two down-projections (eq. (18))
    for name, rname in (("ctx", "r_attn"), ("hidden", "r_mlp")):
        if name not in taps_fp:
            continue
        dr = _flat(taps_fp[rname]) - _flat(taps_q[rname])
        xh = _flat(taps_q[name])
        acc.add(f"L{layer}/{name}/dr_h", dr, xh)
    # per-expert routed-token covariances (MoE family; quantized-model
    # routing — App. D practice of calibrating on X̂)
    if "expert_in" in taps_q:
        buf = np.asarray(taps_q["expert_in"], np.float64)     # (E, C, d)
        hid = np.asarray(taps_q["expert_hidden"], np.float64)  # (E, C, ff)
        occ = np.asarray(taps_q["expert_occ"], np.float64)     # (E, C)
        for e in range(buf.shape[0]):
            acc.add(f"L{layer}/e{e}/in/xx", buf[e], weights=occ[e])
            acc.add(f"L{layer}/e{e}/hid/xx", hid[e], weights=occ[e])


def stats_for_matrix(acc: StatsAccumulator, layer: int, tap: str, *,
                     use_drift=True, use_residual=False,
                     eps_qr: float = 0.0, eps_aw: float = 1.0,
                     weighted_available=False) -> CalibStats:
    """Assemble CalibStats with adaptive mixing (eqs. (58)-(59)).

    eps_qr → 1 falls back to unquantized statistics; eps_aw → 1 disables
    attention weighting.  Σ_{Δ,X̂} enters as Wᵀ-free cross term: the caller
    turns dr_h (d_resid × n) into the (a × n) Σ_{Δ,X̂} (here a == d_resid).
    """
    def mix(suffix):
        base = acc.get(f"L{layer}/{tap}/{suffix}")
        if weighted_available and acc.has(f"L{layer}/{tap}/{suffix}_w"):
            w = acc.get(f"L{layer}/{tap}/{suffix}_w")
            return (1 - eps_aw) * w + eps_aw * base
        return base

    sx = mix("xx")
    if not use_drift:
        return CalibStats(sigma_x=jnp.asarray(sx, jnp.float32))
    shh = mix("hh")
    sxh = mix("xh")
    # eq. (58): interpolate drift-corrected ↔ original statistics
    shh = (1 - eps_qr) * shh + eps_qr * sx
    sxh = (1 - eps_qr) * sxh + eps_qr * sx
    sdx = None
    if use_residual and acc.has(f"L{layer}/{tap}/dr_h"):
        sdx = jnp.asarray(acc.get(f"L{layer}/{tap}/dr_h"), jnp.float32)
    return CalibStats(sigma_x=jnp.asarray(sx, jnp.float32),
                      sigma_xhat=jnp.asarray(shh, jnp.float32),
                      sigma_x_xhat=jnp.asarray(sxh, jnp.float32),
                      sigma_delta_xhat=sdx)
