"""Unified model builder for all assigned architectures.

Families (configs/base.ArchConfig.family):
  dense   — llama/qwen/minicpm/minitron-like decoder (GQA, optional bias)
  moe     — dense attention + top-k MoE FFN (phi3.5-moe, moonshot)
  ssm     — RWKV6 (attention-free)
  hybrid  — RecurrentGemma (RG-LRU + local attention, pattern-scanned)
  encdec  — Whisper (stub audio frontend; encoder + causal decoder w/ cross)
  vlm     — PaliGemma (stub vision frontend; prefix-LM gemma backbone)

API (all pure functions of (cfg, params, ...)):
  init_params(cfg, key, dtype)            -> Px tree (values + logical axes)
  forward_train(cfg, params, batch)       -> logits (full sequence)
  loss_fn(cfg, params, batch)             -> scalar mean CE
  prefill(cfg, params, batch, max_len)    -> (last-token logits, cache)
  init_cache(cfg, batch, max_len, dtype)  -> cache pytree
  decode_step(cfg, params, cache, token, pos) -> (logits, cache)

Homogeneous stacks are scanned (stacked layer params, `jax.lax.scan` +
optional remat) to keep HLO size O(1) in depth; the recurrentgemma pattern
scans over (rec, rec, attn) groups with an unscanned tail.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import logical_shard
from . import rglru as rg
from . import rwkv6 as rk
from .layers import (KVCache, KeyGen, Px, attention_decode, attention_init,
                     attention_train, cross_attention_decode, dense,
                     dense_init, embed, embed_init, kv_heads_per_row,
                     layernorm, layernorm_init, mlp, mlp_init, moe, moe_init,
                     rmsnorm, rmsnorm_init, scoped, sinusoidal_positions,
                     split_tree, unembed)

__all__ = ["init_params", "forward_train", "loss_fn", "prefill", "init_cache",
           "decode_step", "param_specs_tree", "cache_write_slot",
           "cache_reset_slot"]


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    return rmsnorm_init(d) if cfg.norm == "rmsnorm" else layernorm_init(d)


def _norm(cfg, p, x):
    return rmsnorm(p, x) if cfg.norm == "rmsnorm" else layernorm(p, x)


def _stacked_norm_init(cfg, stack, d=None):
    d = d or cfg.d_model
    p = {"scale": Px(jnp.ones((stack, d), jnp.float32), ("layers", None))}
    if cfg.norm == "layernorm":
        p["bias"] = Px(jnp.zeros((stack, d), jnp.float32), ("layers", None))
    return p


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _attn_kwargs(cfg):
    return dict(n_q=cfg.n_heads, n_kv=cfg.n_kv,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta)


def _decoder_layer_init(cfg, key, stack, dtype):
    """Stacked decoder layers; matmul weights in ``dtype``, norms in f32."""
    kg = KeyGen(key)
    p = {
        "ln_attn": _stacked_norm_init(cfg, stack),
        "attn": attention_init(kg(), cfg.d_model, cfg.n_heads, cfg.n_kv,
                               cfg.resolved_head_dim, bias=cfg.qkv_bias,
                               out_bias=cfg.out_bias, dtype=dtype,
                               stack=stack),
        "ln_mlp": _stacked_norm_init(cfg, stack),
    }
    if cfg.n_experts:
        p["moe"] = moe_init(kg(), cfg.d_model, cfg.d_ff, cfg.n_experts,
                            gated=cfg.gated_mlp, dtype=dtype, stack=stack)
    else:
        p["mlp"] = mlp_init(kg(), cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
                            bias=cfg.out_bias, dtype=dtype, stack=stack)
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    """Px tree for ``cfg``.  ``dtype`` sets the embedding and, for the
    decoder families (dense/moe/vlm), every layer matmul weight."""
    kg = KeyGen(key)
    params: Dict[str, Any] = {"embed": embed_init(kg(), cfg.padded_vocab,
                                                  cfg.d_model, dtype)}
    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _decoder_layer_init(cfg, kg(), cfg.n_layers,
                                               dtype)
        params["ln_f"] = _norm_init(cfg)
    elif cfg.family == "ssm":
        blk = rk.rwkv6_init(kg(), cfg.d_model, cfg.d_ff,
                            head_dim=cfg.wkv_head_dim,
                            decay_lora=cfg.decay_lora, dtype=dtype,
                            stack=cfg.n_layers)
        params["layers"] = {
            "ln_tm": _stacked_norm_init(cfg, cfg.n_layers),
            "ln_cm": _stacked_norm_init(cfg, cfg.n_layers),
            **blk,
        }
        params["ln_f"] = _norm_init(cfg)
    elif cfg.family == "hybrid":
        pat = cfg.block_pattern
        n_groups = cfg.n_layers // len(pat)
        tail_n = cfg.n_layers - n_groups * len(pat)
        lru = cfg.lru_width or cfg.d_model
        group = {}
        for idx, kind in enumerate(pat):
            sub = {"ln_t": _stacked_norm_init(cfg, n_groups),
                   "ln_mlp": _stacked_norm_init(cfg, n_groups),
                   "mlp": mlp_init(kg(), cfg.d_model, cfg.d_ff,
                                   gated=cfg.gated_mlp, stack=n_groups)}
            if kind == "attn":
                sub["attn"] = attention_init(
                    kg(), cfg.d_model, cfg.n_heads, cfg.n_kv,
                    cfg.resolved_head_dim, stack=n_groups)
            else:
                sub["rec"] = rg.rglru_init(kg(), cfg.d_model, lru,
                                           conv_width=cfg.conv_width,
                                           stack=n_groups)
            group[f"b{idx}"] = sub
        params["groups"] = group
        tail = []
        for k in range(tail_n):
            kind = pat[k]
            sub = {"ln_t": _norm_init(cfg), "ln_mlp": _norm_init(cfg),
                   "mlp": mlp_init(kg(), cfg.d_model, cfg.d_ff,
                                   gated=cfg.gated_mlp)}
            if kind == "attn":
                sub["attn"] = attention_init(kg(), cfg.d_model, cfg.n_heads,
                                             cfg.n_kv, cfg.resolved_head_dim)
            else:
                sub["rec"] = rg.rglru_init(kg(), cfg.d_model, lru,
                                           conv_width=cfg.conv_width)
            tail.append(sub)
        params["tail"] = tail
        params["ln_f"] = _norm_init(cfg)
    elif cfg.family == "encdec":
        # encoder (stub conv frontend feeds frame embeddings directly)
        params["enc_layers"] = {
            "ln_attn": _stacked_norm_init(cfg, cfg.enc_layers),
            "attn": attention_init(kg(), cfg.d_model, cfg.n_heads, cfg.n_kv,
                                   cfg.resolved_head_dim, bias=True,
                                   out_bias=True, stack=cfg.enc_layers),
            "ln_mlp": _stacked_norm_init(cfg, cfg.enc_layers),
            "mlp": mlp_init(kg(), cfg.d_model, cfg.d_ff, gated=False,
                            bias=True, stack=cfg.enc_layers),
        }
        params["enc_ln_f"] = _norm_init(cfg)
        params["dec_layers"] = {
            "ln_self": _stacked_norm_init(cfg, cfg.n_layers),
            "self_attn": attention_init(kg(), cfg.d_model, cfg.n_heads,
                                        cfg.n_kv, cfg.resolved_head_dim,
                                        bias=True, out_bias=True,
                                        stack=cfg.n_layers),
            "ln_cross": _stacked_norm_init(cfg, cfg.n_layers),
            "cross_attn": attention_init(kg(), cfg.d_model, cfg.n_heads,
                                         cfg.n_kv, cfg.resolved_head_dim,
                                         bias=True, out_bias=True,
                                         stack=cfg.n_layers),
            "ln_mlp": _stacked_norm_init(cfg, cfg.n_layers),
            "mlp": mlp_init(kg(), cfg.d_model, cfg.d_ff, gated=False,
                            bias=True, stack=cfg.n_layers),
        }
        params["dec_pos"] = Px(
            jax.random.normal(kg(), (4096, cfg.d_model), jnp.float32) * 0.01,
            (None, None))
        params["ln_f"] = _norm_init(cfg)
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# scanned decoder stacks
# ---------------------------------------------------------------------------


def _decoder_block(cfg, x, lp, *, prefix_len=None):
    ak = _attn_kwargs(cfg)
    h = attention_train(lp["attn"], _norm(cfg, lp["ln_attn"], x),
                        causal=True,
                        window=cfg.local_window or None,
                        prefix_len=prefix_len, **ak)
    x = x + h
    hin = _norm(cfg, lp["ln_mlp"], x)
    if cfg.n_experts:
        h2 = moe(lp["moe"], hin, n_experts=cfg.n_experts, top_k=cfg.top_k,
                 capacity_factor=cfg.capacity_factor,
                 activation=cfg.activation)
    else:
        h2 = mlp(lp["mlp"], hin, activation=cfg.activation)
    return x + h2


def _scan_layers(cfg, layer_params, x, block_fn):
    def body(carry, lp):
        y = block_fn(carry, lp)
        return y, None
    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, layer_params)
    return x


# ---------------------------------------------------------------------------
# train / prefill forwards
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, params, tokens):
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return logical_shard(x, "batch", "seq", "d_model")


def forward_train(cfg: ArchConfig, params, batch) -> jnp.ndarray:
    """Full-sequence logits."""
    if cfg.family in ("dense", "moe"):
        x = _embed_tokens(cfg, params, batch["tokens"])
        x = _scan_layers(cfg, params["layers"], x,
                         functools.partial(_decoder_block, cfg))
        x = _norm(cfg, params["ln_f"], x)
        return unembed(params["embed"], x, cfg.vocab)

    if cfg.family == "vlm":
        tok = _embed_tokens(cfg, params, batch["tokens"])
        x = jnp.concatenate([batch["patches"].astype(tok.dtype), tok], axis=1)
        x = logical_shard(x, "batch", "seq", "d_model")
        x = _scan_layers(
            cfg, params["layers"], x,
            functools.partial(_decoder_block, cfg,
                              prefix_len=cfg.prefix_tokens))
        x = _norm(cfg, params["ln_f"], x)
        return unembed(params["embed"], x, cfg.vocab)[:, cfg.prefix_tokens:, :]

    if cfg.family == "ssm":
        x = _embed_tokens(cfg, params, batch["tokens"])

        def block(carry, lp):
            y = carry + rk.rwkv_time_mix_train(
                lp["tm"], _norm(cfg, lp["ln_tm"], carry),
                head_dim=cfg.wkv_head_dim)
            y = y + rk.rwkv_channel_mix_train(
                lp["cm"], _norm(cfg, lp["ln_cm"], y))
            return y
        x = _scan_layers(cfg, params["layers"], x, lambda c, lp: block(c, lp))
        x = _norm(cfg, params["ln_f"], x)
        return unembed(params["embed"], x, cfg.vocab)

    if cfg.family == "hybrid":
        x = _embed_tokens(cfg, params, batch["tokens"])
        pat = cfg.block_pattern

        def group_block(carry, gp):
            y = carry
            for idx, kind in enumerate(pat):
                sub = gp[f"b{idx}"]
                t_in = _norm(cfg, sub["ln_t"], y)
                if kind == "attn":
                    h = attention_train(sub["attn"], t_in, causal=True,
                                        window=cfg.local_window or None,
                                        **_attn_kwargs(cfg))
                else:
                    h = rg.rglru_train(sub["rec"], t_in)
                y = y + h
                y = y + mlp(sub["mlp"], _norm(cfg, sub["ln_mlp"], y),
                            activation=cfg.activation)
            return y

        def body(carry, gp):
            return group_block(carry, gp), None
        body_fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
        x, _ = jax.lax.scan(body_fn, x, params["groups"])
        for k, sub in enumerate(params["tail"]):
            kind = pat[k]
            t_in = _norm(cfg, sub["ln_t"], x)
            h = (attention_train(sub["attn"], t_in, causal=True,
                                 window=cfg.local_window or None,
                                 **_attn_kwargs(cfg))
                 if kind == "attn" else rg.rglru_train(sub["rec"], t_in))
            x = x + h
            x = x + mlp(sub["mlp"], _norm(cfg, sub["ln_mlp"], x),
                        activation=cfg.activation)
        x = _norm(cfg, params["ln_f"], x)
        return unembed(params["embed"], x, cfg.vocab)

    if cfg.family == "encdec":
        enc = _encode(cfg, params, batch["frames"])
        return _decode_train(cfg, params, batch["tokens"], enc)

    raise ValueError(cfg.family)


def _encode(cfg, params, frames):
    x = frames + sinusoidal_positions(frames.shape[1], cfg.d_model,
                                      frames.dtype)[None]
    x = logical_shard(x, "batch", "frames", "d_model")

    def block(carry, lp):
        y = carry + attention_train(lp["attn"],
                                    _norm(cfg, lp["ln_attn"], carry),
                                    causal=False, use_rope=False,
                                    **_attn_kwargs(cfg))
        y = y + mlp(lp["mlp"], _norm(cfg, lp["ln_mlp"], y),
                    activation="gelu")
        return y
    x = _scan_layers(cfg, params["enc_layers"], x, lambda c, lp: block(c, lp))
    return _norm(cfg, params["enc_ln_f"], x)


def _decode_train(cfg, params, tokens, enc):
    s = tokens.shape[1]
    pos_table = params["dec_pos"]
    x = _embed_tokens(cfg, params, tokens)
    pos = jax.lax.dynamic_slice_in_dim(
        pos_table, 0, min(s, pos_table.shape[0]), axis=0)
    if s > pos_table.shape[0]:  # extend cyclically for long shape exercises
        reps = -(-s // pos_table.shape[0])
        pos = jnp.tile(pos, (reps, 1))[:s]
    x = x + pos[None].astype(x.dtype)

    def block(carry, lp):
        y = carry + attention_train(lp["self_attn"],
                                    _norm(cfg, lp["ln_self"], carry),
                                    causal=True, use_rope=False,
                                    **_attn_kwargs(cfg))
        y = y + attention_train(lp["cross_attn"],
                                _norm(cfg, lp["ln_cross"], y),
                                kv_x=enc, use_rope=False, **_attn_kwargs(cfg))
        y = y + mlp(lp["mlp"], _norm(cfg, lp["ln_mlp"], y), activation="gelu")
        return y
    x = _scan_layers(cfg, params["dec_layers"], x, lambda c, lp: block(c, lp))
    x = _norm(cfg, params["ln_f"], x)
    return unembed(params["embed"], x, cfg.vocab)


def loss_fn(cfg: ArchConfig, params, batch) -> jnp.ndarray:
    logits = forward_train(cfg, params, batch)
    targets = batch["targets"]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------


class DecodeCache(NamedTuple):
    kv: Any                   # per-family state (stacked over layers)
    pos: jnp.ndarray          # int32 current position: scalar (lockstep
                              # static batching) or (B,) per-slot vector
                              # (continuous batching, DESIGN.md §9)
    extras: Any = ()          # enc-dec: (enc_k, enc_v) stacked; else ()


def _kv_buf(cfg, batch, buf_len, dtype, n_layers=None):
    nl = n_layers if n_layers is not None else cfg.n_layers
    hd = cfg.resolved_head_dim
    per_row = kv_heads_per_row(cfg.n_kv, hd)
    shape = (nl, batch, cfg.n_kv // per_row, buf_len, per_row * hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


@scoped("kv_cache")
def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, *, per_slot: bool = False) -> DecodeCache:
    """Fresh decode cache.  ``per_slot=True`` makes ``pos`` a (batch,) int32
    vector — one independent position counter per serving slot (continuous
    batching, DESIGN.md §9) — instead of the scalar lockstep counter.  Slot
    state is refreshed by :func:`cache_write_slot` (admission graft) and
    :func:`cache_reset_slot` (eviction)."""
    pos0 = (jnp.zeros((batch,), jnp.int32) if per_slot
            else jnp.zeros((), jnp.int32))
    if cfg.family in ("dense", "moe", "vlm"):
        buf = min(max_len, cfg.local_window) if cfg.local_window else max_len
        return DecodeCache(_kv_buf(cfg, batch, buf, dtype), pos0)
    if cfg.family == "ssm":
        h = cfg.d_model // cfg.wkv_head_dim
        st = rk.RWKVState(
            tm_shift=jnp.zeros((cfg.n_layers, batch, cfg.d_model), dtype),
            cm_shift=jnp.zeros((cfg.n_layers, batch, cfg.d_model), dtype),
            wkv=jnp.zeros((cfg.n_layers, batch, h, cfg.wkv_head_dim,
                           cfg.wkv_head_dim), jnp.float32))
        return DecodeCache(st, pos0)
    if cfg.family == "hybrid":
        pat = cfg.block_pattern
        types = cfg._layer_types()
        n_attn = sum(1 for t in types if t == "attn")
        n_rec = cfg.n_layers - n_attn
        lru = cfg.lru_width or cfg.d_model
        kv = _kv_buf(cfg, batch, min(max_len, cfg.local_window or max_len),
                     dtype, n_layers=n_attn)
        rec = rg.RGLRUState(
            h=jnp.zeros((n_rec, batch, lru), dtype),
            conv=jnp.zeros((n_rec, batch, cfg.conv_width - 1, lru), dtype))
        return DecodeCache({"kv": kv, "rec": rec}, pos0)
    if cfg.family == "encdec":
        kv = _kv_buf(cfg, batch, max_len, dtype)
        ek_shape = (cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv,
                    cfg.resolved_head_dim)
        extras = (jnp.zeros(ek_shape, dtype), jnp.zeros(ek_shape, dtype))
        return DecodeCache(kv, pos0, extras)
    raise ValueError(cfg.family)


@scoped("kv_cache")
def cache_write_slot(cache: DecodeCache, sub: DecodeCache,
                     slot) -> DecodeCache:
    """Graft a batch-1 ``sub`` cache into row ``slot`` of a per-slot cache.

    Admission primitive of the continuous engine (DESIGN.md §9): a new
    request is prefilled on its own batch-1 cache (via decode_chunk, exact
    w.r.t. the per-token reference) and its state rows are copied into the
    free slot, leaving every other slot's state untouched.  All state leaves
    carry batch on axis 1 (layer-stacked); ``pos`` carries batch on axis 0.
    ``slot`` may be a traced int32 — one jit covers all slots.
    """
    assert cache.pos.ndim == 1, "cache_write_slot needs a per-slot cache"

    def graft(big, small):
        return jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=1)

    kv = jax.tree.map(graft, cache.kv, sub.kv)
    extras = jax.tree.map(graft, cache.extras, sub.extras)
    sub_pos = sub.pos if sub.pos.ndim == 0 else sub.pos[0]
    pos = cache.pos.at[slot].set(sub_pos.astype(jnp.int32))
    return DecodeCache(kv, pos, extras)


@scoped("kv_cache")
def cache_reset_slot(cache: DecodeCache, slot) -> DecodeCache:
    """Zero row ``slot`` of a per-slot cache (eviction hygiene).

    Functionally optional — a freed slot's stale K/V rows are never attended
    to (its position mask resets on the next graft) — but zeroing keeps the
    idle slot's position at 0 so it re-writes its own row instead of
    scattering past the buffer, and makes state leaks impossible rather than
    merely masked.
    """
    assert cache.pos.ndim == 1, "cache_reset_slot needs a per-slot cache"

    def zero(big):
        row = jnp.zeros(big.shape[:1] + (1,) + big.shape[2:], big.dtype)
        return jax.lax.dynamic_update_slice_in_dim(big, row, slot, axis=1)

    kv = jax.tree.map(zero, cache.kv)
    extras = jax.tree.map(zero, cache.extras)
    return DecodeCache(kv, cache.pos.at[slot].set(0), extras)


def prefill(cfg: ArchConfig, params, batch, max_len: int,
            cache_dtype=jnp.bfloat16):
    """Run the full prompt, return (last logits, populated cache).

    Recurrent families (ssm, hybrid) step the prompt through
    :func:`decode_chunk` on a fresh cache, exactly as serving does.
    Attention families run :func:`forward_train` for the logits and fill
    the cache by :func:`_capture_kv`, which projects the embeddings, not
    each layer's normed input, and applies no RoPE: that cache is the
    right shape and dataflow for the dry-run but NOT the cache a decode
    would build, so nothing may decode from it (DESIGN.md §6).
    """
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        return _prefill_attn(cfg, params, batch, max_len, cache_dtype)
    if cfg.family in ("ssm", "hybrid"):
        tokens = batch["tokens"]
        cache = init_cache(cfg, tokens.shape[0], max_len, cache_dtype)
        return decode_chunk(cfg, params, cache, tokens)
    raise ValueError(cfg.family)


def _prefill_attn(cfg, params, batch, max_len, cache_dtype):
    """Prefill for attention families: forward + K/V capture."""
    toks = batch.get("tokens")
    b, s = toks.shape
    cache = init_cache(cfg, b, max_len, cache_dtype)
    logits = forward_train(cfg, params, batch)
    # recompute per-layer K/V once more inside a capture scan would double
    # compute; instead capture via forward hooks: here we re-run the embed +
    # per-layer K/V projections only (cheap: 2·d·kv·hd per token).
    kv = _capture_kv(cfg, params, batch, cache.kv.k.shape[3], cache_dtype)
    extras = None
    if cfg.family == "encdec":
        enc = _encode(cfg, params, batch["frames"])
        extras = _capture_cross_kv(cfg, params, enc, cache_dtype)
    pos = jnp.asarray(s if cfg.family != "vlm" else s + cfg.prefix_tokens,
                      jnp.int32)
    return logits[:, -1, :], DecodeCache(kv, pos, extras)


def _capture_kv(cfg, params, batch, buf_len, cache_dtype):
    """Recompute post-norm K/V per layer and write into cache buffers.

    NOTE: exactness requires the *layer inputs*, which we do not re-run here;
    the serve engine uses prefill only as a shape/dataflow exercise for the
    dry-run, while the functional engine path (serve/engine.py) builds the
    cache by stepping decode_step over the prompt (exact).  Documented in
    DESIGN.md §6.
    """
    x = _embed_tokens(cfg, params, batch["tokens"])
    lp = params["layers"] if cfg.family != "encdec" else params["dec_layers"]
    attn_p = lp["attn"] if "attn" in lp else lp["self_attn"]
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv

    def capture(lp_attn_w):  # (L, d, kv*hd)
        k = jnp.einsum("bsd,ldk->lbsk", x, lp_attn_w)
        return k
    k_all = capture(attn_p["wk"]["w"]).astype(cache_dtype)
    v_all = capture(attn_p["wv"]["w"]).astype(cache_dtype)
    L = k_all.shape[0]
    b, s = x.shape[0], x.shape[1]
    rows = nkv // kv_heads_per_row(nkv, hd)
    k_all = jnp.swapaxes(k_all.reshape(L, b, s, rows, -1), 2, 3)
    v_all = jnp.swapaxes(v_all.reshape(L, b, s, rows, -1), 2, 3)
    k_all, v_all = k_all[:, :, :, -buf_len:], v_all[:, :, :, -buf_len:]
    buf = _kv_buf(cfg, b, buf_len, cache_dtype, n_layers=L)
    k_buf = jax.lax.dynamic_update_slice_in_dim(buf.k, k_all, 0, axis=3)
    v_buf = jax.lax.dynamic_update_slice_in_dim(buf.v, v_all, 0, axis=3)
    return KVCache(k=k_buf, v=v_buf)


def _capture_cross_kv(cfg, params, enc, cache_dtype):
    lp = params["dec_layers"]["cross_attn"]
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv
    k = jnp.einsum("bsd,ldk->lbsk", enc, lp["wk"]["w"])
    v = jnp.einsum("bsd,ldk->lbsk", enc, lp["wv"]["w"])
    b, s = enc.shape[0], enc.shape[1]
    L = k.shape[0]
    k = k.reshape(L, b, s, nkv, hd) + 0.0
    v = v.reshape(L, b, s, nkv, hd)
    if "b" in lp["wk"]:
        k = k + lp["wk"]["b"].reshape(L, 1, 1, nkv, hd)
        v = v + lp["wv"]["b"].reshape(L, 1, 1, nkv, hd)
    return (k.astype(cache_dtype), v.astype(cache_dtype))


def decode_step(cfg: ArchConfig, params, cache: DecodeCache, token,
                ):
    """One decode step: token (B, 1) int32 → (logits (B, vocab), cache)."""
    pos = cache.pos
    x = _embed_tokens(cfg, params, token)
    ak = _attn_kwargs(cfg)

    if cfg.family in ("dense", "moe", "vlm"):
        window = cfg.local_window or None

        # the stacked cache rides in the carry, so each layer writes its
        # token into it in place by index: as scan xs/ys every layer's
        # cache would be sliced out and written back whole each step
        def body(carry, lps):
            h, kv = carry
            lp, layer = lps
            a_in = _norm(cfg, lp["ln_attn"], h)
            a_out, kv = attention_decode(lp["attn"], a_in, kv, layer, pos,
                                         window=window, **ak)
            h = h + a_out
            m_in = _norm(cfg, lp["ln_mlp"], h)
            if cfg.n_experts:
                m_out = moe(lp["moe"], m_in, n_experts=cfg.n_experts,
                            top_k=cfg.top_k,
                            capacity_factor=cfg.capacity_factor,
                            activation=cfg.activation)
            else:
                m_out = mlp(lp["mlp"], m_in, activation=cfg.activation)
            return (h + m_out, kv), None

        (x, kv), _ = jax.lax.scan(body, (x, cache.kv),
                                  (params["layers"],
                                   jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        x = _norm(cfg, params["ln_f"], x)
        logits = unembed(params["embed"], x, cfg.vocab)[:, 0, :]
        return logits, DecodeCache(kv, pos + 1, cache.extras)

    if cfg.family == "ssm":
        st = cache.kv

        def body(carry, lps):
            h, = carry
            lp, tm_s, cm_s, wkv = lps
            t_out, tm_new, wkv_new = rk.rwkv_time_mix_decode(
                lp["tm"], _norm(cfg, lp["ln_tm"], h), tm_s, wkv,
                head_dim=cfg.wkv_head_dim)
            h = h + t_out
            c_out, cm_new = rk.rwkv_channel_mix_decode(
                lp["cm"], _norm(cfg, lp["ln_cm"], h), cm_s)
            return (h + c_out,), (tm_new, cm_new, wkv_new)

        (x,), (tm_new, cm_new, wkv_new) = jax.lax.scan(
            body, (x,), (params["layers"], st.tm_shift, st.cm_shift, st.wkv))
        x = _norm(cfg, params["ln_f"], x)
        logits = unembed(params["embed"], x, cfg.vocab)[:, 0, :]
        st2 = rk.RWKVState(tm_shift=tm_new, cm_shift=cm_new, wkv=wkv_new)
        return logits, DecodeCache(st2, pos + 1, cache.extras)

    if cfg.family == "hybrid":
        pat = cfg.block_pattern
        n_groups = cfg.n_layers // len(pat)
        st = cache.kv
        kv, rec = st["kv"], st["rec"]
        # scan over groups; the attention stack rides in the carry (group
        # g's a-th attention layer is entry g * n_attn_per_group + a), the
        # recurrent states are scanned per group
        n_attn_per_group = sum(1 for t in pat if t == "attn")
        n_rec_per_group = len(pat) - n_attn_per_group
        rec_g = jax.tree.map(
            lambda t: t[:n_rec_per_group * n_groups].reshape(
                (n_groups, n_rec_per_group) + t.shape[1:]), rec)

        def body(carry, lps):
            h, kv = carry
            gp, rec_l, g = lps
            ai, ri = 0, 0
            rec_out = []
            for idx, kind in enumerate(pat):
                sub = gp[f"b{idx}"]
                t_in = _norm(cfg, sub["ln_t"], h)
                if kind == "attn":
                    a_out, kv = attention_decode(
                        sub["attn"], t_in, kv, g * n_attn_per_group + ai, pos,
                        window=cfg.local_window or None, **ak)
                    h = h + a_out
                    ai += 1
                else:
                    reci = rg.RGLRUState(h=rec_l.h[ri], conv=rec_l.conv[ri])
                    r_out, rec_new = rg.rglru_decode(sub["rec"], t_in, reci)
                    rec_out.append(rec_new)
                    h = h + r_out
                    ri += 1
                h = h + mlp(sub["mlp"], _norm(cfg, sub["ln_mlp"], h),
                            activation=cfg.activation)
            rec_stack = jax.tree.map(lambda *ts: jnp.stack(ts), *rec_out) \
                if rec_out else rec_l
            return (h, kv), rec_stack

        (x, kv_new), rec_new_g = jax.lax.scan(
            body, (x, kv),
            (params["groups"], rec_g, jnp.arange(n_groups, dtype=jnp.int32)))
        rec_new = jax.tree.map(
            lambda t: t.reshape((-1,) + t.shape[2:]), rec_new_g)
        # unscanned tail: for the recurrentgemma pattern (rec, rec, attn)
        # the tail layers (n_layers mod 3) are always recurrent.
        tail_rec_states = []
        base_r = n_rec_per_group * n_groups
        for k, sub in enumerate(params["tail"]):
            kind = pat[k]
            assert kind != "attn", "tail attention layers unsupported"
            t_in = _norm(cfg, sub["ln_t"], x)
            idx = base_r + k
            reci = rg.RGLRUState(h=rec.h[idx], conv=rec.conv[idx])
            r_out, rec_i_new = rg.rglru_decode(sub["rec"], t_in, reci)
            tail_rec_states.append(rec_i_new)
            x = x + r_out
            x = x + mlp(sub["mlp"], _norm(cfg, sub["ln_mlp"], x),
                        activation=cfg.activation)
        if tail_rec_states:
            tail_h = jnp.stack([s.h for s in tail_rec_states])
            tail_conv = jnp.stack([s.conv for s in tail_rec_states])
            rec_new = rg.RGLRUState(
                h=jnp.concatenate([rec_new.h, tail_h], axis=0),
                conv=jnp.concatenate([rec_new.conv, tail_conv], axis=0))
        x = _norm(cfg, params["ln_f"], x)
        logits = unembed(params["embed"], x, cfg.vocab)[:, 0, :]
        st2 = {"kv": kv_new, "rec": rec_new}
        return logits, DecodeCache(st2, pos + 1, cache.extras)

    if cfg.family == "encdec":
        enc_k, enc_v = cache.extras
        n_pos = params["dec_pos"].shape[0]
        if jnp.ndim(pos) == 1:          # per-slot: one table row per slot
            pos_emb = jnp.take(params["dec_pos"], pos % n_pos,
                               axis=0)[:, None]
        else:
            pos_emb = jax.lax.dynamic_slice_in_dim(
                params["dec_pos"], pos % n_pos, 1, axis=0)[None]
        x = x + pos_emb.astype(x.dtype)

        def body(carry, lps):
            h, kv = carry
            lp, ek, ev, layer = lps
            a_out, kv = attention_decode(
                lp["self_attn"], _norm(cfg, lp["ln_self"], h), kv, layer,
                pos, use_rope=False, **ak)
            h = h + a_out
            c_out = cross_attention_decode(
                lp["cross_attn"], _norm(cfg, lp["ln_cross"], h), ek, ev,
                n_q=cfg.n_heads, n_kv=cfg.n_kv,
                head_dim=cfg.resolved_head_dim)
            h = h + c_out
            h = h + mlp(lp["mlp"], _norm(cfg, lp["ln_mlp"], h),
                        activation="gelu")
            return (h, kv), None

        (x, kv), _ = jax.lax.scan(
            body, (x, cache.kv),
            (params["dec_layers"], enc_k, enc_v,
             jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        x = _norm(cfg, params["ln_f"], x)
        logits = unembed(params["embed"], x, cfg.vocab)[:, 0, :]
        return logits, DecodeCache(kv, pos + 1, cache.extras)

    raise ValueError(cfg.family)


def decode_chunk(cfg: ArchConfig, params, cache: DecodeCache, tokens):
    """Step the cache ``tokens.shape[1]`` tokens in ONE jittable call.

    ``tokens`` (B, C) int32 → (logits of the LAST token (B, vocab), cache).
    Semantically identical to C sequential :func:`decode_step` calls — the
    scan body IS decode_step, so the cache trajectory and logits are
    bit-exact w.r.t. the per-token path — but it costs one device dispatch
    (and one jit cache entry per chunk shape) instead of C.  This is the
    chunked-prefill primitive of serve.ServeEngine (DESIGN.md §8): prompt
    prefill drops from O(prompt_len) dispatches to ceil(prompt_len/chunk).
    """
    def step(c, tok):
        logits, c = decode_step(cfg, params, c, tok[:, None])
        return c, logits

    cache, logits_seq = jax.lax.scan(step, cache, jnp.swapaxes(tokens, 0, 1))
    return logits_seq[-1], cache


def param_specs_tree(params_px):
    """Px tree -> (values, PartitionSpec tree) via dist.sharding rules."""
    from repro.dist.sharding import spec_for_axes
    vals, axes = split_tree(params_px)
    specs = jax.tree.map(lambda ax: spec_for_axes(ax), axes,
                         is_leaf=lambda x: isinstance(x, tuple))
    return vals, specs
