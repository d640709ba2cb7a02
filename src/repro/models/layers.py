"""Shared model building blocks (pure-functional JAX).

Parameters are created as ``Px(value, logical_axes)`` leaves; ``split_tree``
separates them into a value pytree and a logical-axes pytree that
dist.sharding converts to PartitionSpecs — init and sharding can never drift.

Blocks: RMSNorm/LayerNorm, rotary embeddings, GQA attention (optional QKV
bias, local window with ring-buffer KV cache, prefix-LM mask, cross
attention), gated/plain MLPs, sort-based capacity-buffer MoE (EP-shardable),
embedding/unembedding.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import logical_shard

__all__ = [
    "Px", "split_tree", "KeyGen", "scoped",
    "rmsnorm_init", "rmsnorm", "layernorm_init", "layernorm",
    "dense_init", "dense",
    "rope", "sinusoidal_positions",
    "attention_init", "attention_train", "attention_decode", "KVCache",
    "mlp_init", "mlp", "moe_init", "moe",
    "embed_init", "embed", "unembed",
]


# ---------------------------------------------------------------------------
# Param plumbing
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Px:
    """A parameter leaf annotated with logical axis names."""

    value: Any
    axes: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    Px, lambda p: ((p.value,), tuple(p.axes)),
    lambda aux, ch: Px(ch[0], aux))


def _is_px(x):
    return isinstance(x, Px)


def split_tree(tree):
    """Px tree -> (param values, logical axes) twin pytrees."""
    vals = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_px)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_px)
    return vals, axes


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``, so the
    device ops it traces carry ``name`` in their ``op_name`` (a fresh scope
    per call: the context object keeps state and is not shared)."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap


class KeyGen:
    def __init__(self, key):
        self._key = key

    def __call__(self):
        self._key, sub = jax.random.split(self._key)
        return sub


def _norm_init(shape):  # ones
    return jnp.ones(shape, jnp.float32)


def _dense_w(key, shape, scale, dtype):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d):
    return {"scale": Px(_norm_init((d,)), (None,))}


def rmsnorm(p, x, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def layernorm_init(d):
    return {"scale": Px(_norm_init((d,)), (None,)),
            "bias": Px(jnp.zeros((d,), jnp.float32), (None,))}


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


def dense_init(key, in_dim, out_dim, *, axes, bias=False, scale=1.0,
               dtype=jnp.float32, stack: Optional[int] = None):
    shape = (in_dim, out_dim) if stack is None else (stack, in_dim, out_dim)
    waxes = axes if stack is None else ("layers",) + tuple(axes)
    p = {"w": Px(_dense_w(key, shape, scale, dtype), waxes)}
    if bias:
        bshape = (out_dim,) if stack is None else (stack, out_dim)
        baxes = (axes[-1],) if stack is None else ("layers", axes[-1])
        p["b"] = Px(jnp.zeros(bshape, dtype), baxes)
    return p


def dense(p, x):
    w = p["w"]
    if isinstance(w, dict) and "kshard" in w:
        # Tensor-parallel k-sharded serving leaf (DESIGN.md §13): the
        # payload carries an explicit leading shard axis (one contiguous
        # in-feature block per entry, re-packed planar per shard by
        # serve/sharded.py).  Inside a shard_map body the manual-axes
        # context names the mesh axis and each device computes its single
        # partial; with no context (the single-device oracle) all shard
        # partials are computed locally.  Either way the partials are
        # combined by the same ordered chain-sum, so the two paths are
        # bit-identical.
        from repro.dist.sharding import manual_axis_info
        from repro.kernels.dequant import dequant_matmul_sharded
        ctx = manual_axis_info()
        axis = ctx.get("axis") if ctx else None
        shards = ctx.get("shards") if ctx else None
        lead = x.shape[:-1]
        xf = x.reshape(-1, x.shape[-1])
        if "codes" in w:
            esc = ((w["esc_row"], w["esc_col"], w["esc_dval"])
                   if "esc_row" in w else None)
            y = dequant_matmul_sharded(xf, w["codes"], w.get("s"), w.get("t"),
                                       escapes=esc, axis_name=axis,
                                       shards=shards)
        else:
            y = dequant_matmul_sharded(xf, w["wsh"], axis_name=axis,
                                       shards=shards)
        y = y.reshape(lead + (y.shape[-1],)).astype(x.dtype)
    elif isinstance(w, dict) and "codes" in w:
        if w["codes"].dtype == jnp.uint8:
            # WaterSIC sub-byte serving paths (DESIGN.md §8/§10): the
            # planar int4 nibble payload (out, ceil(in/2)), int3
            # bit-plane payload (out, 3, ceil(in/8)) and int2 field
            # payload (out, 1, ceil(in/4)) all route through the fused
            # packed dequant-matmul with in-VMEM unpack — the wrapper
            # dispatches on the payload shape.  Escapes applied as a
            # sparse COO correction either way.  Mixed-rate serving
            # (repro.plan) mixes these formats freely across leaves.
            from repro.kernels.dequant import dequant_matmul
            lead = x.shape[:-1]
            y = dequant_matmul(
                x.reshape(-1, x.shape[-1]), w["codes"], w["s"], w["t"],
                escapes=(w["esc_row"], w["esc_col"], w["esc_dval"]))
            y = y.reshape(lead + (y.shape[-1],)).astype(x.dtype)
        else:
            # WaterSIC int8 serving path: y = ((x·s) @ codes)·t — the
            # weight stays int8 in HBM (quant/qlinear.py + kernels/dequant)
            y = ((x * w["s"].astype(x.dtype)) @ w["codes"].astype(x.dtype)) \
                * w["t"].astype(x.dtype)
    else:
        y = x @ w.astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding. x: (..., seq, heads, head_dim); positions (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs[None, :]
    cos = jnp.cos(ang)[..., :, None, :]  # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length, dim, dtype=jnp.float32):
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * i / dim)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA / MHA, optional bias, local window, prefix-LM, cross)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Layer-stacked, ring-buffered KV cache: buffer length = window (local
    attn) or max_len (global attn); axis 0 is the layer.

    A row of the buffer holds P = :func:`kv_heads_per_row` heads side by
    side, (n_kv / P, buf, P·hd), so that its minor axis fills the 128
    lanes of a TPU vector (P = 2 at hd 64): a token's K/V is one sublane
    of n_kv·hd / 128 tiles, and the scores and the value sum read each
    row of heads as it lies.  Positions minor instead would spread a
    token over n_kv·hd / 16 tiles."""

    k: jnp.ndarray  # (L, B, n_kv / P, buf, P * hd)
    v: jnp.ndarray  # (L, B, n_kv / P, buf, P * hd)


def kv_heads_per_row(n_kv: int, head_dim: int) -> int:
    """KV heads a cache row holds side by side: as many as fill 128 lanes
    and divide ``n_kv``; 1 at hd ≥ 128."""
    return math.gcd(n_kv, max(1, 128 // head_dim))


def attention_init(key, d_model, n_q, n_kv, head_dim, *, bias=False,
                   out_bias=False, dtype=jnp.float32,
                   stack: Optional[int] = None):
    kg = KeyGen(key)
    return {
        "wq": dense_init(kg(), d_model, n_q * head_dim,
                         axes=("d_model_w", "heads"), bias=bias, dtype=dtype,
                         stack=stack),
        "wk": dense_init(kg(), d_model, n_kv * head_dim,
                         axes=("d_model_w", "kv_heads"), bias=bias,
                         dtype=dtype, stack=stack),
        "wv": dense_init(kg(), d_model, n_kv * head_dim,
                         axes=("d_model_w", "kv_heads"), bias=bias,
                         dtype=dtype, stack=stack),
        "wo": dense_init(kg(), n_q * head_dim, d_model,
                         axes=("heads", "d_model_w"), bias=out_bias,
                         dtype=dtype, stack=stack),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _attn_scores(q, k, scale):
    # q: (B, S, nq, hd), k: (B, T, nkv, hd) with nq = G*nkv
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    scores = jnp.einsum("bsngh,btnh->bngst", qg, k) * scale
    return scores  # (B, nkv, G, S, T)


def _attn_out(scores, v):
    b, nkv, g, s, t = scores.shape
    out = jnp.einsum("bngst,btnh->bsngh", scores, v)
    return out.reshape(b, s, nkv * g * v.shape[-1])


def attention_train(p, x, *, n_q, n_kv, head_dim, rope_theta=10000.0,
                    causal=True, window: Optional[int] = None,
                    prefix_len: Optional[int] = None,
                    kv_x: Optional[jnp.ndarray] = None,
                    positions: Optional[jnp.ndarray] = None,
                    use_rope=True):
    """Full-sequence attention (train / prefill).

    ``kv_x`` switches to cross attention (keys/values from encoder states,
    no causal mask, no rope on cross keys).
    """
    b, s, d = x.shape
    src = x if kv_x is None else kv_x
    t = src.shape[1]
    q = _split_heads(dense(p["wq"], x), n_q, head_dim)
    k = _split_heads(dense(p["wk"], src), n_kv, head_dim)
    v = _split_heads(dense(p["wv"], src), n_kv, head_dim)
    q = logical_shard(q, "batch", "seq", "heads", None)
    k = logical_shard(k, "batch", "seq", "kv_heads", None)
    v = logical_shard(v, "batch", "seq", "kv_heads", None)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if use_rope and kv_x is None:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    scores = _attn_scores(q, k, 1.0 / math.sqrt(head_dim))
    if kv_x is None:
        i = jnp.arange(s)[:, None]
        j = jnp.arange(t)[None, :]
        mask = jnp.ones((s, t), bool)
        if causal:
            mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        if prefix_len is not None:
            mask = mask | (j < prefix_len)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = _attn_out(probs.astype(x.dtype), v)
    out = dense(p["wo"], out)
    return logical_shard(out, "batch", "seq", "d_model")


#: positions a cache write reads back and rewrites around its token: an
#: update one position wide lets TPU layout assignment move the buffer
#: axis, a whole-cache relayout on entry and exit
_PUT_WINDOW = 8


def _put_token(big, new, layer, slot):
    """``big`` (L, B, rows of heads, buf, ·) with each row's token of
    ``new`` (B, 1, n_kv, ·) written at (layer, row, slot): ``slot`` is a
    scalar for every row or a (B,) vector, one per row.  A row whose slot
    lies outside [0, buf) writes nothing.

    Each write is a ``dynamic_update_slice`` of a window of positions read
    from ``big`` with the token selected in (one for all rows at a scalar
    slot, one per row otherwise), so it updates the stack in place."""
    b, buf = big.shape[1], big.shape[3]
    w = min(_PUT_WINDOW, buf)
    new = new.reshape(b, big.shape[2], 1, big.shape[4]).astype(big.dtype)
    blocks = ([(0, b, slot)] if jnp.ndim(slot) == 0
              else [(r, 1, slot[r]) for r in range(b)])
    for row, rows, s in blocks:
        start = jnp.clip(s, 0, buf - w)
        at = (layer, row, 0, start, 0)
        cur = jax.lax.dynamic_slice(big, at, (1, rows, big.shape[2], w,
                                              big.shape[4]))
        hit = (start + jnp.arange(w) == s)[:, None]
        val = jnp.where(hit, new[row:row + rows][None], cur)
        big = jax.lax.dynamic_update_slice(big, val, at)
    return big


def attention_decode(p, x_t, cache: KVCache, layer, pos, *, n_q, n_kv,
                     head_dim, rope_theta=10000.0,
                     window: Optional[int] = None, use_rope=True):
    """Single-token decode of layer ``layer`` against the layer-stacked
    (ring-buffered) cache.

    x_t: (B, 1, d); ``cache`` leaves are (L, B, n_kv / P, buf, P·hd) (see
    :class:`KVCache`), every layer's buffer in one array, and ``layer`` is
    a (traced) int32 index into it.  The new token's K/V is written at
    (layer, row, slot) of the stack, so a layer scan that carries the
    cache updates it in place; the scores and the value sum read layer
    ``layer`` by a dynamic index that XLA fuses into them, so no layer's
    buffer is sliced out or written back.  Returns
    (out, the updated stack).  pos: absolute position of this token —
    either a scalar int32 (lockstep: every batch row sits at the same
    offset) or a (B,) int32 vector (continuous batching, DESIGN.md §9: each
    *slot* carries its own position, so slots at different sequence offsets
    decode in one dispatch).  For local attention the buffer length equals
    the window and indexing is mod-window; entries older than ``window``
    are masked out by recency.
    """
    b = x_t.shape[0]
    from repro.dist.sharding import manual_axis_info
    _ctx = manual_axis_info()
    # Sharded serving (DESIGN.md §13): inside the shard_map body each
    # device holds a contiguous 1/S block of the KV ring buffer (buffer
    # axis over "model").  Slot arithmetic and masking stay GLOBAL; only
    # the write targets the local block, and the layer's K/V are
    # re-assembled by an activation-sized all_gather before the scores.
    kv_sharded = bool(_ctx and _ctx.get("cache_sharded"))
    buf_loc = cache.k.shape[3]
    buf = buf_loc * _ctx["shards"] if kv_sharded else buf_loc
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    with jax.named_scope("attn_proj"):
        q = _split_heads(dense(p["wq"], x_t), n_q, head_dim)
        k_t = _split_heads(dense(p["wk"], x_t), n_kv, head_dim)
        v_t = _split_heads(dense(p["wv"], x_t), n_kv, head_dim)
        posv = pos[:, None] if per_slot else jnp.full((b, 1), pos)
        if use_rope:
            q = rope(q, posv, rope_theta)
            k_t = rope(k_t, posv, rope_theta)
    slot = pos % buf if window is not None else pos
    if kv_sharded:
        # every row writes into the LOCAL block: global slot minus this
        # device's base offset; a row whose slot another device holds
        # falls outside the block and writes nothing
        put_at = slot - jax.lax.axis_index(_ctx["axis"]) * buf_loc
    elif per_slot:
        # a row whose slot is out of range (an idle serving slot stepped
        # past the buffer) writes nothing, never clamped onto live data
        put_at = slot
    else:
        # lockstep keeps dynamic_update_slice's clamp at the buffer's end
        put_at = jnp.minimum(slot, buf - 1)
    with jax.named_scope("kv_cache"):
        k = _put_token(cache.k, k_t, layer, put_at)
        v = _put_token(cache.v, v_t, layer, put_at)
        k = logical_shard(k, "layers", "batch", "kv_heads", None, None)
        v = logical_shard(v, "layers", "batch", "kv_heads", None, None)
    with jax.named_scope("attention"):
        def at_layer(a):
            a = jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            if kv_sharded:
                # reassemble the layer's global ring buffer for the scores
                # — an activation-sized gather (this step's K/V), never
                # weights; shard s holds global slots [s*buf_loc,
                # (s+1)*buf_loc), so the tiled gather reproduces the
                # oracle's buffer ordering
                a = jax.lax.all_gather(a, _ctx["axis"], axis=2, tiled=True)
            return a
        k_l, v_l = at_layer(k), at_layer(v)          # (B, R, buf, P·hd)
        # a row of the cache holds P heads side by side: each query meets
        # its own head's lanes of the row, zeros on the others' (P = 1 at
        # hd ≥ 128), so the scores read every row of heads as it lies
        n_rows, g = k_l.shape[1], n_q // n_kv
        heads = n_kv // n_rows
        eye = jnp.eye(heads, dtype=q.dtype)
        qg = q.reshape(b, n_rows, heads, g, 1, head_dim)
        qz = (qg * eye[:, None, :, None]).reshape(
            b, n_rows, heads, g, heads * head_dim)
        # (B, nkv, G, 1, buf)
        scores = jnp.einsum("brpgx,brtx->brpgt", qz, k_l).reshape(
            b, n_kv, g, 1, buf) * (1.0 / math.sqrt(head_dim))
        idx = jnp.arange(buf)
        if per_slot:
            # (B, buf) mask: every slot masks by ITS OWN position
            if window is not None:
                age = (slot[:, None] - idx[None, :]) % buf
                valid = age < jnp.minimum(pos[:, None] + 1, buf)
            else:
                valid = idx[None, :] <= pos[:, None]
            scores = jnp.where(valid[:, None, None, None, :], scores,
                               -1e30)
        else:
            if window is not None:
                # entry j holds absolute position:
                # j + buf*floor((pos - j)/buf) — valid iff its absolute
                # position ∈ (pos-window, pos]
                age = (slot - idx) % buf
                valid = age < jnp.minimum(pos + 1, buf)
            else:
                valid = idx <= pos
            scores = jnp.where(valid[None, None, None, None, :], scores,
                               -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        out = jnp.einsum("brpgt,brtx->brpgx",
                         probs.astype(x_t.dtype).reshape(
                             b, n_rows, heads, g, buf), v_l)
        # each head keeps its own lanes of the row
        out = jnp.einsum("brpgqh,pq->brpgh", out.reshape(
            b, n_rows, heads, g, heads, head_dim), eye)
        out = out.reshape(b, 1, n_q * head_dim)
    with jax.named_scope("attn_proj"):
        out = dense(p["wo"], out)
    return out, KVCache(k=k, v=v)


def cross_attention_decode(p, x_t, k, v, *, n_q, n_kv, head_dim):
    """Decode-time cross attention against fixed encoder K/V."""
    q = _split_heads(dense(p["wq"], x_t), n_q, head_dim)
    scores = _attn_scores(q, k, 1.0 / math.sqrt(head_dim))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = _attn_out(probs.astype(x_t.dtype), v)
    return dense(p["wo"], out)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, d_model, d_ff, *, gated=True, bias=False,
             dtype=jnp.float32, stack: Optional[int] = None):
    kg = KeyGen(key)
    p = {"w_out": dense_init(kg(), d_ff, d_model, axes=("ff", "d_model_w"),
                             bias=bias, dtype=dtype, stack=stack)}
    if gated:
        p["w_gate"] = dense_init(kg(), d_model, d_ff,
                                 axes=("d_model_w", "ff"), bias=bias,
                                 dtype=dtype, stack=stack)
        p["w_up"] = dense_init(kg(), d_model, d_ff, axes=("d_model_w", "ff"),
                               bias=bias, dtype=dtype, stack=stack)
    else:
        p["w_in"] = dense_init(kg(), d_model, d_ff, axes=("d_model_w", "ff"),
                               bias=bias, dtype=dtype, stack=stack)
    return p


@scoped("mlp")
def mlp(p, x, *, activation="silu"):
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
           "relu2": lambda u: jnp.square(jax.nn.relu(u))}[activation]
    if "w_gate" in p:
        h = act(dense(p["w_gate"], x)) * dense(p["w_up"], x)
    else:
        h = act(dense(p["w_in"], x))
    h = logical_shard(h, "batch", "seq", "ff")
    return dense(p["w_out"], h)


# ---------------------------------------------------------------------------
# MoE (sort-based capacity buffer; experts shard over "model" = EP)
# ---------------------------------------------------------------------------


def moe_init(key, d_model, d_ff, n_experts, *, gated=True, dtype=jnp.float32,
             stack: Optional[int] = None):
    kg = KeyGen(key)
    def ew(shape, axes):
        full = shape if stack is None else (stack,) + shape
        fax = axes if stack is None else ("layers",) + axes
        return Px(_dense_w(kg(), full, 1.0, dtype), fax)
    # NOTE: experts already take the "model" axis (EP) so the ff dim inside
    # an expert stays unsharded; d_model is FSDP-sharded over "data".
    p = {
        "router": dense_init(kg(), d_model, n_experts,
                             axes=("d_model_w", "experts"), dtype=dtype,
                             stack=stack),
        "w_out": ew((n_experts, d_ff, d_model),
                    ("experts", None, "d_model_w")),
    }
    if gated:
        p["w_gate"] = ew((n_experts, d_model, d_ff),
                         ("experts", "d_model_w", None))
        p["w_up"] = ew((n_experts, d_model, d_ff),
                       ("experts", "d_model_w", None))
    else:
        p["w_in"] = ew((n_experts, d_model, d_ff),
                       ("experts", "d_model_w", None))
    return p


def _moe_pack(xt, gates_e, gates_w, n_experts, capacity, top_k):
    """Sort-based dispatch: xt (T, d) → buf (E, C, d) + combine info.

    Routed pairs are sorted by expert (ties keep token order) and each
    lands at its position within its expert's segment; pairs past
    ``capacity`` are dropped."""
    t = xt.shape[0]
    flat_e = gates_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    pos_in_e = jnp.arange(t * top_k) - jnp.searchsorted(
        sorted_e, sorted_e, side="left")
    token_of = order // top_k
    keep = pos_in_e < capacity
    dest = sorted_e * capacity + jnp.where(keep, pos_in_e, 0)
    src = xt[token_of] * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((n_experts * capacity, xt.shape[1]), xt.dtype)
    buf = buf.at[dest].add(src)
    weights = gates_w.reshape(-1)[order]
    return buf.reshape(n_experts, capacity, -1), (token_of, dest, keep,
                                                  weights)


def moe(p, x, *, n_experts, top_k, capacity_factor=1.25, activation="silu",
        router_dtype=jnp.float32):
    """Top-k token-choice MoE with a sort-based capacity buffer.

    Tokens are flattened, routed, sorted by expert, packed into an
    (E, C, d) buffer (EP: E shards over "model", C over "data"), pushed
    through per-expert FFNs as dense einsums (MXU), and combined back with
    router weights.  Over-capacity tokens are dropped (standard GShard
    semantics); capacity_factor controls the slack.
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt @ p["router"]["w"].astype(router_dtype)).astype(router_dtype)
    gates = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_g, top_e = jax.lax.top_k(gates, top_k)                   # (T, k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(t * top_k / n_experts * capacity_factor))
    capacity = max(capacity, top_k)

    buf, (token_of, dest, keep, weights) = _moe_pack(
        xt, top_e, top_g.astype(x.dtype), n_experts, capacity, top_k)
    buf = logical_shard(buf, "experts", "capacity", "d_model")

    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]

    def emm(inp, w):  # (E,C,din) × (E,din,dout), int8/packed-code aware
        if isinstance(w, dict) and "codes" in w:
            if w["codes"].dtype == jnp.uint8:
                # packed-int4 expert payload (E, dout, ceil(din/2)): unpack
                # in-graph (elementwise, fused by XLA into the operand
                # read); synthetic packed experts are escape-free
                assert not (w["codes"].ndim >= 3
                            and w["codes"].shape[-2] in (1, 3)), \
                    "int2/int3 expert leaves unsupported — serve experts " \
                    "≥ 4b (quantize_params_tree promotes them automatically)"
                assert w["esc_row"].shape[-1] == 0, \
                    "packed MoE escapes unsupported; use escape_capacity=0"
                from repro.core.packing import unpack_int4_planar_jnp
                din = inp.shape[-1]
                z = unpack_int4_planar_jnp(w["codes"])[..., :din]
                scaled = inp * w["s"].astype(inp.dtype)[:, None, :]
                out = jnp.einsum("ecd,efd->ecf", scaled, z.astype(inp.dtype))
                return out * w["t"].astype(inp.dtype)[:, None, :]
            scaled = inp * w["s"].astype(inp.dtype)[:, None, :]
            out = jnp.einsum("ecd,edf->ecf", scaled,
                             w["codes"].astype(inp.dtype))
            return out * w["t"].astype(inp.dtype)[:, None, :]
        return jnp.einsum("ecd,edf->ecf", inp, w.astype(inp.dtype))

    if "w_gate" in p:
        h = act(emm(buf, p["w_gate"])) * emm(buf, p["w_up"])
    else:
        h = act(emm(buf, p["w_in"]))
    # experts already occupy "model"; ff stays unsharded inside an expert
    h = logical_shard(h, "experts", "capacity", None)
    out_buf = emm(h, p["w_out"])
    out_buf = out_buf.reshape(n_experts * capacity, d)

    # gather back and combine with gate weights
    gathered = out_buf[dest] * keep[:, None].astype(x.dtype)      # (T*k, d)
    contrib = gathered * weights[:, None]
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(contrib)
    return out.reshape(b, s, d)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embed_init(key, vocab, d_model, dtype=jnp.float32):
    w = (jax.random.normal(key, (vocab, d_model), jnp.float32)
         * 0.02).astype(dtype)
    return {"w": Px(w, ("vocab", "d_model_w"))}


def embed(p, tokens):
    return jnp.take(p["w"], tokens, axis=0)


@scoped("lm_head")
def unembed(p, x, vocab: Optional[int] = None):
    logits = x @ p["w"].astype(x.dtype).T
    logits = logical_shard(logits, "batch", "seq", "vocab")
    if vocab is not None and vocab != logits.shape[-1]:
        logits = logits[..., :vocab]  # drop padded-vocab rows
    return logits
