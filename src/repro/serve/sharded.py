"""Tensor-parallel k-sharded serving on the host mesh (DESIGN.md §13).

Splits every big-matmul weight leaf along IN-features into the mesh's
``model``-axis shard count and serves the whole decode step under ONE
``shard_map`` per dispatch: each device holds one contiguous in-feature
block of every payload (planar-packed sub-byte codes, int8 codes, or raw
fp), its matching scale slice, and the escape-COO entries whose columns
fall in its block.  The decode path therefore moves NO weight bytes
between devices — the only collectives are the (m, n) activation-partial
all-gathers of the ordered-sum epilogue and the KV-buffer gather of
sharded attention (see ``kernels.dequant.ops.dequant_matmul_sharded``
and ``models.layers.attention_decode``).

The sharded leaf format is tagged by a ``"kshard"`` marker entry whose
SHAPE is the leaf's lead (layer-stack) dims — shape ``(L,)`` for stacked
leaves so ``decode_step``'s layer scan can slice it like every other
leaf, ``()`` for unstacked ones — and whose value is the shard count:

=============  ===============================  ==========================
entry          unsharded                        sharded (S shards)
=============  ===============================  ==========================
codes (int4)   uint8 (L, n, ceil(k/2))          uint8 (L, S, n, kg_loc)
codes (int3)   uint8 (L, n, 3, ceil(k/8))       uint8 (L, S, n, 3, k8_loc)
codes (int2)   uint8 (L, n, 1, ceil(k/4))       uint8 (L, S, n, 1, k4_loc)
codes (int8)   int8  (L, k, n)                  int8  (L, S, k_loc, n)
s              f32   (L, k)                     f32   (L, S, k_loc)
t              f32   (L, n)                     f32   (L, n)   [replicated]
esc_row/col/d  (L, cap)                         (L, S, cap_loc), col LOCAL
w (raw fp)     (L, k, n)                        {"wsh": (L, S, k_loc, n)}
=============  ===============================  ==========================

with ``k_loc = ceil(k/S)``; the last shard's ragged tail is zero-filled
to ``k_loc`` and each shard is then padded to its planar multiple ON ITS
OWN (``core.packing.shard_planar_codes_jnp``) so pad columns never sit
mid-matrix from another shard's point of view.  Zero codes × zero scale
keep every pad column an exact no-op, so the single-device oracle
(``dequant_matmul_sharded`` with ``axis_name=None``) and the mesh path
run the SAME ordered chain-sum over the SAME per-shard partials —
token streams are bit-identical by construction.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.packing import (shard_planar_codes_jnp, unpack_int2_planar_jnp,
                                unpack_int3_planar_jnp, unpack_int4_planar_jnp)
from repro.dist.sharding import manual_axes
from repro.models.transformer import decode_chunk, decode_step
from repro.quant.qlinear import _eligible, is_kshard_qweight, is_qweight

__all__ = ["shard_params_tree", "params_pspecs", "cache_pspecs",
           "build_sharded_decode_fns", "build_sharded_engine",
           "lower_decode_hlo", "integer_allgathers"]

_UNPACK = {2: unpack_int2_planar_jnp, 3: unpack_int3_planar_jnp,
           4: unpack_int4_planar_jnp}


def _payload_nbits(codes) -> int:
    """Planar payload bit-width from the shape tag (see qlinear.leaf_format)."""
    if codes.ndim >= 3 and codes.shape[-2] == 3:
        return 3
    if codes.ndim >= 3 and codes.shape[-2] == 1:
        return 2
    return 4


def _marker(lead: Tuple[int, ...], shards: int) -> jnp.ndarray:
    """The ``kshard`` tag: value = shard count, shape = the leaf's lead
    dims so the layer scan of ``decode_step`` can slice it (a scalar
    marker would break ``jax.lax.scan`` over stacked leaves)."""
    return jnp.full(lead, shards, jnp.int32)


def _shard_scale(s: jnp.ndarray, shards: int, k: int) -> jnp.ndarray:
    """(…, k) → (…, S, k_loc), ragged tail zero-filled (scale 0 ⇒ pad
    columns contribute exactly nothing)."""
    k_loc = -(-k // shards)
    total = shards * k_loc
    if total > k:
        widths = [(0, 0)] * (s.ndim - 1) + [(0, total - k)]
        s = jnp.pad(s, widths)
    return s.reshape(s.shape[:-1] + (shards, k_loc))


def _partition_escapes(er, ec, ev, shards: int, k_loc: int):
    """Split escape-COO arrays (…, cap) by owner shard → (…, S, cap_loc)
    with LOCAL column indices.

    Owner of column c is ``c // k_loc``; its local index ``c % k_loc``.
    Host-side (numpy): sharding runs eagerly at load time.  ``cap_loc``
    is the max per-(lead, shard) population; slack slots carry dval = 0 —
    an exact no-op in the correction matmul, same convention as the
    unsharded capacity padding.
    """
    er = np.asarray(er)
    ec = np.asarray(ec)
    ev = np.asarray(ev)
    lead = er.shape[:-1]
    cap = er.shape[-1]
    n_lead = int(np.prod(lead, dtype=np.int64)) if lead else 1
    er2 = er.reshape(n_lead, cap)
    ec2 = ec.reshape(n_lead, cap)
    ev2 = ev.reshape(n_lead, cap)
    buckets: List[List[Tuple[np.ndarray, np.ndarray, np.ndarray]]] = []
    cap_loc = 0
    for l in range(er2.shape[0]):
        live = ev2[l] != 0
        owner = ec2[l] // k_loc
        row: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        for sh in range(shards):
            pick = live & (owner == sh)
            row.append((er2[l, pick], ec2[l, pick] % k_loc, ev2[l, pick]))
            cap_loc = max(cap_loc, int(pick.sum()))
        buckets.append(row)
    out_r = np.zeros((er2.shape[0], shards, cap_loc), np.int32)
    out_c = np.zeros((er2.shape[0], shards, cap_loc), np.int32)
    out_v = np.zeros((er2.shape[0], shards, cap_loc), np.float32)
    for l, row in enumerate(buckets):
        for sh, (r, c, v) in enumerate(row):
            out_r[l, sh, :len(r)] = r
            out_c[l, sh, :len(c)] = c
            out_v[l, sh, :len(v)] = v
    shape = lead + (shards, cap_loc)
    return (jnp.asarray(out_r.reshape(shape)),
            jnp.asarray(out_c.reshape(shape)),
            jnp.asarray(out_v.reshape(shape)))


def _shard_packed_leaf(leaf: Dict[str, jnp.ndarray], shards: int):
    """Sub-byte planar leaf → kshard leaf: unpack, split true-k blocks,
    per-shard re-pack (pad columns land at each shard's own tail)."""
    s = leaf["s"]
    k = s.shape[-1]
    lead = s.shape[:-1]
    k_loc = -(-k // shards)
    nbits = _payload_nbits(leaf["codes"])
    z = _UNPACK[nbits](leaf["codes"])[..., :k]           # (…, n, k) int8
    z2 = z.reshape((-1,) + z.shape[len(lead):])
    packed = jnp.stack([shard_planar_codes_jnp(z2[i], shards, nbits=nbits)
                        for i in range(z2.shape[0])])
    packed = packed.reshape(lead + packed.shape[1:])     # (…, S, n, …)
    er, ec, ev = _partition_escapes(leaf["esc_row"], leaf["esc_col"],
                                    leaf["esc_dval"], shards, k_loc)
    return {"codes": packed, "s": _shard_scale(s, shards, k), "t": leaf["t"],
            "esc_row": er, "esc_col": ec, "esc_dval": ev,
            "kshard": _marker(lead, shards)}


def _shard_int8_leaf(leaf: Dict[str, jnp.ndarray], shards: int):
    """Int8 code leaf (…, k, n) → (…, S, k_loc, n); zero code rows at the
    ragged tail are exact no-ops (0 · x)."""
    s = leaf["s"]
    k = s.shape[-1]
    lead = s.shape[:-1]
    k_loc = -(-k // shards)
    codes = leaf["codes"]
    total = shards * k_loc
    if total > k:
        widths = [(0, 0)] * (codes.ndim - 2) + [(0, total - k), (0, 0)]
        codes = jnp.pad(codes, widths)
    codes = codes.reshape(codes.shape[:-2] + (shards, k_loc, codes.shape[-1]))
    return {"codes": codes, "s": _shard_scale(s, shards, k), "t": leaf["t"],
            "kshard": _marker(lead, shards)}


def _shard_fp_leaf(w: jnp.ndarray, shards: int):
    """Raw fp weight (…, k, n) → {"wsh": (…, S, k_loc, n), "kshard"}."""
    k = w.shape[-2]
    lead = w.shape[:-2]
    k_loc = -(-k // shards)
    total = shards * k_loc
    if total > k:
        widths = [(0, 0)] * (w.ndim - 2) + [(0, total - k), (0, 0)]
        w = jnp.pad(w, widths)
    w = w.reshape(w.shape[:-2] + (shards, k_loc, w.shape[-1]))
    return {"wsh": w, "kshard": _marker(lead, shards)}


def shard_params_tree(params, shards: int, *, min_dim: int = 64,
                      skip_embed: bool = True):
    """In-feature-shard every big-matmul weight leaf of ``params``.

    Quantized leaves (packed uint8 / int8 codes) become kshard dicts;
    eligible raw fp ``"w"`` leaves become ``{"wsh", "kshard"}`` dicts so
    the fp serving rung shards too.  Everything else — embeds, norms,
    biases, MoE expert stacks (their einsum contraction is not on the
    sharded matmul path), native-s4 leaves — stays replicated.  Leaves
    whose in-feature count is below ``shards`` are left alone: a shard
    with zero true columns serves no purpose.
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")

    def walk(node, path):
        if isinstance(node, dict):
            if is_kshard_qweight(node) or "kshard" in node:
                return node
            if is_qweight(node):
                k = node["s"].shape[-1]
                if k < shards:
                    return node
                if node["codes"].dtype == jnp.uint8:
                    return _shard_packed_leaf(node, shards)
                if node["codes"].dtype == jnp.int8:
                    return _shard_int8_leaf(node, shards)
                return node                      # native-s4: unsupported
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            vals = [walk(v, path + (str(i),)) for i, v in enumerate(node)]
            return vals if isinstance(node, list) else tuple(vals)
        if skip_embed and "embed" in path:
            return node
        if (path and path[-1] == "w" and _eligible(path, node, min_dim)
                and node.shape[-2] >= shards):
            return _shard_fp_leaf(node, shards)
        return node

    return walk(params, ())


# ---------------------------------------------------------------------------
# PartitionSpec builders
# ---------------------------------------------------------------------------

#: kshard-leaf entries that carry the shard axis (at position = lead ndim)
_SHARDED_ENTRIES = ("codes", "wsh", "s", "esc_row", "esc_col", "esc_dval")


def params_pspecs(params, *, axis_name: str = "model"):
    """PartitionSpec tree for a sharded param tree: the shard axis of
    every kshard entry maps to ``axis_name``; everything else (markers,
    row scales, embeds, norms, biases) is replicated."""

    def walk(node):
        if isinstance(node, dict):
            if "kshard" in node:
                nd = node["kshard"].ndim        # lead dims before shard axis
                sharded = P(*([None] * nd + [axis_name]))
                return {k: (sharded if k in _SHARDED_ENTRIES else P())
                        for k in node}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            vals = [walk(v) for v in node]
            return vals if isinstance(node, list) else tuple(vals)
        return P()

    return walk(params)


def cache_pspecs(cache, *, axis_name: str = "model", shards: int):
    """(spec tree, cache_sharded) for a decode cache.

    KV buffers (the 5-D ``(L, B, n_kv / P, buf, P·hd)`` leaves) shard
    their buffer axis over ``axis_name`` when the buffer length divides
    evenly; otherwise the whole cache replicates
    (correct either way — attention gathers the sharded buffer back
    before scoring, see ``models.layers.attention_decode``).
    """
    leaves = [x for x in jax.tree.leaves(cache) if getattr(x, "ndim", 0) == 5]
    sharded = bool(leaves) and all(x.shape[3] % shards == 0 for x in leaves)
    spec = jax.tree.map(
        lambda x: P(None, None, None, axis_name)
        if (sharded and getattr(x, "ndim", 0) == 5) else P(), cache)
    return spec, sharded


# ---------------------------------------------------------------------------
# shard_map'd decode dispatches
# ---------------------------------------------------------------------------


def build_sharded_decode_fns(cfg, params, mesh, *, axis_name: str = "model"):
    """(decode_fn, decode_chunk_fn) running the WHOLE decode step under
    one ``shard_map`` — drop-in for the engines' ``decode_fn`` /
    ``decode_chunk_fn`` ctor hooks.

    ``params`` must already be sharded (``shard_params_tree``) with the
    same shard count as ``mesh.shape[axis_name]``.  The body traces under
    ``dist.sharding.manual_axes`` so ``dense`` / ``attention_decode``
    pick the mesh branch (axis-indexed x block, partial all-gather,
    ordered chain-sum); the single-device oracle is simply the default
    engine dispatch over the SAME sharded tree (no context → local loop
    over the identical per-shard partials).  Compiled dispatches memoize
    on (tag, cache/token shapes) so prefill sub-caches and the slot cache
    each compile once.
    """
    shards = int(mesh.shape[axis_name])
    pspecs = params_pspecs(params, axis_name=axis_name)
    compiled: Dict[Any, Any] = {}

    def make(fn, tag):
        def call(p, cache, tok):
            key = (tag,
                   tuple((x.shape, str(x.dtype)) for x in jax.tree.leaves(
                       cache)),
                   tok.shape)
            hit = compiled.get(key)
            if hit is None:
                # mesh span/metric parity with the single-device engines
                # (DESIGN.md §14): trace-building cost on a cache miss + a
                # per-shape compile counter
                with obs.span("serve.mesh.compile", tag=tag, shards=shards,
                              tok_shape=list(tok.shape)):
                    cspecs, cache_sharded = cache_pspecs(
                        cache, axis_name=axis_name, shards=shards)

                    def body(p_, c_, t_):
                        with manual_axes(axis=axis_name, shards=shards,
                                         cache_sharded=cache_sharded):
                            return fn(cfg, p_, c_, t_)

                    hit = compiled[key] = jax.jit(jax.shard_map(
                        body, mesh=mesh,
                        in_specs=(pspecs, cspecs, P()),
                        out_specs=(P(), cspecs),
                        check_vma=False))
                if obs.enabled():
                    obs.counter("repro_serve_mesh_compile_total",
                                tag=tag).inc()
            if obs.enabled():
                obs.counter("repro_serve_mesh_dispatch_total",
                            tag=tag, shards=str(shards)).inc()
            return hit(p, cache, tok)
        return call

    return make(decode_step, "step"), make(decode_chunk, "chunk")


def build_sharded_engine(cfg, params, mesh, *, config=None,
                         continuous: bool = True,
                         axis_name: str = "model"):
    """Mesh engine through the unified config surface (DESIGN.md §15):
    builds the shard_map decode dispatches and injects them into ONE
    :class:`EngineConfig` via ``dataclasses.replace`` — any
    resilience/quality/requant wiring on the caller's config rides
    along unchanged.  ``params`` must already be sharded
    (:func:`shard_params_tree`)."""
    import dataclasses

    from .config import EngineConfig
    from .engine import ContinuousEngine, ServeEngine
    step_fn, chunk_fn = build_sharded_decode_fns(cfg, params, mesh,
                                                 axis_name=axis_name)
    config = dataclasses.replace(config or EngineConfig(),
                                 decode_fn=step_fn, decode_chunk_fn=chunk_fn)
    cls = ContinuousEngine if continuous else ServeEngine
    return cls(cfg, params, config=config)


# ---------------------------------------------------------------------------
# HLO collective audit — the no-weight-all-gather gate
# ---------------------------------------------------------------------------


def lower_decode_hlo(cfg, params, mesh, cache, token, *,
                     axis_name: str = "model", chunk: bool = False) -> str:
    """Compiled HLO text of one sharded decode dispatch (for
    ``launch.hlo_cost.parse_hlo_costs`` and :func:`integer_allgathers`)."""
    shards = int(mesh.shape[axis_name])
    pspecs = params_pspecs(params, axis_name=axis_name)
    cspecs, cache_sharded = cache_pspecs(cache, axis_name=axis_name,
                                         shards=shards)
    fn = decode_chunk if chunk else decode_step

    def body(p_, c_, t_):
        with manual_axes(axis=axis_name, shards=shards,
                         cache_sharded=cache_sharded):
            return fn(cfg, p_, c_, t_)

    jitted = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(pspecs, cspecs, P()),
                               out_specs=(P(), cspecs), check_vma=False))
    return jitted.lower(params, cache, token).compile().as_text()


def integer_allgathers(hlo_text: str) -> List[str]:
    """HLO all-gather lines whose RESULT is an integer tensor.

    Weight payloads are u8/s8 (s4 for native int4); activations and KV
    partials are floating point — so any integer all-gather on the decode
    path means weight bytes crossed devices, exactly what the k-sharded
    layout promises never happens.  Token/position gathers are s32 and
    tiny; they are excluded by the ``>= 2``-dim filter.
    """
    bad = []
    for line in hlo_text.splitlines():
        if "all-gather" not in line or "=" not in line:
            continue
        rhs = line.split("=", 1)[1].strip()
        dtype = rhs.split("[", 1)[0].strip()
        if dtype in ("u8", "s8", "u4", "s4", "u16", "s16"):
            dims = rhs.split("[", 1)[1].split("]", 1)[0]
            if dims.count(",") >= 1:             # ≥ 2-D: a real payload
                bad.append(line.strip())
    return bad
