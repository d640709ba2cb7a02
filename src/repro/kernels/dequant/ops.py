"""Jit'd public wrapper for the fused dequant-matmul.

``dequant_matmul`` dispatches on the payload dtype and shape: int8 code
matrices go to the int8 kernel; uint8 payloads select the packed kernel
with the payload nbits read off the shape (core/packing layouts) —

    (n, ceil(k/2))        planar int4 nibbles          → nbits=4
    (n, 3, ceil(k/8))     int3 bit-planes              → nbits=3
    (n, 1, ceil(k/4))     planar int2 fields           → nbits=2

All three route through the SAME generalized Pallas kernel
(``dequant_matmul_packed_pallas``), which unpacks in-VMEM and contracts
plane-by-plane — the full 2/3/4-bit serving ladder runs in-kernel
(DESIGN.md §8).  This wrapper zero-pads x/s over the ragged in-features
pad columns of any packed payload, scales the activations once (x·s, as
three exact bf16 terms), splits them into the payload's planar groups,
chooses the blocks from the shapes, dispatches to the Pallas kernels on
TPU (or interpret mode when requested) and to the XLA reference twins
(kernels/dequant/ref.py) on CPU, slices any row padding off, and applies
the sparse escape correction — out-of-range codes stored as a COO delta
list — outside the kernel.

``dequant_matmul_xla`` is the collective-friendly pure-XLA formulation used
inside pjit'd serve graphs (the dry-run path): XLA fuses the int8→f32
convert + scale into the matmul's operand read, preserving the HBM-bytes
advantage that the roofline analysis measures.  The packed XLA siblings
(``dequant_matmul_packed_xla`` / ``_packed3_xla`` / ``_packed2_xla``) are
thin aliases of the ref-twin with the payload nbits pinned.

The packed path's scaling, splitting, padding and slicing run under the
``packed_matmul`` scope, and each Pallas call carries its own kernel
name, so a device trace tells the kernel's own time and its wrapper's
pads from the rest of the step (DESIGN.md §11).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from .dequant_matmul import (PLANE_GROUPS, SPLIT_TERMS,
                             dequant_matmul_packed_pallas,
                             dequant_matmul_pallas, packed_blocks, split_bf16)
from .ref import dequant_matmul_packed_ref, dequant_matmul_ref

__all__ = ["dequant_matmul", "dequant_matmul_packed", "dequant_matmul_xla",
           "dequant_matmul_packed_xla", "dequant_matmul_packed3",
           "dequant_matmul_packed3_xla", "dequant_matmul_packed2",
           "dequant_matmul_packed2_xla", "dequant_matmul_sharded",
           "payload_nbits", "payload_checksums", "verify_payloads"]

def _walk_qweights(tree):
    """(path-string, qweight-dict) pairs in quant.leaf_inventory's path
    vocabulary — integrity checksums, the inventory byte audit, and the
    chaos corruption log all key leaves the same way."""
    from repro.quant import is_qweight  # lazy: avoids an import cycle
    out = []

    def walk(node, path):
        if isinstance(node, dict):
            if is_qweight(node):
                out.append(("/".join(path), node))
                return
            for k, v in node.items():
                walk(v, path + (k,))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (str(i),))

    walk(tree, ())
    return out


def payload_checksums(tree) -> Dict[str, int]:
    """crc32 over every quantized leaf's code payload bytes (DESIGN.md §12).

    The checksum covers the ``codes`` array exactly as stored (packed
    uint8 payloads byte-verbatim, int8 code matrices likewise), keyed by
    the ``quant.leaf_inventory`` path — the integrity baseline the
    serving resilience layer verifies against between dispatches.  A
    single flipped payload byte changes the crc, so silent HBM/host
    corruption of served weights is detectable without dequantizing.
    """
    import zlib

    import numpy as np
    return {path: zlib.crc32(np.ascontiguousarray(
                np.asarray(leaf["codes"])).tobytes())
            for path, leaf in _walk_qweights(tree)}


def verify_payloads(tree, checksums: Dict[str, int]):
    """Paths whose payload crc32 no longer matches ``checksums``.

    Leaves added since the baseline (paths missing from ``checksums``)
    are reported too — a served tree must never grow unchecked payloads.
    Returns a sorted list; empty means the tree is intact.
    """
    current = payload_checksums(tree)
    return sorted(p for p, crc in current.items()
                  if checksums.get(p) != crc)


def payload_nbits(payload) -> int:
    """Payload nbits from the uint8 payload shape (see module docstring).

    The int3/int2 formats carry a plane axis of static size 3/1; a 2-D
    payload is the int4 nibble layout.  Weight matrices have ≥ 2 big dims
    (quant/qlinear `min_dim`), so a genuine out-features of 1 or 3 cannot
    alias the plane axis in practice.
    """
    if payload.ndim >= 3 and payload.shape[-2] == 3:
        return 3
    if payload.ndim >= 3 and payload.shape[-2] == 1:
        return 2
    return 4


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _apply_escapes(out, x, col_scale, row_scale, escapes):
    """out[b, r] += x[b, c]·s[c]·dval·t[r] for each COO escape (r, c, dval).

    ``dval = true_code − clipped_code``, so the correction is exact on top
    of the clipped in-kernel body; duplicate rows accumulate (scatter-add).
    A zero-length COO (the common case) is a static no-op.
    """
    esc_row, esc_col, esc_dval = escapes
    if esc_row.shape[0] == 0:
        return out
    coef = (col_scale[esc_col].astype(jnp.float32)
            * esc_dval.astype(jnp.float32)
            * row_scale[esc_row].astype(jnp.float32))
    contrib = x[:, esc_col].astype(jnp.float32) * coef[None, :]
    return out.at[:, esc_row].add(contrib.astype(out.dtype))


def dequant_matmul(x, z, col_scale, row_scale, *, escapes=None,
                   block_m=None, block_n=None, block_k=None,
                   prefer_pallas: bool = True, interpret: bool = False):
    """x (m, k) · dequant(z, s, t)ᵀ → (m, n), padding + escapes handled here.

    ``z`` int8 (n, k) selects the int8 kernel; a uint8 payload selects the
    packed kernel at the nbits its shape encodes (``payload_nbits``).
    ``escapes`` is an optional COO triple (rows, cols, dvals) applied after
    the kernel.  Block sizes left ``None`` are chosen from the shapes.
    """
    if z.dtype == jnp.uint8:
        return dequant_matmul_packed(
            x, z, col_scale, row_scale, nbits=payload_nbits(z),
            escapes=escapes, block_m=block_m, block_n=block_n,
            block_k=block_k, prefer_pallas=prefer_pallas,
            interpret=interpret)
    return _dequant_matmul_int8(
        x, z, col_scale, row_scale, escapes=escapes,
        block_m=block_m or 128, block_n=block_n or 128,
        block_k=block_k or 512, prefer_pallas=prefer_pallas,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "prefer_pallas", "interpret"))
def _dequant_matmul_int8(x, z, col_scale, row_scale, *, escapes=None,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 512, prefer_pallas: bool = True,
                         interpret: bool = False):
    m, k = x.shape
    n = z.shape[0]
    on_tpu = jax.default_backend() == "tpu"
    if prefer_pallas and (on_tpu or interpret):
        block_k_eff = min(block_k, max(128, k))
        xp = _pad_to(_pad_to(x, block_m, 0), block_k_eff, 1)
        zp = _pad_to(_pad_to(z, block_n, 0), block_k_eff, 1)
        sp = _pad_to(col_scale, block_k_eff, 0)
        tp = _pad_to(row_scale, block_n, 0)
        out = dequant_matmul_pallas(
            xp, zp, sp, tp, block_m=block_m, block_n=block_n,
            block_k=block_k_eff, interpret=interpret or not on_tpu)[:m, :n]
    else:
        out = dequant_matmul_xla(x, z, col_scale, row_scale)
    if escapes is not None:
        out = _apply_escapes(out, x, col_scale, row_scale, escapes)
    return out


def dequant_matmul_packed(x, payload, col_scale, row_scale, *,
                          nbits: int = 4, escapes=None,
                          block_m=None, block_n=None, block_k=None,
                          prefer_pallas: bool = True,
                          interpret: bool = False):
    """Packed serving matmul: x (m, k) × planar sub-byte payload.

    Ragged in-features are handled here: the payload's pad columns hold
    code 0 (or an arbitrary value — see below), and x / col_scale are
    zero-padded to the packed width G·kg before the planar groups are
    split, so every pad column multiplies an all-zero activation column
    and contributes nothing.  The same argument covers a block-align pad
    of the byte axis, which only explicit blocks or the fallback of
    ``packed_blocks`` ask for.  Block sizes left ``None`` are chosen from
    the shapes (``packed_blocks``); ``block_k`` counts columns, G per
    payload byte.
    """
    return _dequant_matmul_packed(
        x, payload, col_scale, row_scale, nbits=nbits, escapes=escapes,
        block_m=block_m, block_n=block_n, block_k=block_k,
        prefer_pallas=prefer_pallas, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("nbits", "block_m", "block_n",
                                             "block_k", "prefer_pallas",
                                             "interpret"))
def _dequant_matmul_packed(x, payload, col_scale, row_scale, *,
                           nbits: int = 4, escapes=None,
                           block_m=None, block_n=None, block_k=None,
                           prefer_pallas: bool = True,
                           interpret: bool = False):
    with jax.named_scope("packed_matmul"):
        g = PLANE_GROUPS[nbits]
        m, k = x.shape
        n, kg = payload.shape[0], payload.shape[-1]
        k_packed = g * kg
        assert k_packed - g < k <= k_packed, (x.shape, payload.shape, nbits)
        xp = _pad_to(x, k_packed, 1) if k < k_packed else x
        sp = _pad_to(col_scale, k_packed, 0) if k < k_packed else col_scale
        on_tpu = jax.default_backend() == "tpu"
        if prefer_pallas and (on_tpu or interpret):
            bm, bn, bkg = packed_blocks(m, kg, n, nbits)
            bm, bn = block_m or bm, block_n or bn
            if block_k:
                bkg = min(max(128, block_k // g), max(128, kg))
            # x·s once per call, over the real rows, as three exact bf16
            # terms; each row block stacks its terms as rows, per group
            # (planar order is group-major, so the grouped view is a
            # reshape — but a byte-axis block pad must land INSIDE each
            # group)
            xs = split_bf16(xp.astype(jnp.float32)
                            * sp.astype(jnp.float32)[None, :])
            xs = _pad_to(xs, bm, 1)
            mb = xs.shape[1] // bm
            xs = xs.reshape(SPLIT_TERMS, mb, bm, g, kg).transpose(
                1, 3, 0, 2, 4).reshape(mb, g, SPLIT_TERMS * bm, kg)
            xs = _pad_to(xs, bkg, -1)
            pp = _pad_to(_pad_to(payload, bn, 0), bkg, -1)
            tp = _pad_to(row_scale, bn, 0)
            out = dequant_matmul_packed_pallas(
                xs, pp, tp, nbits=nbits, block_m=bm, block_n=bn,
                block_kg=bkg, interpret=interpret or not on_tpu)[:m, :n]
        else:
            out = dequant_matmul_packed_ref(xp, payload, sp, row_scale,
                                            nbits=nbits)
        if escapes is not None:
            out = _apply_escapes(out, x, col_scale, row_scale, escapes)
        return out


@jax.jit
def dequant_matmul_xla(x, z, col_scale, row_scale):
    """Scale-the-activations formulation; XLA keeps weights int8 in HBM."""
    xs = x.astype(jnp.float32) * col_scale.astype(jnp.float32)[None, :]
    acc = jax.lax.dot_general(xs, z.astype(jnp.bfloat16).astype(jnp.float32),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return acc * row_scale.astype(jnp.float32)[None, :]


def _chain_sum(stacked):
    """Fixed-order chain sum over the leading axis: s0 + s1 + ... + s_{S-1}.

    The k-sharded matmul's psum epilogue.  An explicit add chain (not
    ``jnp.sum``) so BOTH the single-device oracle loop and the shard_map
    all-gather path reduce the per-shard partials through the identical
    op sequence — XLA never reassociates explicit float adds, which is
    what makes sharded streams bit-identical to the single-device engine.
    """
    acc = stacked[0]
    for i in range(1, stacked.shape[0]):
        acc = acc + stacked[i]
    return acc


def _shard_partial(x_loc, z_s, s_s, t, *, nbits, esc_s, kw):
    """One in-feature shard's (m, n) partial product.

    Per-shard zero-fill happened at pack time (``shard_planar_codes_jnp``:
    every shard's ragged tail carries code 0 / scale 0 at the END of its
    own block), so the single-shard packed path's local padding is exact —
    the global pad-to-``block_k_eff`` that put pad columns mid-matrix on
    all but the last shard never happens.
    """
    if z_s.dtype == jnp.uint8:
        return _dequant_matmul_packed(x_loc, z_s, s_s, t,
                                      nbits=nbits, escapes=esc_s, **kw)
    if z_s.dtype == jnp.int8:
        # scale-the-activations int8 partial; the shared row scale t is
        # applied once, after the chain sum (linear, so exactness holds)
        return (x_loc * s_s.astype(x_loc.dtype)) @ z_s.astype(x_loc.dtype)
    return x_loc @ z_s.astype(x_loc.dtype)   # raw fp shard (k_loc, n)


def dequant_matmul_sharded(x, z, col_scale=None, row_scale=None, *,
                           escapes=None, axis_name=None, shards=None,
                           **kw):
    """k-sharded matmul with an ordered psum epilogue (DESIGN.md §13).

    ``z`` stacks per-shard weight blocks along a leading shard axis:
    uint8 packed payloads ``(S, n, …kg_loc)`` (nbits read off the trailing
    planar shape as usual), int8 code matrices ``(S, k_loc, n)``, or raw
    fp blocks ``(S, k_loc, n)``.  ``col_scale`` is ``(S, k_loc)``,
    ``row_scale`` ``(n,)``, and ``escapes`` an optional COO triple whose
    arrays are ``(S, cap_loc)`` with *local* column indices.  ``x`` is the
    full ``(m, k)`` activation, zero-padded here to ``S·k_loc`` and split
    into contiguous per-shard blocks.

    Two execution modes, bit-identical by construction:

    * ``axis_name=None`` — the single-device oracle: loop the S shards
      locally, stack the partials, chain-sum.
    * ``axis_name="model"`` — inside a ``shard_map`` body: ``z`` et al.
      arrive with a local shard axis of size 1, this device computes ONLY
      its partial, then ``all_gather`` over the axis reproduces the same
      ``(S, m, n)`` stack the oracle built and the same chain sum runs.
      The gather moves the (m, n) *activation* partials — weights never
      cross devices on the decode path.
    """
    if axis_name is None:
        shards = z.shape[0]
    elif shards is None:
        raise ValueError("axis_name given but shards is None — the mesh "
                         "path needs the static shard count (the local z "
                         "block's shard axis is 1)")
    nbits = payload_nbits(z) if z.dtype == jnp.uint8 else None
    k_loc = col_scale.shape[-1] if z.dtype == jnp.uint8 else z.shape[-2]
    m, k = x.shape
    total = shards * k_loc
    xp = _pad_to(x, total, 1) if k < total else x
    xg = xp.reshape(m, shards, k_loc)

    def esc_at(i):
        if escapes is None:
            return None
        er, ec, ev = escapes
        return (er[i], ec[i], ev[i])

    if axis_name is None:
        partials = [
            _shard_partial(xg[:, s, :], z[s],
                           None if col_scale is None else col_scale[s],
                           row_scale, nbits=nbits, esc_s=esc_at(s), kw=kw)
            for s in range(shards)]
        stacked = jnp.stack(partials, axis=0)
    else:
        idx = jax.lax.axis_index(axis_name)
        x_loc = jax.lax.dynamic_index_in_dim(xg, idx, 1, keepdims=False)
        partial = _shard_partial(
            x_loc, z[0], None if col_scale is None else col_scale[0],
            row_scale, nbits=nbits, esc_s=esc_at(0), kw=kw)
        stacked = jax.lax.all_gather(partial, axis_name, axis=0,
                                     tiled=False)
    out = _chain_sum(stacked)
    if z.dtype == jnp.int8:
        out = out * row_scale.astype(out.dtype)
    return out


def dequant_matmul_packed3(x, payload, col_scale, row_scale, *,
                           escapes=None, **kw):
    """Int3 serving matmul: x (m, k) × bit-plane payload (n, 3, ceil(k/8)),
    through the generalized in-kernel bit-plane unpack (DESIGN.md §8)."""
    return dequant_matmul_packed(x, payload, col_scale, row_scale,
                                 nbits=3, escapes=escapes, **kw)


def dequant_matmul_packed2(x, payload, col_scale, row_scale, *,
                           escapes=None, **kw):
    """Int2 serving matmul: x (m, k) × planar field payload
    (n, 1, ceil(k/4)) — ~0.25 B/weight of HBM traffic + escapes."""
    return dequant_matmul_packed(x, payload, col_scale, row_scale,
                                 nbits=2, escapes=escapes, **kw)


def dequant_matmul_packed_xla(x, payload, col_scale, row_scale):
    """Int4 XLA twin (in-graph nibble unpack, fused by XLA).  x and
    col_scale must already span the packed width 2·payload.shape[-1]."""
    return dequant_matmul_packed_ref(x, payload, col_scale, row_scale,
                                     nbits=4)


def dequant_matmul_packed3_xla(x, payload, col_scale, row_scale):
    """Int3 XLA twin (in-graph bit-plane unpack); packed width 8·kg."""
    return dequant_matmul_packed_ref(x, payload, col_scale, row_scale,
                                     nbits=3)


def dequant_matmul_packed2_xla(x, payload, col_scale, row_scale):
    """Int2 XLA twin (in-graph field unpack); packed width 4·kg."""
    return dequant_matmul_packed_ref(x, payload, col_scale, row_scale,
                                     nbits=2)
