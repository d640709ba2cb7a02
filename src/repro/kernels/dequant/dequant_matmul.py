"""Fused dequantize-matmul Pallas TPU kernels.

The serving hot spot of WaterSIC-quantized models: weights live in HBM as
int8 ZSIC codes Z (out, in) plus a fused per-column scale s = α⊙γ (the 16/n
overhead of Alg. 3) and per-row scale t (the 16/a overhead).  The effective
weight is  Ŵ[o, i] = t[o]·Z[o, i]·s[i]  and the layer computes

    out[b, o] = Σ_i x[b, i] · Ŵ[o, i]
              = t[o] · Σ_i (x[b, i]·s[i]) · Z[o, i]

Fusing the dequantization into the matmul means the bf16 weight matrix never
round-trips through HBM — at decode batch sizes the matmul is weight-bytes
bound, so int8 codes cut the dominant roofline term ~2× vs bf16, and the
sub-byte variants (``dequant_matmul_packed_pallas``) cut it further: the
kernel streams uint8 planar-packed codes from HBM and unpacks them in-VMEM
(shift/mask/sign-extend for int4/int2, bit-plane reassembly for int3, all
on the VPU) right before the MXU dots, so HBM only ever sees
``nbits/8`` bytes per weight (DESIGN.md §8).  The column scaling is applied
to the *activation tile* (n ops per tile instead of a·n), the row scaling
to the accumulator.

Grid: (M/bm, N/bn, K/bk), K innermost (sequential) with an f32 VMEM
accumulator.  The int8 kernel's blocks are multiples of 128 (ops.py pads
to them).  The packed kernel's blocks are chosen from the shapes
(``packed_blocks``): rows in blocks of m rounded up to 8 (128 above that),
the payload's byte axis whole where it fits VMEM (else a 128-multiple
divisor), out-features in the divisor whose payload block comes nearest
``STEP_BYTES`` — so at served widths the payload is never padded per
call, and a matrix takes a few tens of grid steps.  The packed kernel
contracts over *byte* blocks: every planar layout (core/packing) assigns
byte j's G = 8/nbits codes (8 bit-planes for int3) to columns j, j+K/G,
…, so plane g of the payload block dots against the g-th contiguous
*group* of activation columns — G contiguous MXU dots, no lane
interleave.

The codes (−8..7) are exact in bf16, and ops.py splits x·s once per call
into three bf16 terms (``split_bf16``: 8 + 8 + 8 significant bits, exact)
stacked as rows, so each plane is one bf16 × bf16 MXU dot of 3·bm rows
with exact products and f32 sums: f32 accuracy in one pass over the
weights.  Out-of-range escapes are applied OUTSIDE the kernel as a sparse
COO correction (ops._apply_escapes), keeping the hot loop branch-free.

The int2 payload block squeezes its unit plane axis out; the int3 block
carries its three bit-planes ((bn, 3, bkg)), which on real TPUs ride one
padded (32, 128) uint8 tile — ``packed_blocks`` counts that padding in
VMEM, and the payload stays the smallest HBM operand (3/8 byte a code).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dequant_matmul_pallas", "dequant_matmul_packed_pallas"]


def _kernel(x_ref, z_ref, s_ref, t_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; accumulate over the K grid dimension.

    x_ref: (bm, bk) activations        s_ref: (1, bk) column scales (α⊙γ)
    z_ref: (bn, bk) int8 codes         t_ref: (1, bn) row scales
    o_ref: (bm, bn) output             acc_ref: (bm, bn) f32 VMEM scratch
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = x_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        xs, z, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * t_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret",
                     "out_dtype"))
def dequant_matmul_pallas(x, z, col_scale, row_scale, *,
                          block_m: int = 128, block_n: int = 128,
                          block_k: int = 512, interpret: bool = False,
                          out_dtype=jnp.float32):
    """x (m, k) · dequant(z (n, k), s (k,), t (n,))ᵀ → (m, n).

    All dims must be multiples of the block sizes (ops.py pads).
    """
    m, k = x.shape
    n, k2 = z.shape
    assert k == k2, (x.shape, z.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, block_k), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name="dequant_matmul_int8",
    )(x, z, col_scale.reshape(1, k), row_scale.reshape(1, n))


# ---------------------------------------------------------------------------
# Generalized packed kernel: int4 nibbles / int3 bit-planes / int2 fields
# ---------------------------------------------------------------------------

#: column groups per payload byte-column, by payload nbits
PLANE_GROUPS = {2: 4, 3: 8, 4: 2}
#: bf16 terms of the exact split of an f32 activation (8 + 8 + 8 bits)
SPLIT_TERMS = 3
#: payload bytes one grid step aims to carry
STEP_BYTES = 1 << 20
#: payload bytes one in-kernel unpack chunk carries at most
CHUNK_BYTES = 256 << 10
#: VMEM the blocks of one grid step may take (double-buffered operands,
#: accumulator, one chunk's unpacked planes), and the compiler's limit
VMEM_BUDGET = 16 << 20
VMEM_LIMIT = 32 << 20


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def _lane_blocks(total: int):
    """Block sizes along a lane axis of extent ``total``, ascending: the
    multiples of 128 that divide it, and the whole axis."""
    return sorted({total} | {d for d in range(128, total, 128)
                             if total % d == 0})


def _chunk_rows(block_n: int, block_kg: int) -> int:
    """Payload rows one in-kernel unpack chunk takes: the largest
    multiple of 128 dividing ``block_n`` within ``CHUNK_BYTES``, at least
    128 (the output tile's lane width), or the whole block."""
    if block_n % 128:
        return block_n
    fit = [r for r in range(128, block_n + 1, 128)
           if block_n % r == 0 and r * block_kg <= CHUNK_BYTES]
    return max(fit, default=128)


def _vmem_bytes(bm: int, bn: int, bkg: int, nbits: int) -> int:
    """VMEM one grid step holds, with each block padded to its tiles."""
    g = PLANE_GROUPS[nbits]
    lanes = _round_up(bkg, 128)
    x = g * _round_up(SPLIT_TERMS * bm, 16) * lanes * 2     # bf16 (16, 128)
    p = _round_up(bn, 32) * lanes * (32 if nbits == 3 else 1)
    out = _round_up(bm, 8) * _round_up(bn, 128) * 4
    rows = _chunk_rows(bn, bkg)
    planes = rows * lanes * (8 + 2 * g)  # int32/f32 fields, bf16 planes
    return 2 * (x + p) + 3 * out + planes


def packed_blocks(m: int, kg: int, n: int, nbits: int):
    """``(block_m, block_n, block_kg)`` for ``m`` rows against an ``(n, …,
    kg)`` payload: a pure function of the shapes.

    Rows go in blocks of ``m`` rounded up to 8 (the f32 output's sublane
    multiple), 128 above that.  The byte axis goes whole, else in its
    largest 128-multiple divisor that fits ``VMEM_BUDGET``; out-features
    in the divisor (a multiple of 128, or the whole axis) whose payload
    block comes nearest ``STEP_BYTES`` from below.  Blocks divide the
    payload, so it is never padded per call, unless no divisor fits: then
    the axes are padded to 128-multiples and blocked again.
    """
    bm = min(_round_up(m, 8), 128)
    row_bytes = 3 if nbits == 3 else 1
    for kg_e, n_e in ((kg, n), (_round_up(kg, 128), _round_up(n, 128))):
        for bkg in reversed(_lane_blocks(kg_e)):
            fit = [bn for bn in _lane_blocks(n_e)
                   if _vmem_bytes(bm, bn, bkg, nbits) <= VMEM_BUDGET]
            if fit:
                under = [bn for bn in fit
                         if bn * bkg * row_bytes <= STEP_BYTES]
                return bm, max(under, default=fit[0]), bkg
    raise ValueError(f"no packed blocks fit VMEM: {(m, kg, n, nbits)}")


def _top_bf16(v):
    """``v`` truncated to its 8 leading significant bits: an f32 that
    bf16 holds exactly."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def split_bf16(xs):
    """f32 ``xs`` → ``(3, …)`` bf16 terms whose sum is ``xs`` exactly:
    ``hi`` holds its 8 leading significant bits, ``mid`` the next 8 of
    ``xs − hi``, ``lo = xs − hi − mid`` the last 8.  The terms are cut by
    masking the f32 bits, not by rounding through bf16, so each is exact in
    f32 before its cast: XLA may drop an f32→bf16→f32 round trip as excess
    precision, which would leave ``xs − hi`` zero."""
    hi = _top_bf16(xs)
    rest = xs - hi
    mid = _top_bf16(rest)
    return jnp.stack([hi, mid, rest - mid]).astype(jnp.bfloat16)


def _unpack_planes(bytes_, nbits: int):
    """Payload byte planes (each (rows, bkg) uint8) → G (rows, bkg) bf16
    code planes, exact.

    int4: one byte plane of two nibble fields, int2: one of four 2-bit
    fields, each sign-extended by a left then an arithmetic right shift;
    int3: three bit-plane bytes reassembled into eight biased codes
    (u = code + 4).  All VPU elementwise ops; every code (−8..7) is exact
    in bf16.
    """
    v = [b.astype(jnp.int32) for b in bytes_]
    if nbits == 4:
        fields = [(v[0] << 28) >> 28, (v[0] << 24) >> 28]
    elif nbits == 2:
        fields = [(v[0] << (30 - 2 * g)) >> 30 for g in range(4)]
    else:
        assert nbits == 3, nbits
        fields = [(((v[0] >> g) & 1) | (((v[1] >> g) & 1) << 1)
                   | (((v[2] >> g) & 1) << 2)) - 4 for g in range(8)]
    return [f.astype(jnp.float32).astype(jnp.bfloat16) for f in fields]


def _packed_kernel(x_ref, p_ref, t_ref, o_ref, acc_ref, *, n_k: int,
                   nbits: int, rows: int):
    """One (bm, bn) output tile over a planar sub-byte payload.

    x_ref:  (G, 3·bm, bkg) bf16: plane g's activation columns (x·s), as
            the hi, mid and lo row blocks of their exact split
    p_ref:  the (bn, bkg) uint8 int4 nibbles or int2 fields, or the
            (bn, 3, bkg) int3 bit-planes — code plane g holds column
            group g (planar layouts, core/packing)
    t_ref:  (1, bn) row scales    o_ref: (bm, bn) output
    acc_ref: (bm, bn) f32 VMEM scratch

    Each chunk of ``rows`` payload rows is unpacked to exact bf16 codes
    and contracted once per plane against all three terms stacked as rows:
    one bf16 MXU pass per weight tile, the products exact, the sums f32.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm = acc_ref.shape[0]
    dims = (((1,), (1,)), ((), ()))
    for r in range(0, acc_ref.shape[1], rows):
        p = p_ref[r:r + rows]
        planes = _unpack_planes([p[:, b, :] for b in range(3)] if nbits == 3
                                else [p], nbits)
        part = None
        for g, z in enumerate(planes):
            d = jax.lax.dot_general(x_ref[g], z, dims,
                                    preferred_element_type=jnp.float32)
            part = d if part is None else part + d
        acc_ref[:, r:r + rows] += (part[:bm] + part[bm:2 * bm]
                                   + part[2 * bm:])

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * t_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("nbits", "block_m", "block_n", "block_kg", "interpret",
                     "out_dtype"))
def dequant_matmul_packed_pallas(x_split, payload, row_scale, *,
                                 nbits: int = 4, block_m: int, block_n: int,
                                 block_kg: int, interpret: bool = False,
                                 out_dtype=jnp.float32):
    """Generalized packed fused dequant-matmul (DESIGN.md §8).

    ``x_split`` (m/bm, G, 3·bm, kg) bf16 carries the scaled activations
    x·s pre-split into the G = 8/nbits planar groups (8 for int3) matching
    the payload layout, each row block as its hi, mid and lo terms
    (``split_bf16``); ``payload`` is (n, kg) uint8 for int4, (n, 3, kg)
    for int3 bit-planes, (n, 1, kg) for int2.  All dims must be multiples
    of the block sizes (ops.py chooses them with ``packed_blocks``).  HBM
    reads per grid step: a (bn, bkg) payload block carrying G·bkg codes a
    row — nbits/8 of a byte per weight; the activation block is read once
    per row block and byte block.
    """
    g = PLANE_GROUPS[nbits]
    mb, g2, rows3, kg = x_split.shape
    n = payload.shape[0]
    assert g2 == g and rows3 == SPLIT_TERMS * block_m, (x_split.shape,
                                                         block_m, nbits)
    assert payload.shape[-1] == kg, (x_split.shape, payload.shape)
    if nbits == 4:
        assert payload.ndim == 2, payload.shape
        p_spec = pl.BlockSpec((block_n, block_kg), lambda i, j, kk: (j, kk))
    else:
        assert payload.shape[1:-1] == ({3: 3, 2: 1}[nbits],), payload.shape
        # int2's unit plane axis is squeezed out of the block; int3's
        # three bit-planes ride in one padded (32, 128) uint8 tile
        p_spec = pl.BlockSpec((block_n, 3 if nbits == 3 else None,
                               block_kg), lambda i, j, kk: (j, 0, kk))
    assert n % block_n == 0 and kg % block_kg == 0, (
        (n, kg), (block_n, block_kg))
    n_k = kg // block_kg
    return pl.pallas_call(
        functools.partial(_packed_kernel, n_k=n_k, nbits=nbits,
                          rows=_chunk_rows(block_n, block_kg)),
        grid=(mb, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((None, g, rows3, block_kg),
                         lambda i, j, kk: (i, 0, 0, kk)),
            p_spec,
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mb * block_m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=f"dequant_matmul_packed_int{nbits}",
    )(x_split, payload, row_scale.reshape(1, n))
