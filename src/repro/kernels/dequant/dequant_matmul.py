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
accumulator; MXU dims (bm, bn, bk) are multiples of 128 by construction in
ops.py.  The packed kernel contracts over *byte* blocks: every planar
layout (core/packing) assigns byte j's G = 8/nbits codes (8 bit-planes for
int3) to columns j, j+K/G, …, so plane g of the payload block dots against
the g-th contiguous *group* of activation columns — G contiguous MXU dots,
no lane interleave.  ops.py reshapes x/s to (m, G, kg) so one 3-D block
spec carries all groups of a byte-block step.  Out-of-range escapes are
applied OUTSIDE the kernel as a sparse COO correction
(ops._apply_escapes), keeping the hot loop branch-free.

Payload blocks for int3/int2 carry a small plane axis ((bn, 3, bkg) /
(bn, 1, bkg)); on real TPUs the sublane dim of a uint8 tile is 32, so the
plane axis rides in one padded tile — acceptable because the payload block
is the *smallest* operand by construction (3/8 resp. 1/4 byte per code).
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


def _unpack_planes(p, nbits: int):
    """uint8 payload block → list of G (bn, bkg) f32 code planes.

    int4: two nibble fields (shift/mask/sign-extend); int2: four 2-bit
    fields (same, narrower); int3: three bit-plane bytes reassembled into
    eight biased codes (u = code + 4).  All pure VPU elementwise ops.
    """
    if nbits == 4:
        v = p.astype(jnp.int32)
        return [jnp.where(f > 7, f - 16, f).astype(jnp.float32)
                for f in ((v & 0xF), ((v >> 4) & 0xF))]
    if nbits == 2:
        v = p[:, 0, :].astype(jnp.int32)
        return [jnp.where(f > 1, f - 4, f).astype(jnp.float32)
                for f in (((v >> (2 * g)) & 0x3) for g in range(4))]
    assert nbits == 3, nbits
    b0 = p[:, 0, :].astype(jnp.int32)
    b1 = p[:, 1, :].astype(jnp.int32)
    b2 = p[:, 2, :].astype(jnp.int32)
    return [(((b0 >> g) & 1) | (((b1 >> g) & 1) << 1)
             | (((b2 >> g) & 1) << 2)).astype(jnp.float32) - 4.0
            for g in range(8)]


def _packed_kernel(xg_ref, p_ref, sg_ref, t_ref, o_ref, acc_ref, *,
                   n_k: int, nbits: int):
    """One (bm, bn) output tile over a planar sub-byte payload.

    xg_ref: (bm, G, bkg) activation column groups (G = PLANE_GROUPS[nbits])
    p_ref:  (bn, bkg) uint8 int4 payload, or (bn, 3, bkg) int3 bit-planes,
            or (bn, 1, bkg) int2 fields — plane g holds column group g
            (planar layouts, core/packing)
    sg_ref: (1, G, bkg) column-scale groups    t_ref: (1, bn)
    o_ref:  (bm, bn) output    acc_ref: (bm, bn) f32 VMEM scratch
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    planes = _unpack_planes(p_ref[...], nbits)     # G × (bn, bkg) VPU unpack
    dims = (((1,), (1,)), ((), ()))
    acc = acc_ref[...]
    for g, z in enumerate(planes):
        xs = (xg_ref[:, g, :].astype(jnp.float32)
              * sg_ref[:, g, :].astype(jnp.float32))
        acc += jax.lax.dot_general(xs, z, dims,
                                   preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = (acc_ref[...] * t_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("nbits", "block_m", "block_n", "block_kg", "interpret",
                     "out_dtype"))
def dequant_matmul_packed_pallas(x_groups, payload, s_groups, row_scale, *,
                                 nbits: int = 4, block_m: int = 128,
                                 block_n: int = 128, block_kg: int = 256,
                                 interpret: bool = False,
                                 out_dtype=jnp.float32):
    """Generalized packed fused dequant-matmul (DESIGN.md §8).

    ``x_groups`` (m, G, kg) carries the activation columns pre-split into
    the G = 8/nbits planar groups (8 for int3) matching the payload layout;
    ``payload`` is (n, kg) uint8 for int4, (n, 3, kg) for int3 bit-planes,
    (n, 1, kg) for int2; ``s_groups`` (G, kg) the column-scale groups.
    All dims must be multiples of the block sizes (ops.py splits, pads,
    and re-fuses).  HBM reads per output tile: bkg weight *bytes* per
    (bm, bn) step carrying G·bkg codes — nbits/8 of a byte per weight.
    """
    g = PLANE_GROUPS[nbits]
    m, g2, kg = x_groups.shape
    n = payload.shape[0]
    assert g2 == g and payload.shape[-1] == kg, (x_groups.shape,
                                                 payload.shape, nbits)
    if nbits == 4:
        assert payload.ndim == 2, payload.shape
        p_spec = pl.BlockSpec((block_n, block_kg), lambda i, j, kk: (j, kk))
    else:
        planes = payload.shape[1]
        assert payload.ndim == 3 and planes == {3: 3, 2: 1}[nbits], \
            payload.shape
        p_spec = pl.BlockSpec((block_n, planes, block_kg),
                              lambda i, j, kk: (j, 0, kk))
    assert m % block_m == 0 and n % block_n == 0 and kg % block_kg == 0, (
        (m, n, kg), (block_m, block_n, block_kg))
    n_k = kg // block_kg
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_packed_kernel, n_k=n_k, nbits=nbits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, g, block_kg), lambda i, j, kk: (i, 0, kk)),
            p_spec,
            pl.BlockSpec((1, g, block_kg), lambda i, j, kk: (0, 0, kk)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name=f"dequant_matmul_packed_int{nbits}",
    )(x_groups, payload, s_groups.reshape(1, g, kg),
      row_scale.reshape(1, n))
