"""Pallas TPU kernel for the in-block ZSIC recursion (DESIGN.md §4.1).

GPTQ/ZSIC on GPU walks columns with rank-1 trailing updates.  On TPU we use
the blocked restructuring (core.zsic.zsic_blocked): the *sequential* part —
the SIC recursion inside one 128-column block — runs in this kernel with the
block-diagonal square of L resident in VMEM, tiled over independent row
groups; the *trailing* update is left to XLA as a dense MXU matmul.

For iteration i (from the last in-block column down):

    z_i   = round( y[:, i] / (α_i ℓ_ii) )
    y    -= α_i · z_i ⊗ L[i, :block]

Implementation notes (Mosaic-friendly):
  * the α-scaled L rows (α_i·L[i, :]) are precomputed ONCE into a VMEM
    scratch before the loop; each iteration fetches row i with a dynamic
    sublane slice (``pl.ds``) — O(bn) per iteration instead of the
    O(bn²) masked row selection the loop used to run every step, and the
    working residual lives in a VMEM scratch so the current column is a
    dynamic lane slice (O(bm)) rather than an O(bm·bn) masked reduction,
  * per-column scalars (α_i, step_i) are still selected with iota==i masks
    + O(bn) reductions — dense VPU ops, no dynamic scalar loads,
  * the (bn, bn) L block and the (bm, bn) Y tile live in VMEM; with
    bm = bn = 128 and f32 that is 128 KiB ≪ 16 MiB VMEM,
  * each grid step handles one row tile — rows are independent in Alg. 1, so
    the grid is embarrassingly parallel.

``row_select="masked"`` keeps the legacy all-masked body so
benchmarks/kernels_bench.py can measure the hoisting delta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["zsic_block_pallas"]


def _masked_diag(lblk, bn: int):
    """(1, bn) diagonal of the L block via iota masks (no gather)."""
    return jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1),
        lblk, 0.0), axis=0, keepdims=True)


def _kernel(y_ref, l_ref, alpha_ref, z_ref, resid_ref, acc_ref, sl_ref,
            *, bn: int):
    """Hoisted-row variant (default): O(bn + bm) selections per iteration."""
    lblk = l_ref[...].astype(jnp.float32)        # (bn, bn) lower-triangular
    alpha = alpha_ref[...].astype(jnp.float32)   # (1, bn)
    step = alpha * _masked_diag(lblk, bn)        # (1, bn) α_i·ℓ_ii
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)

    # hoisted: α-scaled L rows, computed once — row i is α_i·L[i, :]
    sl_ref[...] = jnp.swapaxes(alpha, 0, 1) * lblk
    acc_ref[...] = y_ref[...].astype(jnp.float32)

    def body(k, carry):
        i = bn - 1 - k
        cmask = (col_iota == i).astype(jnp.float32)              # (1, bn)
        step_i = jnp.sum(step * cmask)                           # O(bn)
        ycol = acc_ref[:, pl.ds(i, 1)]                           # (bm, 1)
        zcol = jnp.rint(ycol / step_i)
        slrow = sl_ref[pl.ds(i, 1), :]                           # (1, bn)
        acc_ref[...] = acc_ref[...] - zcol * slrow
        z_ref[:, pl.ds(i, 1)] = zcol.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, bn, body, 0)
    resid_ref[...] = acc_ref[...].astype(resid_ref.dtype)


def _kernel_masked(y_ref, l_ref, alpha_ref, z_ref, resid_ref, *, bn: int):
    """Legacy body: masked O(bn²)/O(bm·bn) selections EVERY iteration
    (kept for the hoisting-delta benchmark)."""
    y = y_ref[...].astype(jnp.float32)           # (bm, bn)
    lblk = l_ref[...].astype(jnp.float32)        # (bn, bn)
    alpha = alpha_ref[...].astype(jnp.float32)   # (1, bn)

    col_iota = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    step = alpha * _masked_diag(lblk, bn)

    def body(k, carry):
        y, z = carry
        i = bn - 1 - k
        cmask = (col_iota == i).astype(jnp.float32)              # (1, bn)
        alpha_i = jnp.sum(alpha * cmask)
        step_i = jnp.sum(step * cmask)
        ycol = jnp.sum(y * cmask, axis=1, keepdims=True)         # (bm, 1)
        zcol = jnp.rint(ycol / step_i)
        rmask = (row_iota == i).astype(jnp.float32)
        lrow = jnp.sum(lblk * rmask, axis=0, keepdims=True)      # (1, bn)
        y = y - alpha_i * zcol * lrow
        z = jnp.where(cmask > 0, zcol, z)
        return y, z

    z0 = jnp.zeros_like(y)
    y_fin, z_fin = jax.lax.fori_loop(0, bn, body, (y, z0))
    z_ref[...] = z_fin.astype(jnp.int32)
    resid_ref[...] = y_fin.astype(resid_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "row_select"))
def zsic_block_pallas(y, l_block, alphas, *, block_rows: int = 256,
                      interpret: bool = False, row_select: str = "hoisted"):
    """Quantize one column block.  y (a, bn); l_block (bn, bn); alphas (bn,).

    Returns (codes int32 (a, bn), residual (a, bn)).  ``a`` must be a
    multiple of ``block_rows`` (ops.py pads).  ``row_select`` picks the
    kernel body: "hoisted" (default — L rows precomputed outside the loop)
    or "masked" (legacy per-iteration masked selection, for benchmarking).
    """
    a, bn = y.shape
    assert l_block.shape == (bn, bn)
    assert a % block_rows == 0, (a, block_rows)
    grid = (a // block_rows,)
    if row_select == "hoisted":
        kernel = functools.partial(_kernel, bn=bn)
        scratch = [pltpu.VMEM((block_rows, bn), jnp.float32),
                   pltpu.VMEM((bn, bn), jnp.float32)]
    elif row_select == "masked":
        kernel = functools.partial(_kernel_masked, bn=bn)
        scratch = []
    else:
        raise ValueError(row_select)
    z, resid = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, bn), lambda i: (i, 0)),
            pl.BlockSpec((bn, bn), lambda i: (0, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, bn), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, bn), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((a, bn), jnp.int32),
            jax.ShapeDtypeStruct((a, bn), y.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name=f"zsic_block_{row_select}",
    )(y, l_block, alphas.reshape(1, bn))
    return z, resid
