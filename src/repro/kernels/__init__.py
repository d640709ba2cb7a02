"""Pallas TPU kernels for the WaterSIC serving/quantization hot spots.

  dequant/  — fused int8-code dequantize-matmul (decode-time weight-bytes
              bound matmul; the paper's systems payoff on TPU)
  zsic/     — blocked SIC quantizer (in-block recursion in VMEM, trailing
              update on the MXU) — TPU adaptation of GPTQ-style loops
  flash/    — blockwise online-softmax attention (no S×S score
              materialization); no model calls it yet (ROADMAP)

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with padding/dispatch) and ref.py (pure-jnp oracle); tests sweep
shapes/dtypes in interpret mode against the oracle.
"""
from . import dequant, flash, zsic

__all__ = ["dequant", "flash", "zsic"]
