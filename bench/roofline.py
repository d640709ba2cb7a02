"""Work the model requires, from shapes alone, and the chip's peaks.

Counts are of what the model needs, not of what today's code does: real
rows only (no padding to the kernel's block, no idle serving slots), each
weight read once per call, attention over the live context only.  So a
change that removes waste raises a share and can never push it past 100%.
"""
from __future__ import annotations

import json
from typing import Iterable, Tuple

from spec import BENCH, ModelSpec


def peaks(device_kind: str) -> dict:
    """The chip's peaks by ``device_kind``; an unknown chip is an error."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]


def packed_matmul_call(m: int, k: int, n: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one packed-int4 dequant matmul, x (m, k) bf16 by a
    (n, k) int4 payload with f32 column and row scales, bf16 out."""
    return 2 * m * k * n, n * k // 2 + 4 * (k + n) + 2 * m * k + 2 * m * n


def packed_matmul_work(spec: ModelSpec, dispatches: Iterable, peak: dict):
    """(FLOPs, bytes, least seconds) of the packed matmuls that the given
    engine dispatches require: a dispatch feeds ``tokens`` tokens to each of
    ``rows`` rows, and needs each layer's weights once, as one call per
    matrix and layer over its ``rows * tokens`` real rows (a prefill chunk
    need not read the weights once per token, as a scan of decode steps
    does)."""
    flops = byts = least = 0.0
    for d in dispatches:
        for mat in spec.matrices():
            f, b = packed_matmul_call(d.rows * d.tokens, mat.n_in, mat.n_out)
            flops += spec.layers * f
            byts += spec.layers * b
            least += spec.layers * max(f / peak["bf16_flops_per_s"],
                                       b / peak["hbm_bytes_per_s"])
    return flops, byts, least


def model_flops(spec: ModelSpec, prompt_tokens: int, decode_tokens: int,
                requests: int, attn_ctx: int) -> float:
    """FLOPs the forward passes require: every token through every layer's
    matrices, the LM head once per served token (the last prompt token
    serves the first), and attention scores and values over ``attn_ctx``
    summed context positions."""
    per_token = 2 * spec.layers * spec.layer_params()
    head = 2 * spec.d_model * spec.vocab
    attn = 4 * spec.layers * spec.n_heads * spec.head_dim
    return (per_token * (prompt_tokens + decode_tokens)
            + head * (decode_tokens + requests) + attn * attn_ctx)
