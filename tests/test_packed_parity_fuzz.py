"""Packed serving-path parity fuzz across the sub-byte ladder (DESIGN §8).

Property-fuzzes the full packed pipeline for EVERY payload format —
``pack_codes_jnp`` (planar int4 nibbles / int3 bit-planes / int2 fields
+ escape COO export) feeding ``dequant_matmul`` on the uint8 payload,
which routes through the generalized ``dequant_matmul_packed_pallas``
in interpret mode — against the float oracle that materializes the TRUE
(unclipped) codes.  The sweep covers the regimes the kernel's padding
and escape machinery must survive:

  * odd / ragged in_features (the zero pad columns of the 2/4/8-group
    planar layouts must contribute nothing),
  * zero-escape payloads (in-range codes; COO is a static no-op),
  * escape-saturated payloads (a large fraction of out-of-range codes —
    the sparse delta correction carries real signal),
  * degenerate all-equal-code columns (range-edge constants and
    all-zero columns: sign-extension edges and zero-entropy columns),
  * mixed int2/int3/int4 leaves inside ONE served param tree.

CI runs this module as the ``packed-kernel-parity`` matrix job: the
``PACKED_NBITS`` env var pins one payload format per matrix cell (so
each format gets an isolated bit-exactness gate) and ``PACKED_FUZZ_SEED``
adds one matrix-varied seed on top of the in-repo draws.  Locally both
default to "all formats, seed 0".
"""
import os

import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core import pack_codes_jnp
from repro.kernels.dequant import (dequant_matmul, dequant_matmul_packed_ref,
                                   dequant_matmul_ref)

#: nbits → (clip range lo/hi, escape magnitude cap) for case generation
_FMT = {2: (-2, 1, 12), 3: (-4, 3, 20), 4: (-8, 7, 40)}

#: the CI matrix pins one format per job; locally we sweep all three
_NBITS_ENV = os.environ.get("PACKED_NBITS", "")
NBITS_SWEEP = ([int(_NBITS_ENV)] if _NBITS_ENV else sorted(_FMT))
SEED_OFFSET = 31 * int(os.environ.get("PACKED_FUZZ_SEED", "0"))


def _case(m, n, k, seed, esc_frac, degenerate, nbits):
    """True int codes + scales; esc_frac of entries pushed out of range."""
    lo, hi, cap = _FMT[nbits]
    rng = np.random.default_rng(seed + SEED_OFFSET)
    z = rng.integers(lo, hi + 1, (n, k)).astype(np.int32)
    if esc_frac > 0:
        mask = rng.random((n, k)) < esc_frac
        mag = rng.integers(hi + 2, cap, (n, k))
        sign = np.where(rng.random((n, k)) < 0.5, -1, 1)
        z = np.where(mask, sign * mag, z).astype(np.int32)
    if degenerate:
        # constant columns at the field range edges, an interior value,
        # and an all-zero (zero-entropy) column
        for col, val in ((0, hi), (min(1, k - 1), lo), (k // 2, 0)):
            z[:, col] = val
    x = rng.standard_normal((m, k)).astype(np.float32)
    s = (rng.random(k) * 0.2 + 0.01).astype(np.float32)
    t = (rng.random(n) + 0.5).astype(np.float32)
    return x, z, s, t


def _expected_payload_shape(n, k, nbits):
    if nbits == 4:
        return (n, -(-k // 2))
    if nbits == 3:
        return (n, 3, -(-k // 8))
    return (n, 1, -(-k // 4))


def _check(m, n, k, seed, esc_frac, degenerate, nbits):
    x, z, s, t = _case(m, n, k, seed, esc_frac, degenerate, nbits)
    payload, esc_row, esc_col, esc_dval = pack_codes_jnp(jnp.asarray(z),
                                                         nbits=nbits)
    assert payload.dtype == jnp.uint8
    assert payload.shape == _expected_payload_shape(n, k, nbits)
    ref = dequant_matmul_ref(jnp.asarray(x), jnp.asarray(z),
                             jnp.asarray(s), jnp.asarray(t))
    out = dequant_matmul(jnp.asarray(x), payload, jnp.asarray(s),
                         jnp.asarray(t),
                         escapes=(esc_row, esc_col, esc_dval),
                         interpret=True)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5, \
        (m, n, k, seed, esc_frac, degenerate, nbits)
    # XLA reference twin (in-graph unpack) must agree on the clipped body
    # + escapes — the other half of the interpret-mode parity pair
    groups = {4: 2, 3: 8, 2: 4}[nbits]
    k_packed = groups * payload.shape[-1]
    xp = jnp.pad(jnp.asarray(x), ((0, 0), (0, k_packed - k)))
    sp = jnp.pad(jnp.asarray(s), (0, k_packed - k))
    body = dequant_matmul_packed_ref(xp, payload, sp, jnp.asarray(t),
                                     nbits=nbits)
    if esc_row.shape[0]:
        coef = s[np.asarray(esc_col)] * np.asarray(esc_dval) \
            * t[np.asarray(esc_row)]
        corr = np.zeros((m, n), np.float32)
        for r, c, cf in zip(np.asarray(esc_row), np.asarray(esc_col), coef):
            corr[:, r] += x[:, c] * cf
        body = body + corr
    assert float(jnp.abs(body - ref).max()) / scale < 1e-4


@settings(max_examples=8, deadline=None)
@given(m=st.integers(min_value=1, max_value=5),
       n=st.integers(min_value=2, max_value=24),
       k=st.integers(min_value=3, max_value=33),
       seed=st.integers(min_value=0, max_value=10_000),
       esc_mode=st.integers(min_value=0, max_value=2))
def test_packed_kernel_parity_fuzz(m, n, k, seed, esc_mode):
    """Randomized shapes (both k parities forced below) × escape regimes
    × payload formats: 0 = escape-free, 1 = saturated (~30% escapes),
    2 = saturated + degenerate constant/all-zero columns."""
    esc_frac = 0.0 if esc_mode == 0 else 0.3
    degenerate = esc_mode == 2
    for nbits in NBITS_SWEEP:
        # force both parities of k to appear regardless of the draw
        for kk in (k, k + 1):
            _check(m, n, kk, seed, esc_frac, degenerate, nbits)


def test_packed_parity_named_edges():
    """Deterministic corners per format: odd-k escape-free, fully
    saturated rows, and all-columns-degenerate payloads."""
    for nbits in NBITS_SWEEP:
        _check(2, 8, 7, 1, esc_frac=0.0, degenerate=False, nbits=nbits)
        _check(3, 6, 9, 2, esc_frac=0.9, degenerate=False, nbits=nbits)
        _check(1, 4, 5, 3, esc_frac=0.0, degenerate=True, nbits=nbits)
        # every entry escape-saturated AND degenerate columns, odd k
        _check(4, 5, 11, 4, esc_frac=1.0, degenerate=True, nbits=nbits)


def test_int2_all_zero_columns_and_saturation():
    """int2-specific satellite corners: degenerate all-zero columns (the
    payload byte is 0 for four columns at once) and escape-saturated
    columns where EVERY code of a column rides the COO correction."""
    if 2 not in NBITS_SWEEP:
        pytest.skip("int2 not in NBITS_SWEEP (PACKED_NBITS pins another "
                    "format in this CI matrix cell)")
    rng = np.random.default_rng(9 + SEED_OFFSET)
    m, n, k = 3, 10, 21                          # ragged k: 3 pad columns
    z = np.zeros((n, k), np.int32)               # all-zero payload
    z[:, 5] = 17                                 # one fully-escaped column
    z[:, 13] = -11
    x = rng.standard_normal((m, k)).astype(np.float32)
    s = (rng.random(k) * 0.2 + 0.01).astype(np.float32)
    t = (rng.random(n) + 0.5).astype(np.float32)
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z), nbits=2)
    assert int(er.shape[0]) == 2 * n             # two saturated columns
    ref = dequant_matmul_ref(jnp.asarray(x), jnp.asarray(z),
                             jnp.asarray(s), jnp.asarray(t))
    out = dequant_matmul(jnp.asarray(x), payload, jnp.asarray(s),
                         jnp.asarray(t), escapes=(er, ec, ev),
                         interpret=True)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_mixed_format_tree_serves_all_rungs():
    """One param tree mixing int2/int3/int4 leaves serves through the
    engine with per-leaf dispatch, and the engine-reported weight bytes
    match the exact per-leaf storage accounting (ISSUE acceptance)."""
    if _NBITS_ENV:
        pytest.skip("needs all formats — runs in the unpinned (tier1) "
                    "sweep, not the per-format parity matrix cells")
    import jax

    from repro.configs.base import ArchConfig
    from repro.models import init_params, split_tree
    from repro.quant import (leaf_format_histogram, leaf_inventory,
                             quantize_params_tree, qweight_bytes)
    from repro.serve import Request, ServeEngine

    cfg = ArchConfig(name="mixfmt", family="dense", n_layers=3, d_model=64,
                     n_heads=4, n_kv=4, d_ff=128, vocab=128, head_dim=16)
    params, _ = split_tree(init_params(cfg, jax.random.PRNGKey(0)))

    picks = {}

    def nbits_by_path(path):
        # rotate 2/3/4 across eligible leaves; leave the rest fp
        b = (2, 3, 4)[len(picks) % 3]
        picks["/".join(path)] = b
        return b

    mixed = quantize_params_tree(params, min_dim=32,
                                 nbits_by_path=nbits_by_path)
    hist = leaf_format_histogram(mixed)
    assert {"packed-int2", "packed-int3", "packed-int4"} <= set(hist), hist

    qb, fb = qweight_bytes(mixed)
    inv = leaf_inventory(mixed)
    assert sum(r["bytes"] for r in inv) == qb    # exact accounting
    for r in inv:
        if r["format"] == "packed-int2":
            assert r["payload_bytes"] == \
                r["stack"] * r["out"] * (-(-r["in"] // 4))

    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, mixed, n_slots=2, max_len=12, prefill_chunk=3)
    assert eng.weight_bytes == qb                # engine-reported bytes
    for i in range(2):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab, 5)
                           .astype(np.int32), max_new_tokens=3))
    done = eng.run_until_done()
    assert all(len(r.out_tokens) == 3 for r in done)
