"""Fused dequant-matmul kernel vs pure-jnp oracle (interpret mode sweeps)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import pack_codes_jnp
from repro.kernels.dequant import (dequant_matmul, dequant_matmul_packed,
                                   dequant_matmul_packed_xla,
                                   dequant_matmul_ref, dequant_matmul_xla,
                                   dequantize_ref)


def _case(m, k, n, seed=0, xdtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((m, k)).astype(xdtype)
    z = rng.integers(-8, 8, (n, k)).astype(np.int8)
    s = (rng.random(k) * 0.2 + 0.01).astype(np.float32)
    t = (rng.random(n) + 0.5).astype(np.float32)
    return (jnp.asarray(x), jnp.asarray(z), jnp.asarray(s), jnp.asarray(t))


@pytest.mark.parametrize("m,k,n", [
    (1, 128, 128),       # decode batch 1
    (8, 256, 512),
    (128, 512, 384),
    (130, 300, 200),     # non-aligned: exercises padding
    (64, 1024, 256),
])
def test_matches_oracle_shapes(m, k, n):
    args = _case(m, k, n, seed=m + k + n)
    out = dequant_matmul(*args, interpret=True)
    ref = dequant_matmul_ref(*args)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


@pytest.mark.parametrize("xdtype", [np.float32, jnp.bfloat16])
def test_dtypes(xdtype):
    args = _case(32, 256, 128, seed=7, xdtype=np.float32)
    x = args[0].astype(xdtype)
    out = dequant_matmul(x, *args[1:], interpret=True)
    ref = dequant_matmul_ref(x.astype(jnp.float32), *args[1:])
    scale = float(jnp.abs(ref).max()) + 1e-6
    tol = 2e-2 if xdtype == jnp.bfloat16 else 1e-5
    assert float(jnp.abs(out - ref).max()) / scale < tol


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (128, 256, 512)])
def test_block_shape_sweep(bm, bn, bk):
    args = _case(256, 1024, 512, seed=9)
    out = dequant_matmul(*args, block_m=bm, block_n=bn, block_k=bk,
                         interpret=True)
    ref = dequant_matmul_ref(*args)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_xla_path_matches():
    args = _case(16, 384, 256, seed=11)
    out = dequant_matmul_xla(*args)
    ref = dequant_matmul_ref(*args)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


# ---------------------------------------------------------------------------
# Packed-int4 path (planar payload, in-kernel unpack, escape COO)
# ---------------------------------------------------------------------------


def _packed_case(m, k, n, seed=0, esc=True):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    hi = 12 if esc else 8                 # >7 ⇒ some codes escape int4 range
    z = rng.integers(-hi, hi, (n, k)).astype(np.int32)
    s = jnp.asarray(rng.random(k) * 0.2 + 0.01, jnp.float32)
    t = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z))
    return x, z, s, t, payload, (er, ec, ev)


@pytest.mark.parametrize("m,k,n", [
    (1, 128, 128),       # decode batch 1
    (8, 256, 256),
    (3, 129, 70),        # odd in-features: pad nibble column
    (16, 300, 200),      # non-aligned both dims
])
def test_packed_matches_int8_kernel(m, k, n):
    """Acceptance: packed dispatch ≡ int8 kernel within 1e-5, escapes incl.

    Codes are clipped to the int8 range for the reference, so drawing them
    in [-12, 12) exercises real escapes on the packed side while the int8
    kernel stores them exactly."""
    x, z, s, t, payload, escapes = _packed_case(m, k, n, seed=m + k + n)
    out_i8 = dequant_matmul(x, jnp.asarray(z, jnp.int8), s, t,
                            interpret=True)
    out_p = dequant_matmul(x, payload, s, t, escapes=escapes, interpret=True)
    scale = float(jnp.abs(out_i8).max()) + 1e-6
    assert float(jnp.abs(out_p - out_i8).max()) / scale < 1e-5
    assert escapes[0].shape[0] > 0        # the sweep actually had escapes


def test_packed_dispatches_on_dtype():
    """dequant_matmul routes uint8 payloads to the packed kernel."""
    x, z, s, t, payload, escapes = _packed_case(4, 128, 64, seed=5,
                                                esc=False)
    via_dispatch = dequant_matmul(x, payload, s, t, interpret=True)
    direct = dequant_matmul_packed(x, payload, s, t, interpret=True)
    np.testing.assert_array_equal(np.asarray(via_dispatch),
                                  np.asarray(direct))


def test_packed_xla_path_matches_oracle():
    x, z, s, t, payload, escapes = _packed_case(6, 200, 96, seed=11)
    ref = ((x * s[None, :]) @ jnp.asarray(z, jnp.float32).T) * t[None, :]
    k_even = 2 * payload.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, k_even - x.shape[1])))
    sp = jnp.pad(s, (0, k_even - s.shape[0]))
    out = dequant_matmul_packed_xla(xp, payload, sp, t)
    from repro.kernels.dequant.ops import _apply_escapes
    out = _apply_escapes(out, x, s, t, escapes)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_packed_escape_correction_exact():
    """With escapes applied, packed output equals the FULL-code oracle
    (not the clipped one) — packing loses nothing."""
    x, z, s, t, payload, escapes = _packed_case(5, 160, 80, seed=21)
    ref = ((x * s[None, :]) @ jnp.asarray(z, jnp.float32).T) * t[None, :]
    out = dequant_matmul(x, payload, s, t, escapes=escapes, interpret=True)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_dequantize_matches_quantized_linear():
    """Kernel weight model equals core.QuantizedLinear.dequant (live dims)."""
    from repro.core import CalibStats, watersic_quantize, random_covariance
    rng = np.random.default_rng(3)
    n, a = 48, 32
    sigma, _ = random_covariance(n, condition=10.0, seed=4)
    w = rng.standard_normal((a, n)).astype(np.float32)
    q = watersic_quantize(w, CalibStats(sigma_x=jnp.asarray(sigma, jnp.float32)),
                          0.1, erase_dead=False)
    w_hat_kernel = dequantize_ref(jnp.asarray(q.codes),
                                  jnp.asarray(q.column_scale, jnp.float32),
                                  jnp.asarray(q.t, jnp.float32))
    np.testing.assert_allclose(np.asarray(w_hat_kernel),
                               np.asarray(q.dequant()), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# int3 bit-plane payload (DESIGN.md §8/§10): in-kernel + XLA-twin parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (1, 128, 128),       # decode batch 1
    (8, 120, 96),        # k % 8 == 0
    (5, 67, 96),         # ragged k: pad columns must contribute nothing
])
def test_packed3_matches_int8_path(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    z = rng.integers(-4, 4, (n, k)).astype(np.int8)
    s = jnp.asarray((rng.random(k) * 0.2 + 0.01).astype(np.float32))
    t = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z, jnp.int32), nbits=3)
    assert payload.shape == (n, 3, -(-k // 8))
    assert er.shape[0] == 0              # in-range codes: no escapes
    out = dequant_matmul(x, payload, s, t)
    ref = dequant_matmul_xla(x, jnp.asarray(z), s, t)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_packed3_escape_correction_exact():
    """Codes outside [-4, 3] must be restored exactly by the COO deltas."""
    rng = np.random.default_rng(33)
    m, k, n = 4, 40, 64
    z = rng.integers(-4, 4, (n, k)).astype(np.int32)
    z[0, 3], z[7, 11], z[63, 39] = 21, -9, 3  # 3 in-range: not an escape
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    s = jnp.asarray((rng.random(k) * 0.2 + 0.01).astype(np.float32))
    t = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z), nbits=3)
    assert er.shape[0] == 2
    out = dequant_matmul(x, payload, s, t, escapes=(er, ec, ev))
    ref = jnp.asarray(np.asarray(x) @ (np.asarray(z).T
                                       * np.asarray(s)[:, None])
                      * np.asarray(t)[None, :])
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_packed3_pallas_kernel_matches_xla_twin():
    """Satellite acceptance: the in-kernel Pallas bit-plane unpack (int3)
    is bit-exact vs its XLA reference twin in interpret mode."""
    from repro.kernels.dequant import dequant_matmul_packed3
    rng = np.random.default_rng(17)
    for (m, k, n) in [(2, 128, 64), (4, 61, 48)]:
        z = rng.integers(-4, 4, (n, k)).astype(np.int32)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        s = jnp.asarray(rng.random(k) * 0.2 + 0.01, jnp.float32)
        t = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
        payload, *_ = pack_codes_jnp(jnp.asarray(z), nbits=3)
        out_k = dequant_matmul_packed3(x, payload, s, t, interpret=True)
        out_x = dequant_matmul_packed3(x, payload, s, t,
                                       prefer_pallas=False)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                                   rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("nbits", [2, 3])
def test_from_watersic_subbyte_serving_matches_dequant(nbits):
    """from_watersic(nbits=2/3) leaves through models.layers.dense equal
    the QuantizedLinear dequant oracle — the planner's lowest-rung
    serving formats (escapes restore every out-of-range code)."""
    from repro.core import CalibStats, quantize_at_rate
    from repro.models.layers import dense
    from repro.quant import from_watersic
    rng = np.random.default_rng(5)
    a, nn = 48, 40
    sigma = np.eye(nn) + 0.1 * np.ones((nn, nn))
    w = rng.standard_normal((a, nn)).astype(np.float32)
    q = quantize_at_rate(jnp.asarray(w),
                         CalibStats(sigma_x=jnp.asarray(sigma, jnp.float32)),
                         1.5 if nbits == 2 else 2.5, damp=1e-4)
    leaf = from_watersic(q, nbits=nbits)
    assert leaf["codes"].dtype == jnp.uint8
    if nbits == 2:
        assert leaf["codes"].shape == (a, 1, 10)
    x = jnp.asarray(rng.standard_normal((3, nn)).astype(np.float32))
    y = dense({"w": leaf}, x)
    ref = x @ q.dequant().T
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(y - ref).max()) / scale < 1e-4


# ---------------------------------------------------------------------------
# int2 planar payload (DESIGN.md §8): in-kernel shift/mask unpack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (1, 128, 128),       # decode batch 1
    (8, 120, 96),        # k % 4 == 0
    (5, 67, 96),         # ragged k: pad columns must contribute nothing
])
def test_packed2_matches_int8_path(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    z = rng.integers(-2, 2, (n, k)).astype(np.int8)
    s = jnp.asarray((rng.random(k) * 0.2 + 0.01).astype(np.float32))
    t = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z, jnp.int32), nbits=2)
    assert payload.shape == (n, 1, -(-k // 4))
    assert er.shape[0] == 0              # in-range codes: no escapes
    out = dequant_matmul(x, payload, s, t, interpret=True)
    ref = dequant_matmul(x, jnp.asarray(z), s, t, interpret=True)
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_packed2_escape_correction_exact():
    """Codes outside [-2, 1] must be restored exactly by the COO deltas."""
    rng = np.random.default_rng(33)
    m, k, n = 4, 40, 64
    z = rng.integers(-2, 2, (n, k)).astype(np.int32)
    z[0, 3], z[7, 11], z[63, 39] = 21, -9, 1  # 1 in-range: not an escape
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    s = jnp.asarray((rng.random(k) * 0.2 + 0.01).astype(np.float32))
    t = jnp.asarray((rng.random(n) + 0.5).astype(np.float32))
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z), nbits=2)
    assert er.shape[0] == 2
    out = dequant_matmul(x, payload, s, t, escapes=(er, ec, ev),
                         interpret=True)
    ref = jnp.asarray(np.asarray(x) @ (np.asarray(z).T
                                       * np.asarray(s)[:, None])
                      * np.asarray(t)[None, :])
    scale = float(jnp.abs(ref).max()) + 1e-6
    assert float(jnp.abs(out - ref).max()) / scale < 1e-5


def test_packed2_pallas_kernel_matches_xla_twin():
    """Satellite acceptance: the in-kernel Pallas shift/mask unpack (int2)
    is bit-exact vs its XLA reference twin in interpret mode."""
    from repro.kernels.dequant import dequant_matmul_packed2
    rng = np.random.default_rng(19)
    for (m, k, n) in [(2, 128, 64), (4, 61, 48)]:
        z = rng.integers(-2, 2, (n, k)).astype(np.int32)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        s = jnp.asarray(rng.random(k) * 0.2 + 0.01, jnp.float32)
        t = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
        payload, *_ = pack_codes_jnp(jnp.asarray(z), nbits=2)
        out_k = dequant_matmul_packed2(x, payload, s, t, interpret=True)
        out_x = dequant_matmul_packed2(x, payload, s, t,
                                       prefer_pallas=False)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                                   rtol=1e-6, atol=1e-5)


def test_payload_nbits_discriminates_formats():
    """Shape-encoded dispatch: the three uint8 payload layouts resolve to
    their nbits without out-of-band metadata."""
    from repro.kernels.dequant import payload_nbits
    z = np.zeros((16, 32), np.int32)
    for nbits in (2, 3, 4):
        payload, *_ = pack_codes_jnp(jnp.asarray(z), nbits=nbits)
        assert payload_nbits(payload) == nbits


# ---------------------------------------------------------------------------
# Exact bf16 contraction and shape-chosen blocks (DESIGN.md §8)
# ---------------------------------------------------------------------------


def test_split_bf16_terms_sum_exactly():
    """hi + mid + lo rebuilds every f32 exactly, across magnitudes."""
    from repro.kernels.dequant.dequant_matmul import split_bf16
    rng = np.random.default_rng(23)
    xs = (rng.standard_normal(4096)
          * 10.0 ** rng.integers(-20, 20, 4096)).astype(np.float32)
    terms = split_bf16(jnp.asarray(xs))
    assert terms.shape == (3, 4096) and terms.dtype == jnp.bfloat16
    t = np.asarray(terms.astype(jnp.float32), np.float64)
    np.testing.assert_array_equal(t.sum(0), xs.astype(np.float64))


@pytest.mark.parametrize("m", [1, 8, 130])
@pytest.mark.parametrize("nbits", [2, 3, 4])
def test_packed_kernel_matches_f32_highest(nbits, m):
    """The kernel's three-term bf16 contraction keeps f32 accuracy: it
    matches an f32 ``highest`` reference to rtol 1e-6 (interpret mode),
    over ragged in-features, at decode, prefill and multi-block rows."""
    import jax
    k, n = 301, 200
    lo, hi = -(2 ** (nbits - 1)), 2 ** (nbits - 1)
    rng = np.random.default_rng(100 * nbits + m)
    z = rng.integers(lo, hi, (n, k)).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    s = jnp.asarray(rng.random(k) * 0.2 + 0.01, jnp.float32)
    t = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
    payload, er, _, _ = pack_codes_jnp(jnp.asarray(z), nbits=nbits)
    assert er.shape[0] == 0
    out = np.asarray(dequant_matmul(x, payload, s, t, interpret=True))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(dequant_matmul_ref(x, jnp.asarray(z), s, t))
    np.testing.assert_allclose(out, ref, rtol=1e-6,
                               atol=1e-6 * np.abs(ref).max())


#: (in, out) widths of minicpm-2b (attention, w_gate/w_up, w_out) and of
#: minitron-8b (w_up, w_out)
SERVED_WIDTHS = [(2304, 2304), (2304, 5760), (5760, 2304), (4096, 16384),
                 (16384, 4096)]
#: grid steps one matrix may take at decode rows
MAX_GRID_STEPS = {4: 32, 3: 64, 2: 64}


@pytest.mark.parametrize("m", [1, 5, 8])
@pytest.mark.parametrize("nbits", [2, 3, 4])
@pytest.mark.parametrize("k,n", SERVED_WIDTHS)
def test_packed_blocks_fit_served_shapes(k, n, nbits, m):
    """At the served widths the blocks divide the payload (no per-call
    pad of its byte or row axis), pad the rows only to a multiple of 8,
    and give each matrix a few tens of grid steps."""
    from repro.kernels.dequant.dequant_matmul import (
        PLANE_GROUPS, VMEM_BUDGET, _vmem_bytes, packed_blocks)
    kg = k // PLANE_GROUPS[nbits]
    bm, bn, bkg = packed_blocks(m, kg, n, nbits)
    assert bm == 8
    assert kg % bkg == 0 and (bkg % 128 == 0 or bkg == kg)
    assert n % bn == 0 and (bn % 128 == 0 or bn == n)
    assert (n // bn) * (kg // bkg) <= MAX_GRID_STEPS[nbits]
    assert _vmem_bytes(bm, bn, bkg, nbits) <= VMEM_BUDGET


def test_packed_blocks_rows_and_fallback():
    """Rows: m rounded up to 8, 128 above that.  A byte axis with no
    128-multiple divisor that fits goes whole if it fits, else is padded
    to a 128-multiple and blocked."""
    from repro.kernels.dequant.dequant_matmul import packed_blocks
    assert [packed_blocks(m, 1152, 2304, 4)[0]
            for m in (1, 7, 8, 9, 64, 128, 129, 512)] == [
                8, 8, 8, 16, 64, 128, 128, 128]
    assert packed_blocks(8, 2880, 2304, 4)[2] == 2880     # whole axis
    assert packed_blocks(8, 150, 200, 4)[1:] == (200, 150)
    bm, bn, bkg = packed_blocks(128, 40000 + 8, 2304, 4)  # 8 · 5001
    assert bkg % 128 == 0 and bkg < 40008
