"""Bit-packing round trips (serving storage path)."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (escapes_to_coo, pack_codes, pack_codes_jnp,
                        pack_int4, pack_int4_planar_jnp, unpack_codes,
                        unpack_int4, unpack_int4_planar_jnp)


def test_int4_roundtrip():
    rng = np.random.default_rng(0)
    z = rng.integers(-8, 8, size=(16, 32))
    np.testing.assert_array_equal(unpack_int4(pack_int4(z)), z)


def test_pack_codes_with_escapes():
    rng = np.random.default_rng(1)
    z = rng.integers(-8, 8, size=(8, 10)).astype(np.int64)
    z[3, 4] = 1000
    z[7, 9] = -77
    p = pack_codes(z, nbits=4)
    assert p.escape_idx.size == 2
    np.testing.assert_array_equal(unpack_codes(p), z)


def test_pack_codes_int8():
    rng = np.random.default_rng(2)
    z = rng.integers(-128, 128, size=(9, 7)).astype(np.int64)
    p = pack_codes(z, nbits=8)
    np.testing.assert_array_equal(unpack_codes(p), z)
    assert p.storage_bits_per_entry == 8.0


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), rows=st.integers(1, 32),
       cols=st.integers(1, 33), scale=st.floats(0.5, 50.0))
def test_property_pack_roundtrip(seed, rows, cols, scale):
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal((rows, cols)) * scale).round().astype(np.int64)
    for nbits in (4, 8):
        p = pack_codes(z, nbits=nbits)
        np.testing.assert_array_equal(unpack_codes(p), z)


def test_storage_bits_exact_with_odd_pad():
    """Odd-n int4 payload: the pad nibble column must NOT count as payload,
    and small matrices get uint32 (not int64) escape indices."""
    z = np.zeros((6, 5), np.int64)           # odd n, no escapes
    p = pack_codes(z, nbits=4)
    assert p.payload.shape == (6, 3)          # padded to 6 nibble pairs
    assert p.storage_bits_per_entry == 4.0    # exact — pad excluded
    assert p.escape_idx.dtype == np.uint32
    z[1, 2] = 99
    p2 = pack_codes(z, nbits=4)
    # (payload 144 bits − pad column 24 bits + one uint32+int32 escape) / 30
    assert p2.storage_bits_per_entry == (144 - 24 + 64) / 30


def test_escapes_to_coo_matches_packed_delta():
    rng = np.random.default_rng(7)
    z = rng.integers(-30, 30, size=(12, 9)).astype(np.int64)
    p = pack_codes(z, nbits=4)
    rows, cols, dval = escapes_to_coo(p)
    body = unpack_codes(
        pack_codes(np.clip(z, -8, 7), nbits=4)).astype(np.float64)
    body[rows, cols] += dval
    np.testing.assert_array_equal(body, z)


# ---------------------------------------------------------------------------
# Device-side (jnp) planar layout — the packed serving path
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), rows=st.integers(1, 24),
       cols=st.integers(1, 31), scale=st.floats(0.5, 40.0))
def test_property_device_pack_roundtrip_with_escapes(seed, rows, cols, scale):
    """pack_codes_jnp: planar payload + escape COO reconstructs z exactly."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal((rows, cols)) * scale).round().astype(np.int64)
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z, jnp.int32))
    body = np.asarray(unpack_int4_planar_jnp(payload))[:, :cols]
    body = body.astype(np.float64)
    body[np.asarray(er), np.asarray(ec)] += np.asarray(ev)
    np.testing.assert_array_equal(body, z)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), rows=st.integers(1, 16),
       cols=st.integers(1, 12))
def test_property_device_pack_capacity_padding(seed, rows, cols):
    """Fixed escape_capacity: excess slots are dval=0 no-ops, truth kept."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    z = rng.integers(-40, 40, size=(rows, cols)).astype(np.int64)
    cap = int(((np.clip(z, -8, 7) != z).sum()) + 3)
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z, jnp.int32),
                                         escape_capacity=cap)
    assert er.shape == (cap,) and ev.shape == (cap,)
    body = np.asarray(unpack_int4_planar_jnp(payload))[:, :cols]
    body = body.astype(np.float64)
    np.add.at(body, (np.asarray(er), np.asarray(ec)), np.asarray(ev))
    np.testing.assert_array_equal(body, z)


def test_planar_pack_matches_paired_values():
    """Planar and paired layouts store the same codes, different order."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    z = rng.integers(-8, 8, size=(5, 10))
    planar = np.asarray(unpack_int4_planar_jnp(
        pack_int4_planar_jnp(jnp.asarray(z, jnp.int32))))
    paired = unpack_int4(pack_int4(z))
    np.testing.assert_array_equal(planar, z)
    np.testing.assert_array_equal(paired, z)


# ---------------------------------------------------------------------------
# int3 bit-plane payload (8 codes / 3 bytes — DESIGN.md §10)
# ---------------------------------------------------------------------------


def test_int3_planar_roundtrip():
    import jax.numpy as jnp

    from repro.core import pack_int3_planar_jnp, unpack_int3_planar_jnp
    rng = np.random.default_rng(0)
    z = rng.integers(-4, 4, size=(16, 40))
    pk = pack_int3_planar_jnp(jnp.asarray(z))
    assert pk.shape == (16, 3, 5)          # 8 codes per 3 bytes
    np.testing.assert_array_equal(np.asarray(unpack_int3_planar_jnp(pk)), z)


def test_pack_codes_int3_with_escapes():
    rng = np.random.default_rng(1)
    z = rng.integers(-4, 4, size=(8, 10)).astype(np.int64)
    z[3, 4] = 1000
    z[7, 9] = -77
    p = pack_codes(z, nbits=3)
    assert p.escape_idx.size == 2
    np.testing.assert_array_equal(unpack_codes(p), z)
    rows, cols, dval = escapes_to_coo(p)
    body = unpack_codes(
        pack_codes(np.clip(z, -4, 3), nbits=3)).astype(np.float64)
    body[rows, cols] += dval
    np.testing.assert_array_equal(body, z)


def test_int3_storage_bits_exact_with_pad():
    """8-group pad columns must NOT count as payload: exactly 3 bits/code."""
    z = np.zeros((6, 13), np.int64)           # 13 → padded to 16 columns
    p = pack_codes(z, nbits=3)
    assert p.payload.shape == (6, 3, 2)
    assert p.storage_bits_per_entry == 3.0    # exact — pad excluded
    z[1, 2] = 99
    p2 = pack_codes(z, nbits=3)
    # (payload 6·13·3 bits + one uint32+int32 escape) / 78
    assert p2.storage_bits_per_entry == (6 * 13 * 3 + 64) / 78


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), rows=st.integers(1, 24),
       cols=st.integers(1, 31), scale=st.floats(0.5, 40.0))
def test_property_int3_roundtrip(seed, rows, cols, scale):
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal((rows, cols)) * scale).round().astype(np.int64)
    p = pack_codes(z, nbits=3)
    np.testing.assert_array_equal(unpack_codes(p), z)


# ---------------------------------------------------------------------------
# int2 planar payload (4 codes / byte — DESIGN.md §8)
# ---------------------------------------------------------------------------


def test_int2_planar_roundtrip():
    import jax.numpy as jnp

    from repro.core import pack_int2_planar_jnp, unpack_int2_planar_jnp
    rng = np.random.default_rng(0)
    z = rng.integers(-2, 2, size=(16, 40))
    pk = pack_int2_planar_jnp(jnp.asarray(z))
    assert pk.shape == (16, 1, 10)         # 4 codes/byte, singleton plane
    np.testing.assert_array_equal(np.asarray(unpack_int2_planar_jnp(pk)), z)


def test_pack_codes_int2_with_escapes():
    rng = np.random.default_rng(1)
    z = rng.integers(-2, 2, size=(8, 10)).astype(np.int64)
    z[3, 4] = 1000
    z[7, 9] = -77
    p = pack_codes(z, nbits=2)
    assert p.escape_idx.size == 2
    np.testing.assert_array_equal(unpack_codes(p), z)
    rows, cols, dval = escapes_to_coo(p)
    body = unpack_codes(
        pack_codes(np.clip(z, -2, 1), nbits=2)).astype(np.float64)
    body[rows, cols] += dval
    np.testing.assert_array_equal(body, z)


def test_int2_storage_bits_exact_with_pad():
    """4-group pad columns must NOT count as payload: exactly 2 bits/code."""
    z = np.zeros((6, 13), np.int64)           # 13 → padded to 16 columns
    p = pack_codes(z, nbits=2)
    assert p.payload.shape == (6, 1, 4)
    assert p.storage_bits_per_entry == 2.0    # exact — pad excluded
    z[1, 2] = 99
    p2 = pack_codes(z, nbits=2)
    # (payload 6·13·2 bits + one uint32+int32 escape) / 78
    assert p2.storage_bits_per_entry == (6 * 13 * 2 + 64) / 78


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), rows=st.integers(1, 24),
       cols=st.integers(1, 31), scale=st.floats(0.5, 40.0))
def test_property_int2_roundtrip(seed, rows, cols, scale):
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal((rows, cols)) * scale).round().astype(np.int64)
    p = pack_codes(z, nbits=2)
    np.testing.assert_array_equal(unpack_codes(p), z)


def test_pack_codes_jnp_int2_capacity():
    import jax.numpy as jnp

    from repro.core import unpack_int2_planar_jnp
    rng = np.random.default_rng(3)
    z = rng.integers(-2, 2, size=(5, 9)).astype(np.int64)
    z[2, 7] = 30
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z, jnp.int32), nbits=2,
                                         escape_capacity=4)
    assert payload.shape == (5, 1, 3)
    assert er.shape == (4,)                   # static COO length
    body = np.asarray(unpack_int2_planar_jnp(payload))[:, :9].astype(float)
    body[np.asarray(er), np.asarray(ec)] += np.asarray(ev)
    np.testing.assert_array_equal(body, z)
    import pytest as _pytest
    with _pytest.raises(ValueError):          # undersized capacity rejected
        pack_codes_jnp(jnp.asarray(z, jnp.int32), nbits=2,
                       escape_capacity=0)


def test_pack_codes_jnp_int3_capacity():
    import jax.numpy as jnp

    from repro.core import unpack_int3_planar_jnp
    rng = np.random.default_rng(3)
    z = rng.integers(-4, 4, size=(5, 9)).astype(np.int64)
    z[2, 7] = 30
    payload, er, ec, ev = pack_codes_jnp(jnp.asarray(z, jnp.int32), nbits=3,
                                         escape_capacity=4)
    assert er.shape == (4,)                   # static COO length
    body = np.asarray(unpack_int3_planar_jnp(payload))[:, :9].astype(float)
    body[np.asarray(er), np.asarray(ec)] += np.asarray(ev)
    np.testing.assert_array_equal(body, z)
    import pytest as _pytest
    with _pytest.raises(ValueError):          # undersized capacity rejected
        pack_codes_jnp(jnp.asarray(z, jnp.int32), nbits=3,
                       escape_capacity=0)
