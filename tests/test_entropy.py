"""Entropy estimation + Huffman/codecs (paper §4 Entropy coding, Table 6)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (HuffmanCode, codec_bits_lzma, codec_bits_zlib,
                        column_entropies, effective_rate, empirical_entropy,
                        huffman_bits)


def test_entropy_uniform():
    z = np.arange(16).repeat(100).reshape(40, 40)
    assert abs(empirical_entropy(z) - 4.0) < 1e-9


def test_entropy_degenerate():
    assert empirical_entropy(np.zeros((5, 5), np.int64)) == 0.0


def test_huffman_within_one_bit_of_entropy():
    rng = np.random.default_rng(0)
    z = rng.geometric(0.3, size=(256, 64)) - 1
    h = empirical_entropy(z)
    bits = huffman_bits(z)
    assert h <= bits + 1e-9
    assert bits < h + 1.0  # Huffman redundancy bound


def test_huffman_roundtrip():
    rng = np.random.default_rng(1)
    z = (rng.standard_normal((64, 32)) * 3).round().astype(np.int64)
    hc = HuffmanCode.from_data(z)
    payload, nbits = hc.encode(z)
    dec = hc.decode(payload, nbits, z.size)
    np.testing.assert_array_equal(dec, z.ravel())
    assert nbits == hc.measure_bits(z)


def test_huffman_prefix_free():
    rng = np.random.default_rng(2)
    z = (rng.standard_normal(4096) * 5).round().astype(np.int64)
    hc = HuffmanCode.from_data(z)
    codes = [(format(c, f"0{L}b")) for c, L in hc.codes.values()]
    for i, ci in enumerate(codes):
        for j, cj in enumerate(codes):
            if i != j:
                assert not cj.startswith(ci)
    # Kraft equality for a complete code
    assert abs(sum(2.0 ** -len(c) for c in codes) - 1.0) < 1e-9


def test_single_symbol_alphabet():
    z = np.full((8, 8), 3, np.int64)
    hc = HuffmanCode.from_data(z)
    payload, nbits = hc.encode(z)
    assert nbits == z.size  # 1 bit/symbol degenerate code
    np.testing.assert_array_equal(hc.decode(payload, nbits, z.size), z.ravel())


def test_codecs_close_to_entropy():
    """Table 6: zlib/LZMA bits ≈ entropy + small overhead for iid codes."""
    rng = np.random.default_rng(3)
    z = (rng.standard_normal((512, 256)) * 1.2).round().astype(np.int64)
    h = empirical_entropy(z)
    for codec in (codec_bits_zlib, codec_bits_lzma):
        bpp = codec(z)
        assert bpp > h * 0.9  # can't beat entropy materially
        assert bpp < h + 1.2  # and shouldn't be far above (paper: ~+0.1)


def test_effective_rate_overhead():
    z = np.zeros((100, 50), np.int64)
    z[0, 0] = 1
    r = effective_rate(z)
    assert abs(r - (empirical_entropy(z) + 16 / 100 + 16 / 50)) < 1e-12


def test_column_entropies_shape_and_range():
    rng = np.random.default_rng(4)
    z = (rng.standard_normal((128, 10)) * np.arange(1, 11)).round().astype(int)
    ce = column_entropies(z)
    assert ce.shape == (10,)
    assert (ce[1:] >= ce[:-1] - 0.5).all()  # roughly increasing with scale


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), scale=st.floats(0.1, 10.0),
       rows=st.integers(2, 64), cols=st.integers(1, 16))
def test_property_huffman_roundtrip(seed, scale, rows, cols):
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal((rows, cols)) * scale).round().astype(np.int64)
    hc = HuffmanCode.from_data(z)
    payload, nbits = hc.encode(z)
    np.testing.assert_array_equal(hc.decode(payload, nbits, z.size), z.ravel())
    assert empirical_entropy(z) <= nbits / z.size + 1e-9 <= \
        empirical_entropy(z) + 1.0 + 1e-9
